"""BENCHMARK.json against the limits a benchmark manifest keeps (names,
units, keys, sizes), and every file it names."""
from __future__ import annotations

import json
import re

import pytest

from chipbench.tests.conftest import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
M = json.loads((ROOT / "BENCHMARK.json").read_text())
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
METRIC_KEYS = {"name", "unit", "better", "source"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|per_tok")


def line_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level():
    assert set(M) == TOP_KEYS
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(M["command"]) <= 32 and all(map(line_ok, M["command"]))
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert not p.startswith("/") and (ROOT / p).is_dir()
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_config(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and line_ok(cfg["why"])
    assert line_ok(cfg["source"]) and cfg["file"].startswith("chipbench/")
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in data and not WIDTH.search(key)
    assert any(w["config"] == cfg["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and line_ok(cell["why"])
    assert cell["config"] in {c["name"] for c in M["configs"]}
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (HERE / "traffic" / f"{mix['kind']}.py").is_file()
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    if cfg["summaries"] == "computed":
        assert (HERE / "summaries" / f"{cfg['server']['summary']}.py").is_file()
    from chipbench.run import cell_metrics
    e2e = {m["name"] for m in cell_metrics(M, cell["name"], "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell_metrics(M, cell["name"], "per_layer")


def test_cells_unique():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = ([w["name"] for w in M["workloads"]]
             + [c["name"] for c in M["configs"]])
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("m", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(m):
    per_layer = m in M["per_layer"]
    extra = {"layer", "moves"} if per_layer else {"bound"}
    assert METRIC_KEYS | extra <= set(m) <= METRIC_KEYS | extra | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if per_layer:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line_ok(m["layer"])
        assert m["moves"] in {e["name"] for e in M["end_to_end"]}
    else:
        assert m["source"] in ("device_trace", "host_clock")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in M["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"
        kernel = m["name"][:-len("_roofline")]
        assert (HERE / "kernel_costs" / f"{kernel}.py").is_file()
    from chipbench.run import load_reader
    assert callable(load_reader(m["name"]))
