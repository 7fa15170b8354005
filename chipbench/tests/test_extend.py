"""A configuration, a traffic kind and mix, a summary reference, a kernel
and per-layer metrics are added as new files and entries, with no edit to
a file that is there: the harness finds each by its name."""
from __future__ import annotations

import json
import shutil

import pytest

from chipbench import found, run
from chipbench.peaks import Peaks

GENERATOR = '''"""Traffic kind ``calm``: the drift traffic with every client
available in every round."""
from chipbench.traffic import drift


def make(cfg, mix, seed):
    traffic = drift.make(cfg, mix, seed)
    traffic.available[:] = True
    return traffic
'''
SUMMARY = '''"""Reference of the P(y) summary: the client's label distribution."""
import numpy as np

SAMPLE = 8


def compare(cfg, items, control=False):
    gap = 0.0
    for _r, _c, _images, labels, got in items:
        want = np.bincount(labels, minlength=cfg["num_classes"]) / len(labels)
        if control:
            want = want + 2.0 ** -9
        gap = max(gap, float(np.max(np.abs(got - want))))
    return {"py_gap": (gap, 1e-5)}
'''
KERNEL = '''"""``toy_hist``: one add per element of its first operand."""
import math


def ops(result, operands):
    return math.prod(operands[0][1])
'''
ROOFLINE = '''from chipbench.roofline import kernel_share


def read(obs):
    return kernel_share(obs, "toy_hist")
'''


@pytest.fixture()
def grown(toy_root, tmp_path):
    """The toy root with new files only: configuration ``tinyfleet`` (the
    P(y) summary), traffic kind ``calm`` and mix ``calm``, the summary
    reference ``py``, kernel ``toy_hist`` with its roofline, and metric
    ``rounds_seen``."""
    root = tmp_path / "bench"
    shutil.copytree(toy_root, root)
    cfg = json.loads((root / "configs" / "femnist.json").read_text())
    cfg.update(name="tinyfleet", num_clients=24, size_levels=6)
    cfg["server"] = dict(cfg["server"], summary="py")
    new = {"configs/tinyfleet.json": json.dumps(cfg),
           "traffic/calm.json": json.dumps(
               {"kind": "calm", "drift_share": 0.25, "available_share": 0.5,
                "plan_rounds": 6, "warm_rounds": 1}),
           "traffic/calm.py": GENERATOR,
           "summaries/py.py": SUMMARY,
           "kernel_costs/toy_hist.py": KERNEL,
           "metrics/toy_hist_roofline.py": ROOFLINE,
           "metrics/rounds_seen.py": "def read(obs):\n    return obs['rounds']\n"}
    for path, text in new.items():
        assert not (root / path).exists()
        (root / path).write_text(text)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"].append({"name": "tinyfleet.calm",
                                  "config": "tinyfleet", "traffic": "calm",
                                  "chips": 1, "why": "toy"})
    for name in ("rounds_seen", "toy_hist_roofline"):
        manifest["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels", "moves": "round_s",
            "workloads": ["tinyfleet.calm"]})
    return root, manifest


def test_new_config_traffic_summary_and_metric(grown):
    root, manifest = grown
    res = run.run_cell(manifest, "tinyfleet.calm", 41, 0.3, True,
                       require_tpu=False, root=root, control=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["rounds_seen"]["value"] == res["attempted"] > 0
    assert "toy_hist_roofline" not in res["metrics"]   # no such kernel ran
    assert res["control_checks"]["py_gap"]["value"] > 1e-5


def test_new_kernel_roofline_from_a_trace(grown):
    root, _manifest = grown
    text = ("%toy_hist_kernel.1 = f32[4]{0} custom-call(f32[16]{0} %a), "
            "custom_call_target=\"tpu_custom_call\"")
    obs = {"trace": {"op_texts": {text: (1.0, 1)}},
           "peaks": Peaks(flops=16.0, hbm_bw=1e9), "root": root}
    assert found.module("metrics", "toy_hist_roofline", root).read(obs) == (
        pytest.approx(100.0))
