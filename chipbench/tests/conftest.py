"""A toy benchmark root for the CPU tests: a copy of ``chipbench`` with its
manifest, configurations and traffic cut to a size a test holds."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

TOY = {"femnist": dict(num_clients=32, num_classes=6, feature_shape=[8, 8, 1],
                       samples_mean=24, samples_sd=12, max_samples=64,
                       size_levels=8),
       "openimage": dict(num_clients=48, num_classes=12,
                         feature_shape=[8, 8, 3], samples_mean=24,
                         samples_sd=12, max_samples=64)}
MIX = {"kind": "drift", "drift_share": 0.125, "available_share": 0.75,
       "plan_rounds": 10, "warm_rounds": 1}


def make_toy_root(dest: pathlib.Path) -> pathlib.Path:
    """A copy of chipbench with toy fleets; the cells are ``femnist.toy``
    and ``openimage.toy``."""
    shutil.copytree(HERE, dest, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "*.json"))
    (dest / "configs").mkdir(exist_ok=True)
    (dest / "traffic").mkdir(exist_ok=True)
    for name, cut in TOY.items():
        cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
        cfg.update(cut)
        (dest / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (dest / "traffic" / "toy.json").write_text(json.dumps(MIX))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["workloads"] = [
        {"name": f"{c}.toy", "config": c, "traffic": "toy", "chips": 1,
         "why": "toy"} for c in TOY]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    (dest / "BENCHMARK.json").write_text(json.dumps(manifest))
    return dest


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("toy"))
