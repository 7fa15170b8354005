"""The command exits non-zero, and prints no result, without a TPU and in
a checkout that holds only the benchmark."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from chipbench.tests.conftest import HERE, ROOT

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run(ROOT, env)
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = run(tmp_path, env)
    assert p.returncode == 2 and p.stdout.strip() == ""
