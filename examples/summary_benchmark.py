"""Reproduce paper Table 2 at laptop scale.

    PYTHONPATH=src python examples/summary_benchmark.py [--full]

Times the three distribution-summary methods and both clustering pipelines
on FEMNIST-like / OpenImage-like synthetic federations and prints the
speedup ratios the paper reports (30× summary, 360× clustering at full
scale; the scaled-down ratios here are the same asymptotics measured
honestly — see EXPERIMENTS.md for the full-scale extrapolation).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import bench_clustering, bench_summary  # noqa: E402
from repro.utils.cache import use_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print("== summary time (paper Table 2 left) ==")
    bench_summary.main(fast=not args.full)
    print("\n== clustering time (paper Table 2 right) ==")
    bench_clustering.main(fast=not args.full)


if __name__ == "__main__":
    use_compile_cache()
    main()
