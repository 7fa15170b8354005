"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV lines:
  * bench_summary     — paper Table 2 (left): summary computation time
  * bench_clustering  — paper Table 2 (right): device clustering time
                        (+ online maintenance vs full recluster, §5)
  * bench_selection   — paper §2 / HACCS: time-to-accuracy of selection
  * bench_kernels     — Pallas kernel hot spots vs oracles
  * bench_shard       — §7 sharded pipeline at 100k–1M clients
  * bench_server      — §8 async server: critical-path overhead sync vs
                        async at fleet scale
  * bench_resume      — §9 durability: checkpoint save/load, event-log
                        append, and kill+resume overhead
  * bench_dryrun      — §Roofline table from dry-run artifacts (if present)
  * bench_obs         — §10 telemetry: enabled-tracer overhead vs the 2%
                        budget + per-hook microcosts
  * bench_policies    — §11 selection-policy tournament: time-to-accuracy
                        + kl-coverage per policy x preset, and the
                        quota-fix demonstration cell
  * bench_frontend    — §12 check-in front end: request-serve latency
                        percentiles + sustained check-ins/sec at 1M
                        clients, and the bounded-queue admission cell

and mirrors every CSV record into a machine-readable ``BENCH.json``
(``--json PATH`` to relocate, ``--no-json`` to disable) so the perf
trajectory is tracked across PRs — and gated against the committed
``BENCH_baseline.json`` by ``benchmarks.check_regression`` in CI.

Each run also **appends** one schema-stamped group-medians record to
``BENCH_history.jsonl`` (``--history PATH`` / ``--no-history``) — an
append-only trajectory across runs, summarized by
``check_regression --trend BENCH_history.jsonl``.  BENCH.json answers
"is this run slower than the committed baseline"; the history answers
"how has each group moved across the last N runs".

Default sizes are CPU-budget-friendly; --full uses paper-scale settings.
"""
from __future__ import annotations

import argparse
import contextlib
import inspect
import io
import json
import sys
import time
import traceback

from benchmarks import (
    bench_clustering,
    bench_compression,
    bench_dryrun,
    bench_frontend,
    bench_kernels,
    bench_obs,
    bench_policies,
    bench_resume,
    bench_selection,
    bench_server,
    bench_shard,
    bench_summary,
    bench_summary_pipeline,
)
from benchmarks._record import SCHEMA_VERSION
from repro.utils.cache import use_compile_cache

BENCHES = (
    ("summary", bench_summary.main),
    ("clustering", bench_clustering.main),
    ("selection", bench_selection.main),
    ("kernels", bench_kernels.main),
    ("pipeline", bench_summary_pipeline.main),
    ("shard", bench_shard.main),
    ("server", bench_server.main),
    ("resume", bench_resume.main),
    ("obs", bench_obs.main),
    ("policies", bench_policies.main),
    ("frontend", bench_frontend.main),
    ("compression", bench_compression.main),
    ("dryrun", bench_dryrun.main),
)


class _Tee(io.TextIOBase):
    """Mirror bench stdout while keeping a copy to parse into JSON."""

    def __init__(self, out):
        self.out = out
        self.captured = io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.captured.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def parse_records(text: str) -> list[dict]:
    """CSV ``name,us_per_call,derived`` lines -> record dicts (comment and
    header lines are skipped; malformed lines are ignored, not fatal)."""
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",", 2)
        if len(parts) < 2:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        records.append({"name": parts[0], "us_per_call": us,
                        "derived": parts[2] if len(parts) > 2 else ""})
    return records


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="paper-scale sizes (slow)")
    p.add_argument("--only", default="",
                   help="comma-separated bench names to run (CI runs "
                        "single groups this way, e.g. --only server)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed for benches with randomized inputs (passed "
                        "to every bench whose main() accepts seed=)")
    p.add_argument("--json", default="BENCH.json",
                   help="machine-readable output path")
    p.add_argument("--no-json", action="store_true",
                   help="skip writing the JSON mirror")
    p.add_argument("--history", default="BENCH_history.jsonl",
                   help="append-only per-run group-medians trajectory")
    p.add_argument("--no-history", action="store_true",
                   help="skip appending the trajectory record")
    args = p.parse_args(argv)
    only = set(filter(None, args.only.split(",")))
    valid = {name for name, _ in BENCHES}
    unknown = only - valid
    if unknown:
        raise ValueError(
            f"unknown bench group(s) {sorted(unknown)}; "
            f"valid groups: {sorted(valid)}")

    from repro.sim import PRESET_NAMES

    print("name,us_per_call,derived")
    failures = []
    # schema history lives with the record format in benchmarks._record
    # (8: frontend/* check-in latency + admission records; 7: policies/*
    # tournament + quota-fix records; 6: obs/* overhead +
    # server/percentiles/* latency-distribution records; 5:
    # server_resume/* durability; 4: async server/*; 3: sharded/*;
    # 2: scenario sweep)
    report: dict = {"schema": SCHEMA_VERSION, "full": bool(args.full),
                    "seed": int(args.seed),
                    "scenario_presets": list(PRESET_NAMES), "benches": {}}
    for name, fn in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        tee = _Tee(sys.stdout)
        ok = True
        kwargs = {"fast": not args.full}
        if "seed" in inspect.signature(fn).parameters:
            kwargs["seed"] = args.seed
        try:
            with contextlib.redirect_stdout(tee):
                fn(**kwargs)
        except Exception:  # noqa: BLE001 — keep the harness running
            failures.append(name)
            ok = False
            traceback.print_exc()
        dt = time.time() - t0
        report["benches"][name] = {
            "ok": ok,
            "seconds": round(dt, 3),
            "records": parse_records(tee.captured.getvalue()),
        }
        print(f"# {name} done in {dt:.1f}s", flush=True)
    report["failures"] = failures
    if not args.no_json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {args.json}", flush=True)
    if not args.no_history:
        # append-only: one group-medians record per harness run, so the
        # per-group trajectory survives across baseline refreshes
        from benchmarks.check_regression import DEFAULT_GROUPS, group_medians
        rec = {"schema": SCHEMA_VERSION,
               "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "full": bool(args.full), "seed": int(args.seed),
               "only": sorted(only) if only else None,
               "groups": {g: round(m, 2) for g, m in
                          group_medians(report, DEFAULT_GROUPS).items()},
               "failures": failures}
        with open(args.history, "a") as f:
            f.write(json.dumps(rec, separators=(",", ":")) + "\n")
        print(f"# appended {args.history}", flush=True)
    if failures:
        print(f"# FAILED: {','.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
