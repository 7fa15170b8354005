"""Kill-and-resume demo: durable server rounds (DESIGN.md §9).

    PYTHONPATH=src python examples/fl_resume.py
    PYTHONPATH=src python examples/fl_resume.py --rounds 3 --clients 32
    PYTHONPATH=src python examples/fl_resume.py --server async \
        --crash-round 5 --crash-stage SELECT

Runs the same federation three times:

  1. uninterrupted — the reference trace;
  2. durable + fault-injected — ``durability=DurabilityConfig(dir=DIR)``
     journals every committed event to ``DIR/events.jsonl`` and cuts a
     checkpoint at each round boundary, and a ``FaultPlan`` kills the
     server at a chosen ``(round, stage)`` boundary;
  3. resumed — ``api.run(..., resume_from=DIR)`` restores the last
     checkpoint, replays the scenario, and completes the run.

The demo then diffs the resumed trace against the uninterrupted one with
``resume_trace`` — selections, snapshot lineage, sim clock, and accuracy
must match **bitwise** — and exits non-zero if they don't, so CI can run
it as a smoke test.
"""
import argparse
import dataclasses
import os
import sys
import tempfile

import repro.api as api
from repro.checkpoint import read_log
from repro.data.synthetic import FederatedDataset, small_spec
from repro.server.events import Stage
from repro.sim import (
    FaultPlan, PRESET_NAMES, Scenario, ServerKilled, make_scenario,
    resume_trace,
)
from repro.utils.cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mobile-churn",
                    choices=list(PRESET_NAMES))
    ap.add_argument("--server", default="sync", choices=["sync", "async"])
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--crash-round", type=int, default=None,
                    help="round to kill at (default: last round)")
    ap.add_argument("--crash-stage", default="SELECT",
                    choices=[s.name for s in Stage],
                    help="stage boundary to kill at")
    ap.add_argument("--dir", default=None,
                    help="durable directory (default: a temp dir)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    data = FederatedDataset(small_spec(
        num_clients=args.clients, num_classes=5, side=8, avg_samples=24),
        seed=args.seed)
    sc = make_scenario(args.preset, args.clients, seed=args.seed).to_config()
    cfg = api.RunConfig(
        rounds=args.rounds, clients_per_round=8, local_steps=1,
        summary="py", eval_every=max(args.rounds // 3, 1), seed=args.seed,
        clustering=api.ClusteringConfig(num_clusters=4, recluster_every=2),
        server=api.ServerConfig(kind=args.server))
    crash_round = (args.rounds - 1 if args.crash_round is None
                   else args.crash_round)
    crash = (crash_round, Stage[args.crash_stage])

    print(f"=== {args.server} server, {args.preset}, {args.rounds} rounds")
    print("--- run 1: uninterrupted (reference)")
    h0 = api.run(data, cfg, scenario=Scenario.from_config(sc))

    workdir = args.dir or tempfile.mkdtemp(prefix="fl_resume_")
    print(f"--- run 2: durable in {workdir}, killed before round "
          f"{crash[0]} {crash[1].name}")
    durable_cfg = dataclasses.replace(
        cfg, durability=api.DurabilityConfig(dir=workdir))
    try:
        api.run(data, durable_cfg, scenario=Scenario.from_config(sc),
                faults=FaultPlan(crash_points=(crash,)))
        print("    crash point never fired (stage not reached)")
        sys.exit(2)
    except ServerKilled as e:
        print(f"    {e}")
    files = sorted(os.listdir(workdir))
    ckpts = [f for f in files if f.startswith("ckpt_") and
             f.endswith(".npz")]
    print(f"    durable dir: events.jsonl + {len(ckpts)} checkpoint(s)")

    print("--- run 3: resumed from the durable dir")
    h1 = api.run(data, cfg, scenario=Scenario.from_config(sc),
                 resume_from=workdir)

    records = read_log(os.path.join(workdir, "events.jsonl"))
    kinds = [r["type"] for r in records]
    rounds_logged = [r["round"] for r in records if r["type"] == "round"]
    print(f"    log: {len(records)} records "
          f"({kinds.count('event')} events, rounds {rounds_logged}, "
          f"resume markers: {kinds.count('resume')})")

    t0, t1 = resume_trace(h0), resume_trace(h1)
    if t0 == t1:
        print(f"RESUME OK — trace bitwise-identical to the uninterrupted "
              f"run (final acc {h1['final_acc']:.3f}, "
              f"sim time {h1['sim_time'][-1]:.1f})")
    else:
        bad = [k for k in t0 if t0[k] != t1[k]]
        print(f"RESUME MISMATCH in keys: {bad}")
        sys.exit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
