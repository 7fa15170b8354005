"""Sync vs async selection server on a fleet scenario (DESIGN.md §8).

    PYTHONPATH=src python examples/fl_async.py --preset mobile-churn
    PYTHONPATH=src python examples/fl_async.py --rounds 4 --clients 128 \
        --delay 1 --max-age 2                    # CI quick mode

Runs the same federation twice — ``server="sync"`` (every server stage on
the round-critical path) and ``server="async"`` with the bounded-staleness
refresher — and prints, per round, the server overhead that actually sat
on the critical path, the snapshot age selection read, and the final
accuracy/clock, so the pipelining win (and its staleness cost) is visible
side by side.
"""
import argparse

import numpy as np

import repro.api as api
from repro.data.synthetic import FederatedDataset, small_spec
from repro.sim import DATA_HINTS, PRESET_NAMES, Scenario, make_scenario
from repro.utils.cache import use_compile_cache


def run_one(server: str, data, sc_config: dict, args) -> dict:
    is_async = server == "async"
    cfg = api.RunConfig(
        rounds=args.rounds, clients_per_round=8,
        local_steps=args.local_steps, summary=args.summary,
        refresh_kl=0.05, eval_every=max(args.rounds // 4, 1),
        seed=args.seed,
        clustering=api.ClusteringConfig(kind=args.clustering,
                                        num_clusters=6, recluster_every=4),
        server=api.ServerConfig(
            kind=server,
            refresh="staleness" if is_async else "sync",
            ingest_delay_rounds=args.delay,
            snapshot_max_age=args.max_age,
            drift_mass_trigger=args.drift_mass,
            frontend=api.FrontendConfig(
                kind=args.frontend if is_async else "none")))
    return api.run(data, cfg, scenario=Scenario.from_config(sc_config))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mobile-churn",
                    choices=list(PRESET_NAMES))
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--clients", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--summary", default="py",
                    choices=["py", "pxy", "encoder"])
    ap.add_argument("--clustering", default="kmeans",
                    choices=["kmeans", "minibatch", "online",
                             "hierarchical"])
    ap.add_argument("--delay", type=int, default=1,
                    help="async ingest latency (rounds)")
    ap.add_argument("--max-age", type=int, default=3,
                    help="async snapshot staleness bound (rounds)")
    ap.add_argument("--drift-mass", type=float, default=0.05,
                    help="async background-refresh trigger")
    ap.add_argument("--frontend", default="none",
                    choices=["none", "poisson"],
                    help="async check-in front end (DESIGN.md §12)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    alpha = DATA_HINTS[args.preset].get("alpha", 0.5)
    data = FederatedDataset(small_spec(
        num_clients=args.clients, num_classes=8, side=10, avg_samples=48,
        num_styles=4, alpha=alpha), seed=args.seed)
    sc_config = make_scenario(args.preset, args.clients,
                              seed=args.seed).to_config()

    runs = {s: run_one(s, data, sc_config, args) for s in ("sync", "async")}

    print(f"\n=== {args.preset}  ({args.clustering} clustering, "
          f"delay={args.delay}r, max_age={args.max_age}r)")
    print("          ---- overhead on critical path (ms) ----")
    print("  rnd      sync     async   snap_age  snap_ver   acc(s/a)")
    step = max(args.rounds // 8, 1)
    hs, ha = runs["sync"], runs["async"]
    for r in range(0, args.rounds, step):
        print(f"  {r:3d}  {hs['overhead_critical_s'][r] * 1e3:8.2f}  "
              f"{ha['overhead_critical_s'][r] * 1e3:8.2f}  "
              f"{ha['snapshot_age'][r]:8d}  {ha['snapshot_version'][r]:8d}"
              f"   {hs['acc'][r]:.3f}/{ha['acc'][r]:.3f}")
    crit_sync = float(np.sum(hs["overhead_critical_s"]))
    crit_async = float(np.sum(ha["overhead_critical_s"]))
    srv = ha["server"]
    ratio = (f"{crit_sync / crit_async:.1f}x less on-path"
             if crit_async > 1e-6 else "all overhead off-path")
    print(f"  total critical overhead: sync {crit_sync * 1e3:.1f}ms  "
          f"async {crit_async * 1e3:.1f}ms  ({ratio})")
    print(f"  async background: {srv['background_s'] * 1e3:.1f}ms across "
          f"{srv['background_refreshes']} refreshes "
          f"({srv['blocking_refreshes']} blocking), "
          f"{srv['snapshots_published']} snapshots, "
          f"{srv['events']} events")
    fe = srv.get("frontend")
    if fe:
        p99 = max(ha["checkin_p99_s"]) if ha["checkin_p99_s"] else 0.0
        print(f"  check-in front end: {fe['checkins']} check-ins, "
              f"{fe['shed']} shed, {fe['slo_breaches']} SLO breaches, "
              f"worst round p99 {p99 * 1e3:.3f}ms")
    print(f"  final acc  sync {hs['final_acc']:.3f}  "
          f"async {ha['final_acc']:.3f}   "
          f"sim time  sync {hs['sim_time'][-1]:.1f}  "
          f"async {ha['sim_time'][-1]:.1f}")


if __name__ == "__main__":
    use_compile_cache()
    main()
