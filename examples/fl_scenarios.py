"""Federated training under heterogeneous fleet scenarios (DESIGN.md §6).

    PYTHONPATH=src python examples/fl_scenarios.py --preset mobile-churn
    PYTHONPATH=src python examples/fl_scenarios.py --all --rounds 2 \
        --clients 256                       # CI quick mode

Each preset models a different system-heterogeneity regime (churn,
diurnal availability, stragglers with round deadlines, label drift); the
round loop reports how selection coverage, summary overhead, and dropped
clients respond.  ``--clustering``/``--server`` pick a cell of the
support matrix (kmeans/minibatch/online/dbscan/hierarchical x sync/async —
``examples/fl_async.py`` compares the two servers side by side).
"""
import argparse

import numpy as np

import repro.api as api
from repro.data.synthetic import FederatedDataset, small_spec
from repro.sim import DATA_HINTS, PRESET_NAMES, make_scenario
from repro.utils.cache import use_compile_cache


def run_preset(preset: str, args) -> dict:
    alpha = DATA_HINTS[preset].get("alpha", 0.5)
    data = FederatedDataset(small_spec(
        num_clients=args.clients, num_classes=8, side=10, avg_samples=48,
        num_styles=4, alpha=alpha), seed=args.seed)
    scenario = make_scenario(preset, args.clients, seed=args.seed)
    cfg = api.RunConfig(
        rounds=args.rounds, clients_per_round=8,
        local_steps=args.local_steps, summary=args.summary,
        coreset_k=32, refresh_kl=0.05,
        eval_every=max(args.rounds // 4, 1), seed=args.seed,
        clustering=api.ClusteringConfig(kind=args.clustering,
                                        num_clusters=6, recluster_every=4),
        server=api.ServerConfig(kind=args.server))
    h = api.run(data, cfg, scenario=scenario)

    print(f"\n=== {preset}  ({args.clustering} clustering, "
          f"{args.server} server)")
    print("  rnd   acc  sim_time  active  join/dep  dropped  kl_cov")
    step = max(args.rounds // 8, 1)
    for r in range(0, args.rounds, step):
        print(f"  {r:3d}  {h['acc'][r]:.3f}  {h['sim_time'][r]:8.1f}  "
              f"{h['n_active'][r]:6d}  {h['n_joined'][r]:3d}/"
              f"{h['n_departed'][r]:<3d}  {h['dropped'][r]:7d}  "
              f"{h['kl_coverage'][r]:.4f}")
    kl = np.asarray(h["kl_coverage"], np.float64)
    print(f"  final acc {h['final_acc']:.3f}  "
          f"sim time {h['sim_time'][-1]:.1f}  "
          f"summary wall {sum(h['wall_summary_s']):.2f}s  "
          f"dropped {sum(h['dropped'])} clients / "
          f"{h['dropped_rounds']} whole rounds  "
          f"mean KL coverage {np.nanmean(kl):.4f}")
    return h


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mobile-churn",
                    choices=list(PRESET_NAMES))
    ap.add_argument("--all", action="store_true",
                    help="sweep every scenario preset")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--summary", default="py",
                    choices=["py", "pxy", "encoder", "none"])
    ap.add_argument("--clustering", default="kmeans",
                    choices=["kmeans", "minibatch", "online", "dbscan",
                             "hierarchical"])
    ap.add_argument("--server", default="sync", choices=["sync", "async"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    presets = PRESET_NAMES if args.all else (args.preset,)
    for preset in presets:
        run_preset(preset, args)


if __name__ == "__main__":
    use_compile_cache()
    main()
