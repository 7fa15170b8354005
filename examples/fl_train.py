"""End-to-end federated training with the paper's efficient summaries.

    PYTHONPATH=src python examples/fl_train.py [--rounds 20] [--clients 60]

Runs three selection policies on the same drifting non-IID federation and
prints accuracy-vs-simulated-wallclock — the paper's headline effect:
cluster-aware selection with cheap refreshable summaries reaches target
accuracy in less simulated time, and the summary overhead stays negligible
even under drift (where HACCS's one-shot P(X|y) summaries would either go
stale or cost 100s of seconds per refresh).
"""
import argparse

import numpy as np

import repro.api as api
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl.system import SystemSpec
from repro.utils.cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--clients", type=int, default=48)
    ap.add_argument("--drift-start", type=int, default=8)
    args = ap.parse_args()

    data = FederatedDataset(small_spec(
        num_clients=args.clients, num_classes=8, side=10, avg_samples=48,
        num_styles=4), seed=0)
    system = SystemSpec(speed_sigma=1.0, availability=0.85)

    runs = {
        "haccs+encoder": api.RunConfig(
            rounds=args.rounds, clients_per_round=8, local_steps=8,
            summary=api.Summary.ENCODER, coreset_k=32, refresh_kl=0.08,
            clustering=api.ClusteringConfig(num_clusters=6,
                                            recluster_every=4),
            policy=api.PolicyConfig(name="haccs"),
            drift_start=args.drift_start, drift_per_round=0.15),
        "random": api.RunConfig(
            rounds=args.rounds, clients_per_round=8, local_steps=8,
            summary=api.Summary.NONE, policy=api.PolicyConfig(name="random"),
            drift_start=args.drift_start, drift_per_round=0.15),
        "fastest-only": api.RunConfig(
            rounds=args.rounds, clients_per_round=8, local_steps=8,
            summary=api.Summary.NONE,
            policy=api.PolicyConfig(name="fastest"),
            drift_start=args.drift_start, drift_per_round=0.15),
    }
    results = {}
    for name, cfg in runs.items():
        h = api.run(data, cfg, system_spec=system)
        results[name] = h
        print(f"\n=== {name}")
        for r in range(0, args.rounds, max(args.rounds // 8, 1)):
            print(f"  round {r:3d}  acc {h['acc'][r]:.3f}  "
                  f"sim_time {h['sim_time'][r]:8.1f}  "
                  f"refreshes {h['refreshes'][r]}")
        print(f"  final acc {h['final_acc']:.3f}  "
              f"total sim time {h['sim_time'][-1]:.1f}  "
              f"summary wall {sum(h['wall_summary_s']):.1f}s")

    base = results["random"]
    ours = results["haccs+encoder"]
    tgt = 0.8 * max(base["final_acc"], ours["final_acc"])
    t_of = lambda h: next((t for a, t in zip(h["acc"], h["sim_time"])  # noqa
                           if a >= tgt), float("inf"))
    if np.isfinite(t_of(ours)) and np.isfinite(t_of(base)):
        print(f"\ntime-to-{tgt:.2f}-accuracy: haccs {t_of(ours):.1f} vs "
              f"random {t_of(base):.1f} "
              f"({(1 - t_of(ours) / t_of(base)) * 100:.0f}% reduction)")


if __name__ == "__main__":
    use_compile_cache()
    main()
