"""End-to-end selection quality (paper §2: HACCS's 18–38 % training-time
reduction mechanism): simulated time-to-accuracy of cluster-aware selection
vs random / fastest-only selection under system heterogeneity — plus the
scenario sweep (DESIGN.md §6): every named fleet preset run under each
clustering backend with per-round coverage/overhead/dropout metrics.

CSV: strategy,final_acc,sim_time_to_target,refreshes
     scenario/<preset>/<clustering>,0,final_acc=..;kl_cov=..;...
"""
from __future__ import annotations

import numpy as np

import repro.api as api
from benchmarks._record import emit
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl.system import SystemSpec
from repro.sim import DATA_HINTS, PRESET_NAMES, make_scenario


def _time_to(history, target):
    for acc, t in zip(history["acc"], history["sim_time"]):
        if acc >= target:
            return t
    return float("inf")


def run(rounds: int = 16, clients: int = 60, target_acc: float = 0.85,
        seed: int = 0) -> list:
    data = FederatedDataset(small_spec(num_clients=clients, num_classes=8,
                                       side=10, avg_samples=48,
                                       num_styles=4), seed=seed)
    rows = []
    for strategy, summary in (("haccs", "encoder"), ("random", "none"),
                              ("fastest", "none")):
        cfg = api.RunConfig(
            rounds=rounds, clients_per_round=8, local_steps=8,
            summary=summary, coreset_k=32, eval_every=1, seed=seed,
            clustering=api.ClusteringConfig(num_clusters=6,
                                            recluster_every=8),
            policy=api.PolicyConfig(name=strategy))
        h = api.run(data, cfg, system_spec=SystemSpec(speed_sigma=1.0,
                                                      availability=0.8))
        rows.append({
            "name": f"selection/{strategy}",
            "strategy": strategy,
            "final_acc": h["final_acc"],
            "t_to_target": _time_to(h, target_acc),
            "sim_time": h["sim_time"][-1],
            "refreshes": h["refreshes"][-1],
        })
    return rows


SCENARIO_CLUSTERINGS = ("kmeans", "minibatch", "online")


def run_scenarios(rounds: int = 8, clients: int = 48, seed: int = 0,
                  clusterings=SCENARIO_CLUSTERINGS,
                  presets=PRESET_NAMES) -> list:
    """Every scenario preset under each clustering backend; per-round
    metrics aggregated into one record per cell."""
    rows = []
    for preset in presets:
        alpha = DATA_HINTS[preset].get("alpha", 0.5)
        data = FederatedDataset(small_spec(num_clients=clients, num_classes=8,
                                           side=10, avg_samples=48,
                                           num_styles=4, alpha=alpha),
                                seed=seed)
        for clustering in clusterings:
            scenario = make_scenario(preset, clients, seed=seed)
            cfg = api.RunConfig(
                rounds=rounds, clients_per_round=8, local_steps=4,
                summary="py", refresh_kl=0.05,
                eval_every=max(rounds - 1, 1), seed=seed,
                clustering=api.ClusteringConfig(kind=clustering,
                                                num_clusters=6,
                                                recluster_every=4))
            h = api.run(data, cfg, scenario=scenario)
            kl = np.asarray(h["kl_coverage"], np.float64)
            rows.append({
                "name": f"scenario/{preset}/{clustering}",
                "preset": preset,
                "clustering": clustering,
                "final_acc": h["final_acc"],
                "kl_coverage": (float(np.nanmean(kl))
                                if np.isfinite(kl).any() else float("nan")),
                "summary_s": float(sum(h["wall_summary_s"])),
                "dropped": int(sum(h["dropped"])),
                "dropped_rounds": h["dropped_rounds"],
                "sim_time": h["sim_time"][-1],
                "refreshes": h["refreshes"][-1],
                "mean_active": float(np.mean(h["n_active"])),
            })
    return rows


def main(fast: bool = True):
    rows = run(rounds=8 if fast else 20, clients=30 if fast else 80,
               target_acc=0.7 if fast else 0.85)
    for r in rows:
        emit(r["name"], final_acc=f"{r['final_acc']:.3f}",
             t_target=f"{r['t_to_target']:.1f}",
             sim_time=f"{r['sim_time']:.1f}", refreshes=r["refreshes"])
    base = next(r for r in rows if r["strategy"] == "random")
    ours = next(r for r in rows if r["strategy"] == "haccs")
    if np.isfinite(ours["t_to_target"]) and np.isfinite(base["t_to_target"]):
        red = 1 - ours["t_to_target"] / base["t_to_target"]
        emit("selection/time_reduction_vs_random",
             text=f"{red * 100:.1f}%")

    sc_rows = run_scenarios(
        rounds=4 if fast else 12, clients=32 if fast else 96,
        clusterings=("kmeans", "online") if fast else SCENARIO_CLUSTERINGS)
    for r in sc_rows:
        emit(r["name"], final_acc=f"{r['final_acc']:.3f}",
             kl_cov=f"{r['kl_coverage']:.4f}", dropped=r["dropped"],
             dropped_rounds=r["dropped_rounds"],
             summary_s=f"{r['summary_s']:.3f}",
             sim_time=f"{r['sim_time']:.1f}", refreshes=r["refreshes"],
             mean_active=f"{r['mean_active']:.1f}")
    return rows + sc_rows


if __name__ == "__main__":
    main(fast=False)
