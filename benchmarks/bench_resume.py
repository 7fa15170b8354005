"""§9 — durability overhead: checkpoint, log-append, and resume cost.

DESIGN.md §9's fault-tolerance layer must be cheap enough to leave on:
the event log rides the round loop (an append per committed event) and a
checkpoint is cut at every round boundary by default.  This bench prices
exactly those pieces at fleet scale, plus the end-to-end kill + resume
path on a real (small) federated run:

  * ``server_resume/ckpt_save/nN``  — ``save_state`` seconds for a
    fleet-scale server checkpoint (summary registry + online
    maintainer state, the dominant payload);
  * ``server_resume/ckpt_load/nN``  — ``load_state`` + restore into
    fresh runtime objects, the resume-side mirror;
  * ``server_resume/log_append``    — event-log append+flush µs/record;
  * ``server_resume/resume/run``    — wall seconds for crash-at-the-last
    -boundary + resume, vs the uninterrupted run of the same config
    (``overhead`` in derived = resumed / uninterrupted, amortized
    replay cost).

CSV: ``server_resume/<what>,us_per_call,derived``.
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from benchmarks._record import emit
from repro.checkpoint import load_state, save_state
from repro.checkpoint.durable import EventLog
from repro.checkpoint.server_state import (
    maintainer_state, registry_state, restore_maintainer, restore_registry,
)
from repro.core.scheduler import RefreshPolicy
from repro.sim import synthetic_fleet
from repro.stream import OnlineClusterMaintainer, OnlinePolicy, SummaryRegistry


def _server_state(n: int, seed: int, num_classes: int = 10, dim: int = 8,
                  k: int = 8):
    """A populated fleet-scale registry + fitted maintainer — the two
    arrays that dominate checkpoint bytes."""
    fleet = synthetic_fleet(n, num_classes, dim, seed=seed)
    policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=0.05)
    reg = SummaryRegistry(n, policy)
    reg.update_batch(np.arange(n), 0, fleet.summaries, fleet.label_dists)
    m = OnlineClusterMaintainer(k, OnlinePolicy(reseed_every=10 ** 9))
    m.refresh(reg.dense(), np.arange(n), jax.random.PRNGKey(seed),
              live=reg.has_mask())
    return reg, m, policy


def bench_checkpoint(n: int, seed: int = 0, repeats: int = 3) -> dict:
    reg, m, policy = _server_state(n, seed)
    tree = {"registry": registry_state(reg),
            "maintainer": maintainer_state(m)}
    saves, loads = [], []
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "ckpt")
        for _ in range(repeats):
            t0 = time.perf_counter()
            save_state(base, tree)
            saves.append(time.perf_counter() - t0)
            fresh_reg = SummaryRegistry(n, policy)
            fresh_m = OnlineClusterMaintainer(
                m.k, OnlinePolicy(reseed_every=10 ** 9))
            t0 = time.perf_counter()
            st = load_state(base)
            restore_registry(fresh_reg, st["registry"])
            restore_maintainer(fresh_m, st["maintainer"])
            loads.append(time.perf_counter() - t0)
        bytes_ = (os.path.getsize(base + ".npz")
                  + os.path.getsize(base + ".state.json"))
    return {"n": n, "save_s": float(np.min(saves)),
            "load_s": float(np.min(loads)), "bytes": int(bytes_)}


def bench_log_append(records: int = 5000) -> float:
    """Per-record append+flush seconds on the durable event log."""
    with tempfile.TemporaryDirectory() as d:
        log = EventLog(os.path.join(d, "events.jsonl"))
        t0 = time.perf_counter()
        for i in range(records):
            log.append({"type": "event", "round": i % 32, "stage": i % 9,
                        "seq": i, "kind": "bench"})
        dt = time.perf_counter() - t0
        log.close()
    return dt / records


def bench_resume_run(seed: int = 0, rounds: int = 3) -> dict:
    """End-to-end: crash at the last stage boundary, resume, complete —
    vs the same run never interrupted."""
    import dataclasses

    import repro.api as api
    from repro.data.synthetic import FederatedDataset, small_spec
    from repro.server.events import Stage
    from repro.sim import FaultPlan, ServerKilled

    data = FederatedDataset(small_spec(num_clients=16, num_classes=5,
                                       side=8, avg_samples=24), seed=seed)
    cfg = api.RunConfig(
        rounds=rounds, clients_per_round=4, local_steps=1, summary="py",
        eval_every=rounds, seed=seed,
        clustering=api.ClusteringConfig(num_clusters=3, recluster_every=2))
    t0 = time.perf_counter()
    api.run(data, cfg)
    plain_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        try:
            api.run(data, dataclasses.replace(
                cfg, durability=api.DurabilityConfig(dir=d)),
                faults=FaultPlan(crash_points=((rounds - 1, Stage.TRAIN),)))
        except ServerKilled:
            pass
        api.run(data, cfg, resume_from=d)
        resumed_s = time.perf_counter() - t0
    return {"plain_s": plain_s, "resumed_s": resumed_s,
            "overhead": resumed_s / max(plain_s, 1e-9)}


def main(fast: bool = True, seed: int = 0):
    rows = []
    sizes = (100_000,) if fast else (100_000, 1_000_000)
    for n in sizes:
        r = bench_checkpoint(n, seed=seed)
        rows.append(r)
        emit(f"server_resume/ckpt_save/n{n}", us=r["save_s"] * 1e6,
             bytes=r["bytes"])
        emit(f"server_resume/ckpt_load/n{n}", us=r["load_s"] * 1e6,
             text="restore_included")
    ap = bench_log_append()
    emit("server_resume/log_append", us=ap * 1e6,
         text="per_record_flush")
    rr = bench_resume_run(seed=seed)
    emit("server_resume/resume/run", us=rr["resumed_s"] * 1e6,
         plain_s=f"{rr['plain_s']:.3f}", resumed_s=f"{rr['resumed_s']:.3f}",
         overhead=f"{rr['overhead']:.2f}")
    rows.append(rr)
    return rows


if __name__ == "__main__":
    main(fast=False)
