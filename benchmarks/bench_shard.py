"""§7 — sharded fleet pipeline at 100k–1M synthetic clients.

Measures the two scale-out claims of DESIGN.md §7 on this host:

  * ``sharded/scan/*`` — the registry's chunked device-mesh drift scan
    (``repro.stream.SummaryRegistry``), including the N=1M row arena that
    must stream through fixed-size chunks under the CI memory budget;
  * ``sharded/pipeline/*`` — one full server round (drift scan →
    O(drifted) scatter → hierarchical shard-local maintenance → weighted
    global merge) with per-stage seconds.

Every record carries ``n_shards`` so the 4-device CI step
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) can assert the
mesh actually split.  CSV: ``sharded/<...>,us_per_call,derived``.
"""
from __future__ import annotations

import resource
import time

import jax
import numpy as np

from benchmarks._record import emit
from repro.core.scheduler import RefreshPolicy
from repro.shard import HierarchicalClusterMaintainer
from repro.sim import drift_fleet, synthetic_fleet
from repro.stream import SummaryRegistry


def _peak_mb() -> float:
    """Process-lifetime peak RSS.  In the all-bench harness this includes
    whatever earlier benches peaked at, so the CI memory-budget assertion
    only reads it from the isolated ``--only shard`` run."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run_scan(n: int, num_classes: int = 10, dim: int = 8,
             chunk_rows: int = 131072, drift_frac: float = 0.01,
             seed: int = 0) -> dict:
    """One round of refresh decisions at fleet scale (device mesh,
    fixed-size chunks)."""
    fleet = synthetic_fleet(n, num_classes, dim, seed=seed)
    policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=0.05)
    shard = SummaryRegistry(n, policy, chunk_rows=chunk_rows)
    shard.update_batch(np.arange(n), 0, fleet.summaries, fleet.label_dists)
    fresh, _ = drift_fleet(fleet.label_dists, drift_frac, seed=seed + 1)

    shard.stale_clients(1, fresh)            # warm: compile the chunk scan
    chunks0 = shard.scan_chunks
    t0 = time.perf_counter()
    got = shard.stale_clients(1, fresh)
    scan_s = time.perf_counter() - t0
    return {"n": n, "n_shards": shard.n_shards,
            "chunk_rows": shard.chunk_rows,
            "chunks": shard.scan_chunks - chunks0,
            "stale": int(got.size), "scan_s": scan_s,
            "peak_mb": _peak_mb()}


def run_pipeline(n: int, num_classes: int = 10, dim: int = 16, k: int = 8,
                 local_k: int = 16, chunk_rows: int = 131072,
                 drift_frac: float = 0.01, seed: int = 0) -> dict:
    """One full sharded server round with per-stage seconds: scan →
    scatter → shard-local online maintenance → weighted global merge."""
    fleet = synthetic_fleet(n, num_classes, dim, seed=seed)
    policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=0.05)
    reg = SummaryRegistry(n, policy, chunk_rows=chunk_rows)
    reg.update_batch(np.arange(n), 0, fleet.summaries, fleet.label_dists)
    hm = HierarchicalClusterMaintainer(k, n_shards=reg.n_shards,
                                       local_k=local_k)
    # round 0: seed clustering state (local full fits + first merge)
    t0 = time.perf_counter()
    hm.refresh(reg.dense(), np.arange(n), jax.random.PRNGKey(seed))
    seed_s = time.perf_counter() - t0

    fresh, _ = drift_fleet(fleet.label_dists, drift_frac, seed=seed + 1)
    reg.stale_clients(1, fresh)              # warm the chunk scan
    t0 = time.perf_counter()
    stale = reg.stale_clients(1, fresh)
    scan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    reg.update_batch(stale, 1, fleet.summaries[stale], fresh[stale])
    scatter_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = hm.refresh(reg.dense(), stale, jax.random.PRNGKey(seed + 1))
    merge_s = time.perf_counter() - t0
    return {"n": n, "n_shards": reg.n_shards, "k": k, "local_k": local_k,
            "stale": int(stale.size), "seed_s": seed_s, "scan_s": scan_s,
            "scatter_s": scatter_s, "merge_s": merge_s,
            "inertia": out["inertia"], "peak_mb": _peak_mb()}


def main(fast: bool = True):
    rows = []
    # the 1M chunked scan runs even in quick mode — it is the CI memory-
    # budget acceptance check (arenas ~90 MB + O(chunk) device state)
    for n in (100_000, 1_000_000):
        r = run_scan(n)
        rows.append(r)
        emit(f"sharded/scan/n{n}", us=r["scan_s"] * 1e6,
             n_shards=r["n_shards"], scan_s=f"{r['scan_s']:.4f}",
             chunks=r["chunks"],
             chunk_rows=r["chunk_rows"], stale=r["stale"],
             peak_mb=f"{r['peak_mb']:.0f}")

    for n in ((100_000,) if fast else (100_000, 1_000_000)):
        r = run_pipeline(n)
        rows.append(r)
        emit(f"sharded/pipeline/n{n}",
             us=(r["scan_s"] + r["scatter_s"] + r["merge_s"]) * 1e6,
             n_shards=r["n_shards"], scan_s=f"{r['scan_s']:.4f}",
             merge_s=f"{r['merge_s']:.4f}", scatter_s=f"{r['scatter_s']:.5f}",
             seed_s=f"{r['seed_s']:.3f}", stale=r["stale"],
             peak_mb=f"{r['peak_mb']:.0f}")
    return rows


if __name__ == "__main__":
    main(fast=False)
