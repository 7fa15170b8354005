"""Sharded fleet registry (DESIGN.md §7).

``StreamingSummaryRegistry`` collapsed the per-client python loop into one
dense ``[N, C]`` numpy scan — but that scan still runs on a single host
core and materializes the whole fleet at once.  At the million-client
north star the drift scan is the last O(N)-on-one-device pass in the
server round.  This registry keeps the same host-side arenas and decision
semantics and moves the scan onto a JAX device mesh:

  * the ``[N, C]`` stored/fresh label-dist arenas are processed in fixed
    row *chunks* (``chunk_rows``, padded to a multiple of the shard
    count), so device memory is O(chunk · C) no matter how large N grows
    — N=1M streams through in ~8 transfers at the default chunk;
  * each chunk is laid out row-wise across a 1-D ``fleet`` mesh axis
    (``utils.sharding.fleet_mesh`` + ``make_spec`` with ``FLEET_RULES``)
    and the symmetric-KL runs shard-local under ``shard_map`` — the scan
    is row-independent, so no collective is needed and per-device work is
    O(chunk / n_shards · C);
  * updates stay the O(drifted) host-side scatter of the parent class.

**Decision exactness.**  XLA's and numpy's libm differ by ~1 ulp, which
could flip a drift decision that lands exactly on ``kl_threshold``.  Rows
whose device-computed drift falls within ``decision_margin`` of the
threshold are therefore re-checked with the exact baseline math
(``core.scheduler.batch_sym_kl`` is row-independent, so subset re-checks
reproduce the full-scan values bit-for-bit).  That makes the sharded
registry's refresh decisions *provably identical* to the streaming
baseline on any mesh — pinned by ``tests/test_shard.py`` and the
differential harness.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.core.scheduler import RefreshPolicy, batch_sym_kl
from repro.stream.registry import StreamingSummaryRegistry
from repro.utils.roofline import (
    device_peaks, drift_scan_bytes, record_bandwidth,
)
from repro.utils.sharding import FLEET_RULES, fleet_mesh, make_spec


def _sym_kl_rows(p, q, eps: float = 1e-9):
    """Row-wise symmetric KL, elementwise math mirroring ``batch_sym_kl``.

    All-zero (padding) rows normalize to uniform on both sides and yield
    exactly zero drift, so chunk padding can never mark a row stale.
    """
    p = p + eps
    q = q + eps
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    q = q / jnp.sum(q, axis=-1, keepdims=True)
    return 0.5 * (jnp.sum(p * jnp.log(p / q), axis=-1)
                  + jnp.sum(q * jnp.log(q / p), axis=-1))


@functools.lru_cache(maxsize=64)
def _drift_scan(mesh: Mesh, rows: int, num_classes: int):
    """Compiled chunk scan for a (mesh, chunk shape) — cached at module
    level so every registry instance with the same layout shares one
    compile (the differential tests build many registries)."""
    spec = make_spec(("clients", None), (rows, num_classes), mesh,
                     rules=FLEET_RULES)
    sharded = jax.shard_map(_sym_kl_rows, mesh=mesh,
                            in_specs=(spec, spec), out_specs=P(*spec[:1]))
    return jax.jit(sharded,
                   in_shardings=NamedSharding(mesh, spec),
                   out_shardings=NamedSharding(mesh, P(*spec[:1])))


class ShardedSummaryRegistry(StreamingSummaryRegistry):
    """Streaming registry whose drift scan runs chunked over a device mesh.

    Same public contract as ``StreamingSummaryRegistry`` (decisions,
    updates, ``matrix``/``dense`` handoffs); only the ``_drift`` hook
    changes.  ``n_shards`` defaults to every local device; ``mesh`` can be
    passed explicitly to share one mesh across registry and benchmarks.
    """

    def __init__(self, num_clients: int, policy: RefreshPolicy,
                 summary_dim: int | None = None,
                 num_classes: int | None = None,
                 mesh: Mesh | None = None,
                 n_shards: int | None = None,
                 chunk_rows: int = 131072,
                 decision_margin: float = 1e-4):
        super().__init__(num_clients, policy, summary_dim=summary_dim,
                         num_classes=num_classes)
        self.mesh = mesh if mesh is not None else fleet_mesh(n_shards)
        self.n_shards = int(np.prod(self.mesh.devices.shape))
        # chunk no larger than the (shard-padded) fleet, rounded up to a
        # multiple of the shard count so make_spec keeps the fleet axis
        rows = min(max(int(chunk_rows), 1), num_clients)
        self.chunk_rows = -(-rows // self.n_shards) * self.n_shards
        self.decision_margin = float(decision_margin)
        self.scan_chunks = 0          # lifetime chunk-dispatch counter
        self.rechecked_rows = 0       # lifetime borderline re-checks

    def _drift(self, fresh: np.ndarray) -> np.ndarray:
        n, c = self.label_dists.shape
        scan = _drift_scan(self.mesh, self.chunk_rows, c)
        out = np.empty(n, np.float32)
        rows = self.chunk_rows
        pad_p = pad_q = None
        observed = obs.enabled()
        t_scan = time.perf_counter() if observed else 0.0
        chunk_fam = (obs.metrics().family("shard/scan_chunk_s",
                                          labels=("chunk",),
                                          kind="histogram")
                     if observed else None)
        with obs.kernel_span("drift_scan", rows=n, classes=c,
                             n_shards=self.n_shards,
                             chunk_rows=rows) as sp:
            for start in range(0, n, rows):
                stop = min(start + rows, n)
                m = stop - start
                t_chunk = time.perf_counter() if observed else 0.0
                if m == rows:
                    d = scan(self.label_dists[start:stop], fresh[start:stop])
                else:                       # tail chunk: zero-pad to shape
                    if pad_p is None:
                        pad_p = np.zeros((rows, c), np.float32)
                        pad_q = np.zeros((rows, c), np.float32)
                    pad_p[:m] = self.label_dists[start:stop]
                    pad_q[:m] = fresh[start:stop]
                    d = scan(pad_p, pad_q)
                out[start:stop] = np.asarray(d)[:m]
                if chunk_fam is not None:
                    # per-chunk scan time: a straggling shard region
                    # (page-cache miss, NUMA imbalance) shows up as one
                    # labeled stream, not a blur in the whole-scan mean
                    chunk_fam.labeled(start // rows).record(
                        time.perf_counter() - t_chunk)
                self.scan_chunks += 1
            sp.annotate(chunks=-(-n // rows))
        if observed:
            # achieved vs roofline-predicted scan bandwidth (gauges),
            # against the HBM of every device in the mesh
            peaks = device_peaks(self.mesh.devices.flat[0].device_kind)
            record_bandwidth(obs.metrics(), "kernel/drift_scan",
                             drift_scan_bytes(n, c),
                             time.perf_counter() - t_scan,
                             peak_bw=(peaks.hbm_bw * self.n_shards
                                      if peaks else None))
        # borderline band: device libm may differ from numpy by ~1 ulp, so
        # rows near the threshold are re-decided with the exact baseline
        # math — decisions match the streaming registry on any mesh
        near = np.flatnonzero(np.abs(out - self.policy.kl_threshold)
                              <= self.decision_margin)
        if near.size:
            out[near] = batch_sym_kl(self.label_dists[near], fresh[near])
            self.rechecked_rows += int(near.size)
        return out
