"""The fleet's summary registry (DESIGN.md §5, §7).

The whole fleet lives in preallocated dense host arenas

    summaries    [N, D]  float32    (the clustering input, zero-copy)
    label_dists  [N, C]  float32    (the cheap drift signal)
    last_refresh [N]     int64
    has_summary  [N]     bool

so one round of server work is: one drift scan over ``[N, C]`` to find the
O(drifted) refresh set, an O(drifted) row scatter to absorb the recomputed
summaries, and a zero-copy ``dense()`` handoff to clustering.

The drift scan runs on a JAX device mesh:

  * the stored/fresh label-dist arenas are processed in fixed row *chunks*
    (``chunk_rows``, padded to a multiple of the shard count), so device
    memory is O(chunk · C) no matter how large N grows — N=1M streams
    through in ~8 transfers at the default chunk;
  * each chunk is laid out row-wise across a 1-D ``fleet`` mesh axis
    (``utils.sharding.fleet_mesh`` + ``make_spec`` with ``FLEET_RULES``)
    and the symmetric-KL runs shard-local under ``shard_map`` — the scan
    is row-independent, so no collective is needed and per-device work is
    O(chunk / n_shards · C).

**Decision exactness.**  XLA's and numpy's libm differ by ~1 ulp, which
could flip a drift decision that lands exactly on ``kl_threshold``.  Rows
whose device-computed drift falls within ``decision_margin`` of the
threshold are therefore re-checked with the exact host math
(``core.scheduler.batch_sym_kl`` is row-independent, so subset re-checks
reproduce the full-scan values bit-for-bit).  That makes the refresh
decisions equal to the per-client ``sym_kl`` predicate on any mesh and any
chunking — pinned by ``tests/test_shard.py`` and the differential harness.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.core.scheduler import RefreshPolicy, batch_sym_kl, sym_kl
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


def _chunk_spec(mesh: Mesh, rows: int, num_classes: int):
    """A chunk's row-wise layout over the ``fleet`` mesh axis."""
    return make_spec(("clients", None), (rows, num_classes), mesh,
                     rules=FLEET_RULES)


@functools.lru_cache(maxsize=64)
def _drift_scan(mesh: Mesh, rows: int, num_classes: int):
    """Compiled chunk scan for a (mesh, chunk shape) — cached at module
    level so every registry instance with the same layout shares one
    compile (the differential tests build many registries)."""
    spec = _chunk_spec(mesh, rows, num_classes)
    sharded = jax.shard_map(_sym_kl_rows, mesh=mesh,
                            in_specs=(spec, spec), out_specs=P(*spec[:1]))
    return jax.jit(sharded,
                   in_shardings=NamedSharding(mesh, spec),
                   out_shardings=NamedSharding(mesh, P(*spec[:1])))


class SummaryRegistry:
    """Fleet-scale server-side store of client summaries + refresh state.

    ``n_shards`` defaults to every local device; ``mesh`` can be passed
    explicitly to share one mesh across registry and benchmarks.
    """

    def __init__(self, num_clients: int, policy: RefreshPolicy,
                 num_classes: int | None = None,
                 mesh: Mesh | None = None,
                 n_shards: int | None = None,
                 chunk_rows: int = 131072,
                 decision_margin: float = 1e-4):
        self.policy = policy
        self.num_clients = num_clients
        self.refresh_count = 0
        # write-version: bumped on every mutation so the async server's
        # snapshots can record which registry state they captured
        # (repro.server.snapshot, DESIGN.md §8)
        self.version = 0
        self.last_refresh = np.full(num_clients, -(10 ** 9), np.int64)
        self.has_summary = np.zeros(num_clients, bool)
        # the summary arena allocates on the first update (its width is
        # the summary's); the label arena too when C isn't given
        self.summaries = None
        self.label_dists = (np.zeros((num_clients, num_classes), np.float32)
                            if num_classes else None)
        self.mesh = mesh if mesh is not None else fleet_mesh(n_shards)
        self.n_shards = int(np.prod(self.mesh.devices.shape))
        # chunk no larger than the (shard-padded) fleet, rounded up to a
        # multiple of the shard count so make_spec keeps the fleet axis
        rows = min(max(int(chunk_rows), 1), num_clients)
        self.chunk_rows = -(-rows // self.n_shards) * self.n_shards
        self.decision_margin = float(decision_margin)
        self.scan_chunks = 0          # lifetime chunk-dispatch counter
        self.rechecked_rows = 0       # lifetime borderline re-checks

    # ------------------------------------------------------------------
    # refresh decisions

    def stale_mask(self, round_idx: int,
                   fresh_label_dists: np.ndarray,
                   active: np.ndarray | None = None) -> np.ndarray:
        """[N, C] fresh P(y) -> [N] bool refresh decisions, one drift scan
        for the whole fleet.  ``active`` (scenario availability threading)
        keeps absent clients out of the refresh set."""
        missing = ~self.has_summary
        aged = (round_idx - self.last_refresh) >= self.policy.max_age_rounds
        if self.label_dists is None:
            mask = missing | aged
        else:
            drift = self._drift(np.asarray(fresh_label_dists, np.float32))
            mask = missing | aged | (drift > self.policy.kl_threshold)
        if active is not None:
            mask = mask & np.asarray(active, bool)
        return mask

    def _drift(self, fresh: np.ndarray) -> np.ndarray:
        """[N, C] fresh P(y) -> [N] sym-KL against the stored dists, chunk
        by chunk on the mesh, borderline rows re-decided on the host."""
        n, c = self.label_dists.shape
        rows = self.chunk_rows
        scan = _drift_scan(self.mesh, rows, c)
        layout = NamedSharding(self.mesh, _chunk_spec(self.mesh, rows, c))
        out = np.empty(n, np.float32)
        pad_p = pad_q = None
        observed = obs.enabled()
        chunk_fam = (obs.metrics().family("shard/scan_chunk_s",
                                          labels=("chunk",),
                                          kind="histogram")
                     if observed else None)
        with obs.span("drift_scan/chunks", cat="kernel", rows=n, classes=c,
                      n_shards=self.n_shards, chunk_rows=rows) as sp:
            for start in range(0, n, rows):
                stop = min(start + rows, n)
                m = stop - start
                t_chunk = time.perf_counter() if observed else 0.0
                if m == rows:
                    p, q = self.label_dists[start:stop], fresh[start:stop]
                else:                       # tail chunk: zero-pad to shape
                    if pad_p is None:
                        pad_p = np.zeros((rows, c), np.float32)
                        pad_q = np.zeros((rows, c), np.float32)
                    pad_p[:m] = self.label_dists[start:stop]
                    pad_q[:m] = fresh[start:stop]
                    p, q = pad_p, pad_q
                d = scan(*obs.device_put("drift_scan/put", (p, q), layout))
                out[start:stop] = np.asarray(d)[:m]
                if chunk_fam is not None:
                    # per-chunk scan time: a straggling shard region
                    # (page-cache miss, NUMA imbalance) shows up as one
                    # labeled stream, not a blur in the whole-scan mean
                    chunk_fam.labeled(start // rows).record(
                        time.perf_counter() - t_chunk)
                self.scan_chunks += 1
            sp.annotate(chunks=-(-n // rows))
        # borderline band: device libm may differ from numpy by ~1 ulp, so
        # rows near the threshold are re-decided with the exact host math
        # — decisions are the same on any mesh and any chunking
        near = np.flatnonzero(np.abs(out - self.policy.kl_threshold)
                              <= self.decision_margin)
        if near.size:
            out[near] = batch_sym_kl(self.label_dists[near], fresh[near])
            self.rechecked_rows += int(near.size)
        return out

    def stale_clients(self, round_idx: int, fresh_label_dists: np.ndarray,
                      active: np.ndarray | None = None) -> np.ndarray:
        """O(drifted) refresh set (int64 ids) from an ``[N, C]`` array."""
        return np.flatnonzero(self.stale_mask(round_idx, fresh_label_dists,
                                              active=active))

    def needs_refresh(self, client: int, round_idx: int,
                      fresh_label_dist: np.ndarray) -> bool:
        """Per-client reference predicate: the decision ``stale_mask``
        makes for one client, with the exact host ``sym_kl``."""
        if not self.has_summary[client]:
            return True
        if round_idx - self.last_refresh[client] >= self.policy.max_age_rounds:
            return True
        drift = sym_kl(self.label_dists[client], fresh_label_dist)
        return drift > self.policy.kl_threshold

    # ------------------------------------------------------------------
    # updates

    def update_batch(self, client_ids, round_idx: int, summaries,
                     label_dists) -> None:
        """Absorb one refresh round: ``[M, D]`` summaries / ``[M, C]``
        label dists scatter into the fleet matrices (O(M), no scan)."""
        ids = np.asarray(client_ids, np.int64)
        if ids.size == 0:
            return
        summaries = np.asarray(summaries, np.float32)
        label_dists = np.asarray(label_dists, np.float32)
        if self.summaries is None:
            self.summaries = np.zeros((self.num_clients, summaries.shape[-1]),
                                      np.float32)
        if self.label_dists is None:
            self.label_dists = np.zeros(
                (self.num_clients, label_dists.shape[-1]), np.float32)
        self.summaries[ids] = summaries
        self.label_dists[ids] = label_dists
        self.last_refresh[ids] = round_idx
        self.has_summary[ids] = True
        self.refresh_count += ids.size
        self.version += 1
        obs.metrics().counter("registry/scatter_rows").inc(int(ids.size))

    def remove(self, client: int) -> None:
        """Evict a departed client (scenario churn).  Without this, the
        dense row of a client that left the fleet keeps matching the drift
        scan as "fresh" and keeps feeding its stale summary to clustering —
        the stale-row selection bug ``tests/test_stream.py`` pins."""
        self.has_summary[client] = False
        self.last_refresh[client] = -(10 ** 9)
        self.version += 1
        obs.metrics().counter("registry/evictions").inc()
        if self.summaries is not None:
            self.summaries[client] = 0.0
        if self.label_dists is not None:
            self.label_dists[client] = 0.0

    # ------------------------------------------------------------------
    # clustering handoffs

    def has_mask(self) -> np.ndarray:
        """[N] bool: which clients currently hold a summary."""
        return self.has_summary.copy()

    def matrix_rows(self, ids: np.ndarray) -> np.ndarray:
        """Clustering input restricted to ``ids`` — churn-safe.  Asserts
        every requested row holds a summary (misuse must fail loudly, not
        cluster zero rows)."""
        ids = np.asarray(ids, np.int64)
        if self.summaries is None or ids.size == 0:
            return np.zeros((0, 0), np.float32)
        assert self.has_summary[ids].all(), \
            "missing summaries in requested rows"
        return self.summaries[ids]

    def dense(self) -> np.ndarray:
        """Full [N, D] matrix, zero rows for missing clients (stable row
        indexing for online cluster maintenance under churn)."""
        assert self.summaries is not None, "no summaries yet"
        return self.summaries
