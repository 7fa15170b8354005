"""Mergeable sketch summaries (DESIGN.md §5).

Two fixed-width, linear (hence mergeable) summaries of a client's data
stream, designed so the server can hold a whole fleet's state as dense
``[N, ...]`` arrays and update any batch of clients in one dispatch:

  * **count-min label sketch** ``[R, W]`` — estimates the label histogram
    (hence P(y)) within the classic count-min guarantees: estimates never
    undercount, and overcount by at most ``e·n/W`` with probability
    ``1 − e^{−R}``.  ``W`` is independent of the number of classes, so the
    same server-side layout serves C = 62 and C = 600 datasets.
  * **random-projection feature sketch** ``[W_f]`` — the client's summed
    feature vector projected onto ``W_f`` random ±1/√W_f directions
    (Achlioptas-style JL); inner products between clients are preserved in
    expectation, and the sketch of a union is the sum of the sketches.

Both update paths are one-hot × one-hot (or plain) matmuls, so the batched
update fuses across clients via the label-offset trick — on TPU through the
``sketch_update`` Pallas kernel (``kernels/sketch_update.py``), elsewhere
through the pure-jnp oracle.  ``update`` returns *increments*; ``merge`` is
addition — the algebra the streaming registry leans on.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import repro.obs as obs
from repro.kernels.sketch_update import HASH_PRIME, cm_hash_params


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Static configuration of the fleet's sketches (hash seeds included,
    so every node derives identical hash functions)."""
    num_rows: int = 4          # R: count-min hash rows
    width: int = 128           # W: counters per row
    feat_width: int = 64       # W_f: random-projection dims
    seed: int = 0

    @property
    def hash_params(self) -> tuple[tuple, tuple]:
        return cm_hash_params(self.num_rows, self.seed)


# ---------------------------------------------------------------------------
# count-min label sketches


def _hash_buckets(items: np.ndarray, spec: SketchSpec) -> np.ndarray:
    """[K] item ids -> [K, R] counter indices (same math as the kernel)."""
    a, b = spec.hash_params
    av = np.asarray(a, np.int64)[None, :]
    bv = np.asarray(b, np.int64)[None, :]
    return ((np.asarray(items, np.int64)[:, None] * av + bv)
            % HASH_PRIME) % spec.width


def cm_empty(num_sketches: int, spec: SketchSpec) -> np.ndarray:
    return np.zeros((num_sketches, spec.num_rows, spec.width), np.float32)


def cm_update_batch(labels, valid, spec: SketchSpec,
                    use_kernel: bool = False) -> np.ndarray:
    """[M, N] labels / valid -> [M, R, W] count-min increments.

    One fused dispatch for the whole client batch: rows are flattened and
    tagged with their client slot, so a single (kernel or oracle) call
    scatters every client's counts into its own sketch.
    """
    labels = np.asarray(labels, np.int32)
    valid = np.asarray(valid, bool)
    m, n = labels.shape
    a, b = spec.hash_params
    seg = np.repeat(np.arange(m, dtype=np.int32), n)
    with obs.span("sketch_update", cat="kernel", clients=m, items=m * n,
                  kernel=bool(use_kernel)):
        if use_kernel:
            from repro.kernels.ops import sketch_update
            out = sketch_update(labels.reshape(-1), seg, valid.reshape(-1),
                                m, spec.width, a, b)
        else:
            import jax.numpy as jnp

            from repro.kernels.ref import sketch_update_ref
            out = sketch_update_ref(jnp.asarray(labels.reshape(-1)),
                                    jnp.asarray(seg),
                                    jnp.asarray(valid.reshape(-1)),
                                    m, spec.width, a, b)
    return np.asarray(out)


def cm_merge(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sketch of a union of streams = sum of the streams' sketches."""
    return a + b


def cm_estimate(sketch: np.ndarray, items, spec: SketchSpec) -> np.ndarray:
    """[..., R, W] sketches x [K] item ids -> [..., K] count estimates
    (min over rows — never undercounts)."""
    h = _hash_buckets(np.asarray(items), spec)              # [K, R]
    rows = np.arange(spec.num_rows)[None, :]                # [1, R]
    per_row = sketch[..., rows, h]                          # [..., K, R]
    return per_row.min(axis=-1)


def cm_label_dist(sketch: np.ndarray, num_classes: int,
                  spec: SketchSpec) -> np.ndarray:
    """Estimated P(y) over ``num_classes`` classes ([..., C], normalized;
    uniform when the sketch is empty)."""
    est = cm_estimate(sketch, np.arange(num_classes), spec)
    total = est.sum(axis=-1, keepdims=True)
    uniform = np.full_like(est, 1.0 / num_classes)
    return np.where(total > 0, est / np.maximum(total, 1.0), uniform)


# ---------------------------------------------------------------------------
# random-projection feature sketches


@functools.lru_cache(maxsize=8)
def _rp_matrix_cached(feat_dim: int, width: int, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed + 0x5EED)
    signs = rng.randint(0, 2, size=(feat_dim, width)).astype(np.float32)
    return (2.0 * signs - 1.0) / np.sqrt(width)


def rp_matrix(feat_dim: int, spec: SketchSpec) -> np.ndarray:
    """[D, W_f] ±1/√W_f projection, derived from the spec seed."""
    return _rp_matrix_cached(feat_dim, spec.feat_width, spec.seed)


def rp_update_batch(feats, valid, spec: SketchSpec) -> np.ndarray:
    """[M, N, D] features / [M, N] valid -> [M, W_f] sketch increments
    (projection of each client's masked feature sum; linear, so merge=add)."""
    feats = np.asarray(feats, np.float32)
    valid = np.asarray(valid, bool)
    sums = np.einsum("mnd,mn->md", feats, valid.astype(np.float32))
    return sums @ rp_matrix(feats.shape[-1], spec)


# ---------------------------------------------------------------------------
# fleet container


class FleetSketches:
    """Dense per-client sketch state for the whole fleet.

    ``label_sk [N, R, W]``, ``feat_sk [N, W_f]``, ``counts [N]`` — all
    preallocated, all updated by batched scatter-add of increments, so a
    refresh of M drifted clients costs one fused dispatch + an O(M) row
    update, never an O(N) scan.
    """

    def __init__(self, num_clients: int, spec: SketchSpec | None = None):
        self.spec = spec or SketchSpec()
        self.num_clients = num_clients
        self.label_sk = cm_empty(num_clients, self.spec)
        self.feat_sk = np.zeros((num_clients, self.spec.feat_width),
                                np.float32)
        self.counts = np.zeros(num_clients, np.int64)

    def update_batch(self, client_ids, labels, valid, feats=None,
                     use_kernel: bool = False, reset: bool = True) -> None:
        """Update clients ``client_ids`` from padded ``[M, N]`` label /
        valid (and optional ``[M, N, D]`` feature) arrays.  ``reset=True``
        replaces each client's sketch (a fresh summary of drifted data);
        ``reset=False`` merges the increment in (a continuing stream)."""
        ids = np.asarray(client_ids, np.int64)
        inc = cm_update_batch(labels, valid, self.spec, use_kernel=use_kernel)
        if reset:
            self.label_sk[ids] = inc
            self.counts[ids] = np.asarray(valid, bool).sum(axis=1)
            if feats is not None:
                self.feat_sk[ids] = rp_update_batch(feats, valid, self.spec)
        else:
            # np.add.at: duplicated client ids must each contribute (plain
            # fancy-index += applies only the last occurrence)
            np.add.at(self.label_sk, ids, inc)
            np.add.at(self.counts, ids, np.asarray(valid, bool).sum(axis=1))
            if feats is not None:
                np.add.at(self.feat_sk, ids,
                          rp_update_batch(feats, valid, self.spec))

    def merge_from(self, other: "FleetSketches") -> None:
        """Fold another shard's fleet state into this one (same spec)."""
        assert self.spec == other.spec
        self.label_sk += other.label_sk
        self.feat_sk += other.feat_sk
        self.counts += other.counts

    def label_dists(self, num_classes: int) -> np.ndarray:
        """Estimated [N, C] P(y) for every client — the cheap drift signal
        recovered from sketches alone."""
        return cm_label_dist(self.label_sk, num_classes, self.spec)
