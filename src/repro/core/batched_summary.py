"""Fleet-scale batched summary engine (DESIGN.md §4).

The paper's up-to-30x summary speedup comes from making the per-client
computation cheap — but at fleet scale the *dispatch* overhead of running
that cheap computation once per client dominates: a Python loop of per-client
jit calls pays host→device latency, argument marshalling, and dispatch cost
N_clients times per refresh round.  This module removes that axis of cost:

  * stale clients are grouped into **shape buckets** (dataset size rounded up
    to a power of two, the same bucketing ``fl.client.timed_summary`` uses),
  * each bucket is stacked into padded ``[M, N_bucket, ...]`` arrays and the
    whole batch is summarized with **one** jitted call (``jax.vmap`` over the
    client axis) — O(#buckets) dispatches per round instead of O(#clients),
  * where shapes allow, the per-client one-hot matmuls are fused across the
    batch through the existing Pallas kernels via the **label-offset trick**:
    client ``m``'s labels are shifted by ``m * C`` so a single
    ``class_hist`` / ``seg_mean`` call with ``M*C`` classes computes all M
    histograms / per-label means in one kernel launch (DESIGN.md §3-§4).

Per-client timings are recovered by amortizing the measured batch wall time
uniformly over the clients in the dispatch, so the simulated clock and the
``SummaryRegistry`` refresh accounting are unchanged in expectation.

Each client brings an integer PRNG seed, not a key: the seeds go to the
device as one int32 array and the jitted dispatch makes the keys, so the
host never waits on the device per client.

Numerical contract: for every client, the batched result matches the
per-client ``fl.client.timed_summary`` result (same bucket padding, same
seed ⇒ same key ⇒ same coreset) to float tolerance — asserted by
``tests/test_batched_summary.py``.  A seed lies in ``[0, 2**31)``, the range
in which the in-jit ``PRNGKey`` of an int32 equals
``jax.random.PRNGKey(seed)`` bit for bit.
"""
from __future__ import annotations

import dataclasses
import operator
import time
from typing import Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core.coreset import coreset_indices
from repro.core.summary import (
    label_distribution,
    per_label_mean,
    pxy_histogram,
    quantize,
)


def bucket_size(n: int, base: int = 8) -> int:
    """Round ``n`` up to a power of two (minimum ``base``) so jitted summary
    functions are shared across clients instead of retracing per client."""
    b = base
    while b < n:
        b *= 2
    return b


#: [M] int32 seeds -> [M, 2] uint32 keys, ``jax.random.PRNGKey`` of each
seed_keys = jax.vmap(jax.random.PRNGKey)


def _seed(seed) -> int:
    """``seed`` as the dispatch takes it: an int in ``[0, 2**31)``.  An
    int32 outside that range would wrap, and its key would differ from
    ``jax.random.PRNGKey(seed)``."""
    s = operator.index(seed)
    if not 0 <= s < 2**31:
        raise ValueError(f"PRNG seed {s} is outside [0, 2**31)")
    return s


# ---------------------------------------------------------------------------
# batched summary families — each maps client-stacked [M, N, ...] inputs to
# [M, summary_dim] with a single traced computation


def batched_label_distribution(labels, valid, num_classes: int):
    """[M, N] labels/valid -> [M, C] per-client P(y)."""
    return jax.vmap(lambda l, v: label_distribution(l, v, num_classes))(
        labels, valid)


def batched_pxy_histogram(feats, labels, valid, num_classes: int,
                          bins: int = 16, use_kernel: bool = False,
                          interpret: bool = False):
    """[M, N, D] features -> [M, C*D*B] per-client P(X|y) histograms.

    With ``use_kernel`` the M histograms collapse into one ``class_hist``
    launch over ``M*C`` offset classes (label-offset trick, DESIGN.md §4);
    otherwise the single-client one-hot einsum is vmapped.  ``interpret``
    runs that kernel in the Pallas interpreter (CPU tests).
    """
    if use_kernel:
        from repro.kernels.ops import class_hist
        m, n, d = feats.shape
        q = quantize(feats, bins).reshape(m * n, d)
        offset = labels + num_classes * jnp.arange(m, dtype=labels.dtype)[:, None]
        hist = class_hist(q, offset.reshape(-1), valid.reshape(-1),
                          m * num_classes, bins,
                          interpret=interpret)            # [M*C, D, B]
        hist = hist.reshape(m, num_classes, d, bins)
        denom = jnp.maximum(jnp.sum(hist, axis=-1, keepdims=True), 1.0)
        return (hist / denom).reshape(m, -1)
    return jax.vmap(lambda f, l, v: pxy_histogram(f, l, v, num_classes,
                                                  bins=bins))(
        feats, labels, valid)


def batched_per_label_mean(feats, labels, keep, num_classes: int,
                           use_kernel: bool = False, interpret: bool = False):
    """[M, k, H] features -> [M, C, H] per-client per-label means.

    Kernel path: one ``seg_mean`` launch over ``M*C`` offset classes.
    """
    if use_kernel:
        from repro.kernels.ops import seg_mean
        m, k, h = feats.shape
        offset = labels + num_classes * jnp.arange(m, dtype=labels.dtype)[:, None]
        out = seg_mean(feats.reshape(m * k, h), offset.reshape(-1),
                       keep.reshape(-1), m * num_classes,
                       interpret=interpret)                # [M*C, H]
        return out.reshape(m, num_classes, h)
    return jax.vmap(lambda f, l, kp: per_label_mean(f, l, kp, num_classes))(
        feats, labels, keep)


def batched_encoder_summary(feats, labels, valid, encoder_fn: Callable,
                            num_classes: int, coreset_k: int, keys,
                            use_kernel: bool = False):
    """The paper's summary for a whole client batch: [M, C*H + C].

    Coreset selection is vmapped (it is gather/sort bound), but the encoder —
    the FLOPs hot spot — runs as ONE call over the flattened ``[M*k, ...]``
    coreset so the accelerator sees a single large batch instead of M small
    ones.
    """
    def select(f, l, v, k):
        idx, keep = coreset_indices(l, v, num_classes, coreset_k, k)
        return f[idx], l[idx], keep

    core_f, core_l, keep = jax.vmap(select)(feats, labels, valid, keys)
    m = feats.shape[0]
    k_eff = core_f.shape[1]        # coreset_indices caps k at the bucket size
    enc = encoder_fn(core_f.reshape(m * k_eff, *feats.shape[2:]))
    enc = enc.reshape(m, k_eff, -1)                        # [M, k, H]
    means = batched_per_label_mean(enc, core_l, keep, num_classes,
                                   use_kernel=use_kernel)  # [M, C, H]
    p_y = batched_label_distribution(labels, valid, num_classes)
    return jnp.concatenate([means.reshape(m, -1), p_y], axis=-1)


# ---------------------------------------------------------------------------
# the engine: bucketing, padding, dispatch accounting


def _offset_batch_bound(method: str, num_classes: int, bins: int,
                        encoder_dim: int | None) -> int:
    """Largest power-of-two client count whose ``M·C`` offset classes fit
    one label-offset kernel launch (DESIGN.md §4)."""
    from repro.kernels.ops import class_hist_max_classes, seg_mean_max_classes
    if method == "pxy":
        limit = class_hist_max_classes(bins)
    else:
        if encoder_dim is None:
            raise ValueError("the encoder kernel path needs encoder_dim")
        limit = seg_mean_max_classes(encoder_dim)
    if num_classes > limit:
        raise ValueError(
            f"{num_classes} classes exceed the {limit} one {method} kernel "
            "launch holds")
    m = 1
    while 2 * m * num_classes <= limit:
        m *= 2
    return m


class SummaryResult(NamedTuple):
    summary: np.ndarray      # flat summary vector
    label_dist: np.ndarray   # empirical P(y) over the (padded) client data
    seconds: float           # amortized share of the batch wall time


@dataclasses.dataclass
class BatchStats:
    """Dispatch accounting — what the benchmark compares against the
    per-client path (one jitted dispatch per client)."""
    clients: int = 0
    dispatches: int = 0
    wall_s: float = 0.0


class BatchedSummaryEngine:
    """Computes summaries for many clients per jitted dispatch.

    Parameters mirror ``fl.client.timed_summary``; ``max_batch`` bounds the
    number of clients stacked into one dispatch (memory ceiling — the
    transient one-hots of the ``pxy`` family scale with M·N·D·B, so its
    default is far smaller than the other families').  With ``use_kernel``
    the label-offset accumulator of ``M·C`` classes must also fit in VMEM,
    so ``max_batch`` is cut to the largest power of two (the padded client
    axis) whose ``M·C`` the kernel holds.
    """

    def __init__(self, method: str, num_classes: int, *, encoder_fn=None,
                 coreset_k: int = 128, bins: int = 16,
                 use_kernel: bool = False, max_batch: int | None = None,
                 encoder_dim: int | None = None):
        if method not in ("py", "pxy", "encoder"):
            raise ValueError(f"unknown summary method: {method}")
        if method == "encoder" and encoder_fn is None:
            raise ValueError("encoder summary requires encoder_fn")
        if max_batch is None:
            max_batch = 16 if method == "pxy" else 256
        if use_kernel and method != "py":
            max_batch = min(max_batch, _offset_batch_bound(
                method, num_classes, bins, encoder_dim))
        self.method = method
        self.num_classes = num_classes
        self.encoder_fn = encoder_fn
        self.coreset_k = coreset_k
        self.bins = bins
        self.use_kernel = use_kernel
        self.max_batch = int(max_batch)
        self.stats = BatchStats()
        self._execs: dict = {}     # (bucket, feat_shape, M) -> AOT executable
        self._fn = jax.jit(self._build())

    def _build(self) -> Callable:
        C, bins, ck = self.num_classes, self.bins, self.coreset_k
        enc, uk = self.encoder_fn, self.use_kernel
        if self.method == "py":
            def family(feats, labels, valid, keys):
                ld = batched_label_distribution(labels, valid, C)
                return ld, ld
        elif self.method == "pxy":
            def family(feats, labels, valid, keys):
                m, n = feats.shape[:2]
                flat = feats.reshape(m, n, -1)
                s = batched_pxy_histogram(flat, labels, valid, C, bins=bins,
                                          use_kernel=uk)
                return s, batched_label_distribution(labels, valid, C)
        else:
            def family(feats, labels, valid, keys):
                s = batched_encoder_summary(feats, labels, valid, enc, C, ck,
                                            keys, use_kernel=uk)
                return s, batched_label_distribution(labels, valid, C)

        def batched(feats, labels, valid, seeds):
            return family(feats, labels, valid, seed_keys(seeds))
        return batched

    # ------------------------------------------------------------------
    def summarize(self, items: Iterable[tuple]) -> dict[int, SummaryResult]:
        """items: iterable of ``(client_id, feats, labels, valid, seed)``,
        ``seed`` an int in ``[0, 2**31)`` (the client's PRNG key is
        ``jax.random.PRNGKey(seed)``, made inside the dispatch).

        Returns ``{client_id: SummaryResult}``.  Clients are grouped by
        (size bucket, feature shape); each group is dispatched in chunks of
        at most ``max_batch`` clients.
        """
        groups: dict[tuple, list] = {}
        with obs.span("summary/load"):
            for cid, feats, labels, valid, seed in items:
                feats = np.asarray(feats, np.float32)
                labels = np.asarray(labels, np.int32)
                valid = np.asarray(valid, bool)
                b = bucket_size(feats.shape[0])
                groups.setdefault((b, feats.shape[1:]), []).append(
                    (cid, feats, labels, valid, _seed(seed)))

        out: dict[int, SummaryResult] = {}
        for (b, fs), group in groups.items():
            for lo in range(0, len(group), self.max_batch):
                self._dispatch(group[lo:lo + self.max_batch], b, fs, out)
        return out

    def summarize_clients(self, client_ids, sizes, load_fn: Callable,
                          seed_fn: Callable) -> dict[int, SummaryResult]:
        """Memory-bounded variant: group by size *before* loading any data,
        so at most ``max_batch`` clients' datasets are host-resident at a
        time (``summarize`` stages the whole stale set at once — fine for
        benchmarks, not for tens of thousands of stale clients).

        ``sizes[c]`` is client ``c``'s dataset size; ``load_fn(c)`` returns
        ``(feats, labels, valid)``; ``seed_fn(c)`` returns its PRNG seed, an
        int in ``[0, 2**31)``: the dispatch makes ``jax.random.PRNGKey`` of
        it bit for bit, so the same seed gives the same key and the same
        coreset as the per-client path, and the load touches no device.
        Clients sharing a size bucket must share a feature shape (true for
        every ``FederatedDataset``).
        """
        groups: dict[int, list] = {}
        for c in client_ids:
            groups.setdefault(bucket_size(int(sizes[c])), []).append(c)
        out: dict[int, SummaryResult] = {}
        for b, cids in groups.items():
            for lo in range(0, len(cids), self.max_batch):
                chunk = []
                with obs.span("summary/load"):
                    for c in cids[lo:lo + self.max_batch]:
                        feats, labels, valid = load_fn(c)
                        chunk.append((c, np.asarray(feats, np.float32),
                                      np.asarray(labels, np.int32),
                                      np.asarray(valid, bool),
                                      _seed(seed_fn(c))))
                self._dispatch(chunk, b, chunk[0][1].shape[1:], out)
        return out

    def _dispatch(self, chunk: list, b: int, fs: tuple,
                  out: dict[int, SummaryResult]) -> None:
        m = len(chunk)
        mp = bucket_size(m, base=1)    # pad the client axis too: one trace
        with obs.span("summary/assemble", slots=mp * b) as sp:
            feats = np.zeros((mp, b, *fs), np.float32)
            labels = np.zeros((mp, b), np.int32)
            valid = np.zeros((mp, b), bool)
            seeds = np.zeros(mp, np.int32)
            for i, (_cid, f, l, v, s) in enumerate(chunk):
                n = f.shape[0]
                feats[i, :n] = f
                labels[i, :n] = l
                valid[i, :n] = v
                seeds[i] = s
            sp.annotate(filled=int(valid.sum()))
        args = obs.device_put("summary/put", (feats, labels, valid, seeds))

        # AOT-compile per shape so compile time never lands in the timed
        # dispatch and the first chunk is not computed twice
        shape_key = (b, fs, mp)
        exec_ = self._execs.get(shape_key)
        if exec_ is None:
            exec_ = self._fn.lower(*args).compile()
            self._execs[shape_key] = exec_
        with obs.span("summary/execute"):
            t0 = time.perf_counter()
            summaries, lds = jax.block_until_ready(exec_(*args))
            dt = time.perf_counter() - t0
            s_np, ld_np = np.asarray(summaries), np.asarray(lds)

        self.stats.clients += m
        self.stats.dispatches += 1
        self.stats.wall_s += dt
        per_client = dt / m
        for i, (cid, *_rest) in enumerate(chunk):
            out[cid] = SummaryResult(s_np[i], ld_np[i], per_client)
