"""Pallas TPU kernel: batched count-min sketch update (DESIGN.md §5).

A count-min sketch holds ``R`` rows of ``W`` counters; item ``y`` increments
counter ``h_r(y)`` in every row, with ``h_r`` an independent universal hash.
TPUs have no efficient scatter-add, so — like ``class_hist`` — the update
becomes a one-hot × one-hot MXU matmul, fused across a whole batch of
client sketches via the label-offset trick (DESIGN.md §3-§4):

    sketch[m, r, w] = Σ_n  1[seg_n == m] · valid_n · 1[h_r(label_n) == w]

Per grid step we hash the block's labels for all R rows at once in VREGs
(``h_r(y) = ((a_r·y + b_r) mod P) mod W``; the a/b multipliers are baked in
as compile-time constants), build the ``[bn, R·W]`` bucket one-hot with row
``r`` occupying lanes ``[r·W, (r+1)·W)``, and accumulate
``one_hot_segᵀ @ one_hot_bucket`` into the ``[M, R·W]`` VMEM accumulator.
One launch updates every client sketch in the dispatch.

``P = 131071`` (2¹⁷−1) keeps ``a·y + b`` well inside int32 for label
universes up to ~16k classes — every paper setting (C ≤ 600) by a wide
margin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

HASH_PRIME = 131_071  # 2**17 - 1; a*y + b < 2**31 for y < ~16k


def cm_hash_params(num_rows: int, seed: int = 0) -> tuple[tuple, tuple]:
    """Universal-hash coefficients for ``num_rows`` count-min rows.

    Returned as python-int tuples so they can be baked into kernel traces
    as compile-time constants (the sketch spec is static config).
    """
    rng = np.random.RandomState(seed)
    a = tuple(int(v) for v in rng.randint(1, HASH_PRIME, size=num_rows))
    b = tuple(int(v) for v in rng.randint(0, HASH_PRIME, size=num_rows))
    return a, b


def _kernel(labels_ref, seg_ref, valid_ref, o_ref, *, num_slots: int,
            width: int, a: tuple, b: tuple):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    labels = labels_ref[...]                                # [bn, 1] int32
    seg = seg_ref[...]                                      # [bn, 1] int32
    valid = valid_ref[...]                                  # [bn, 1] bool
    bn = labels.shape[0]
    r = len(a)
    # unrolled over the R (static, small) hash rows: python-int coefficients
    # stay weak compile-time scalars, which Pallas requires
    h = jnp.concatenate(
        [((labels * a[j] + b[j]) % HASH_PRIME) % width for j in range(r)],
        axis=1)                                             # [bn, R]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (bn, r, width), 2)
    oh_b = (h[:, :, None] == buckets).astype(jnp.float32)   # [bn, R, W]
    slots = jax.lax.broadcasted_iota(jnp.int32, (bn, num_slots), 1)
    oh_s = ((seg == slots) & valid).astype(jnp.float32)     # [bn, M]
    o_ref[...] += jax.lax.dot_general(
        oh_s, oh_b.reshape(bn, r * width), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [M, R*W]


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "width", "a", "b", "bn",
                                    "interpret"))
def sketch_update_kernel(labels, seg, valid, num_slots: int, width: int,
                         a: tuple, b: tuple, *, bn: int = 256,
                         interpret: bool = False):
    """labels [N] int32, seg [N] int32 slot ids, valid [N] bool ->
    [M, R, W] fp32 count-min increments (add to an existing sketch to
    update; sketches merge by addition)."""
    n = labels.shape[0]
    assert n % bn == 0, (n, bn)
    r = len(a)
    out = pl.pallas_call(
        functools.partial(_kernel, num_slots=num_slots, width=width,
                          a=tuple(a), b=tuple(b)),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_slots, r * width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_slots, r * width), jnp.float32),
        interpret=interpret,
    )(labels[:, None], seg[:, None], valid[:, None])
    return out.reshape(num_slots, r, width)
