"""Pallas TPU kernel: per-label feature means (the paper's summary core).

The scatter-style segment mean is reformulated as a one-hot MXU matmul
(DESIGN.md §3): for each block of N coreset rows, build the [bn, C] one-hot
of labels in VREGs and accumulate  one_hotᵀ @ feats  into a [C, H] VMEM
accumulator together with per-class counts; the final grid step divides.
C*H stays VMEM-resident (C ≤ 600, H ≤ 256 in all paper settings).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(feats_ref, labels_ref, keep_ref, sums_ref, counts_ref,
            *, nblocks: int, num_classes: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    feats = feats_ref[...].astype(jnp.float32)              # [bn, H]
    labels = labels_ref[...]                                # [bn, 1] int32
    keep = keep_ref[...]                                    # [bn, 1] bool
    classes = jax.lax.broadcasted_iota(jnp.int32, (labels.shape[0],
                                                   num_classes), 1)
    oh = ((labels == classes) & keep).astype(jnp.float32)   # [bn, C]
    sums_ref[...] += jax.lax.dot_general(
        oh, feats, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [C, H]
    counts_ref[...] += jnp.sum(oh, axis=0, keepdims=True).T  # [C, 1]

    @pl.when(i == nblocks - 1)
    def _finish():
        sums_ref[...] = sums_ref[...] / jnp.maximum(counts_ref[...], 1.0)


@functools.partial(jax.jit, static_argnames=("num_classes", "bn", "interpret"))
def seg_mean_kernel(feats, labels, keep, num_classes: int, *, bn: int = 256,
                    interpret: bool = False):
    """feats [N,H], labels [N] int32, keep [N] bool -> [C,H] fp32 means."""
    n, h = feats.shape
    assert n % bn == 0, (n, bn)
    nblocks = n // bn
    sums, _ = pl.pallas_call(
        functools.partial(_kernel, nblocks=nblocks, num_classes=num_classes),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((num_classes, h), lambda i: (0, 0)),
            pl.BlockSpec((num_classes, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_classes, h), jnp.float32),
            jax.ShapeDtypeStruct((num_classes, 1), jnp.float32),
        ],
        interpret=interpret,
    )(feats, labels[:, None], keep[:, None])
    return sums
