"""Pallas TPU kernel: fused per-class feature histograms (P(X|y) baseline).

TPUs have no efficient scatter-add; the histogram becomes a one-hot × one-hot
MXU matmul (DESIGN.md §3):

    hist[c, d, b] = Σ_n  1[label_n == c] · 1[q_nd == b]

Per grid step we build the [bn, B·bd] bin one-hot from B lane-aligned
[bn, bd] compares laid side by side (bin-major), and accumulate
one_hot_labelᵀ @ one_hot_bin into the [C, B·bd] VMEM tile for the current D
block.  Grid = (D blocks, N blocks) with N innermost so each D tile
accumulates then retires.  B never sits on the lane axis: a [bn, bd, B]
one-hot pads B up to 128 lanes (16 MiB at bn=256, bd=128), which the
chip's compiler refuses for lack of VMEM.  The wrapper restores the
[C, D, B] layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, labels_ref, valid_ref, o_ref, *, num_classes: int,
            bins: int):
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]                                          # [bn, bd] int32
    labels = labels_ref[...]                                # [bn, 1]
    valid = valid_ref[...]                                  # [bn, 1]
    bn = q.shape[0]
    classes = jax.lax.broadcasted_iota(jnp.int32, (bn, num_classes), 1)
    oh_l = ((labels == classes) & valid).astype(jnp.float32)     # [bn, C]
    oh_b = jnp.concatenate([(q == b).astype(jnp.float32)
                            for b in range(bins)], axis=1)       # [bn, B*bd]
    o_ref[...] += jax.lax.dot_general(
        oh_l, oh_b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # [C, B*bd]


@functools.partial(jax.jit,
                   static_argnames=("num_classes", "bins", "bn", "bd",
                                    "interpret"))
def class_hist_kernel(q, labels, valid, num_classes: int, bins: int, *,
                      bn: int = 256, bd: int = 128, interpret: bool = False):
    """q [N,D] int32 bins, labels [N], valid [N] -> [C, D, B] fp32 counts."""
    n, d = q.shape
    assert n % bn == 0 and d % bd == 0, (n, d, bn, bd)
    out = pl.pallas_call(
        functools.partial(_kernel, num_classes=num_classes, bins=bins),
        grid=(d // bd, n // bn),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda j, i: (i, j)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_classes, bd * bins), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((num_classes, d * bins), jnp.float32),
        interpret=interpret,
    )(q, labels[:, None], valid[:, None])
    # column block j holds [B, bd] for D block j (bin-major)
    out = out.reshape(num_classes, d // bd, bins, bd)
    return out.transpose(0, 1, 3, 2).reshape(num_classes, d, bins)
