"""Pallas TPU kernel: tiled pairwise squared distances (K-means hot spot).

||x_i - c_j||² = Σ_d (x²)_id + Σ_d (c²)_jd − 2 Σ_d x_id c_jd

The grid tiles (N × K × D); the D axis is the innermost (fastest) grid
dimension so each (bn × bk) output tile accumulates its partial matmul and
partial row/col norms in VMEM across D steps — one MXU dot per step with
128-aligned tiles.  fp32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, o_ref, *, nd: int):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # [bn, bd]
    c = c_ref[...].astype(jnp.float32)          # [bk, bd]
    acc = -2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    acc += jnp.sum(x * x, axis=1, keepdims=True)       # row norms (partial)
    acc += jnp.sum(c * c, axis=1)[None, :]             # col norms (partial)
    o_ref[...] += acc

    @pl.when(d == nd - 1)
    def _finish():
        o_ref[...] = jnp.maximum(o_ref[...], 0.0)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bd", "interpret"))
def pairwise_dist_kernel(x, c, *, bn: int = 128, bk: int = 128, bd: int = 512,
                         interpret: bool = False):
    """x [N,D], c [K,D] -> [N,K] fp32.  Caller pads to block multiples."""
    n, d = x.shape
    k = c.shape[0]
    assert n % bn == 0 and k % bk == 0 and d % bd == 0, (n, k, d, bn, bk, bd)
    nd = d // bd
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=(n // bn, k // bk, nd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bd), lambda i, j, l: (j, l)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c)
