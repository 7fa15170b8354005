"""Pallas TPU flash attention (forward) — the §Perf answer to the roofline's
dominant term.

The baseline XLA lowering materializes every [Bq×Bk×heads] f32 score tile to
HBM (they exceed VMEM), which makes attention HBM-bound at 4k+ sequence
lengths (EXPERIMENTS.md §Roofline).  This kernel keeps the online-softmax
state (m, l, acc) in VMEM scratch across the innermost KV-block grid axis,
so HBM traffic collapses to q/k/v/o streaming — the classic flash-attention
memory profile, tiled for the MXU (128-aligned Bq×Bk×D blocks).

Supports causal masking, sliding windows and GQA (q heads grouped onto KV
heads via the BlockSpec index map).  Validated against the pure-jnp oracle
(`repro.models.attention.attend`) in interpret mode on CPU, which its
tests ask for (``interpret=True``).  Training uses the custom_vjp streaming
implementation in models/attention.py (same math, autodiff-ready); this
kernel is the serving / prefill fast path and the deployment artifact for
the memory-term fix.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, nk: int, bq: int,
            bk: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)          # [bk, Dv]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_scr[...]                          # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q [B,H,Sq,D], k/v [B,KV,Sk,D] -> o [B,H,Sq,D].  Sq%bq == Sk%bk == 0."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    assert H % KV == 0 and Sq % bq == 0 and Sk % bk == 0
    G = H // KV
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          nk=nk, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
