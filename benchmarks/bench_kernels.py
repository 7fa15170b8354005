"""Kernel-layer benchmark: the paper's two hot spots as MXU contractions.

We time the jnp oracle and check the Pallas kernels against it.  Off the
TPU the kernels run in the Pallas interpreter, a correctness check only;
no kernel time is taken here.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._record import emit
from repro.kernels import ops, ref


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(n_clients: int = 2048, dim: int = 4096, k: int = 16,
        coreset: int = 1024, hdim: int = 64, classes: int = 62,
        bins: int = 16, feat_d: int = 512, seed: int = 0) -> list:
    rs = np.random.RandomState(seed)
    rows = []
    interp = not ops.on_tpu()

    # K-means assignment distances (clients x centroids)
    x = jnp.asarray(rs.normal(size=(n_clients, dim)), jnp.float32)
    c = jnp.asarray(rs.normal(size=(k, dim)), jnp.float32)
    jit_ref = jax.jit(ref.pairwise_dist_ref)
    t = _time(jit_ref, x, c)
    err = float(jnp.max(jnp.abs(ops.pairwise_dist(x, c, interpret=interp)
                                - jit_ref(x, c))))
    rows.append({"name": "kernels/pairwise_dist", "us": t * 1e6,
                 "derived": f"gflops={2 * n_clients * k * dim / t / 1e9:.1f};"
                            f"kernel_vs_ref_err={err:.1e}"})

    # summary per-label means (coreset x encoder dim)
    f = jnp.asarray(rs.normal(size=(coreset, hdim)), jnp.float32)
    lab = jnp.asarray(rs.randint(0, classes, coreset), jnp.int32)
    keep = jnp.ones(coreset, bool)
    jit_sm = jax.jit(ref.seg_mean_ref, static_argnums=3)
    t = _time(jit_sm, f, lab, keep, classes)
    err = float(jnp.max(jnp.abs(ops.seg_mean(f, lab, keep, classes,
                                             interpret=interp)
                                - jit_sm(f, lab, keep, classes))))
    rows.append({"name": "kernels/seg_mean", "us": t * 1e6,
                 "derived": f"kernel_vs_ref_err={err:.1e}"})

    # P(X|y) histogram
    q = jnp.asarray(rs.randint(0, bins, (coreset, feat_d)), jnp.int32)
    v = jnp.ones(coreset, bool)
    jit_ch = jax.jit(ref.class_hist_ref, static_argnums=(3, 4))
    t = _time(jit_ch, q, lab, v, classes, bins)
    err = float(jnp.max(jnp.abs(ops.class_hist(q, lab, v, classes, bins,
                                               interpret=interp)
                                - jit_ch(q, lab, v, classes, bins))))
    rows.append({"name": "kernels/class_hist", "us": t * 1e6,
                 "derived": f"kernel_vs_ref_err={err:.1e}"})
    return rows


def main(fast: bool = True):
    rows = run(n_clients=512 if fast else 4096, dim=1024 if fast else 8192,
               coreset=256 if fast else 1024, feat_d=128 if fast else 512)
    for r in rows:
        emit(r["name"], us=r["us"], text=r["derived"])
    return rows


if __name__ == "__main__":
    main(fast=False)
