"""jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples and exposes the same contract as
ref.py.  The kernels target the TPU: ``interpret`` is an explicit argument
(off by default), so a CPU caller that wants the Pallas interpreter asks
for it and nothing drops into it silently.  ``on_tpu`` is the one place
the library decides whether its main path uses the kernels at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.class_hist import class_hist_kernel
from repro.kernels.pairwise_dist import pairwise_dist_kernel
from repro.kernels.seg_mean import seg_mean_kernel
from repro.kernels.sketch_update import sketch_update_kernel

# VMEM one label-offset accumulator may take.  seg_mean and class_hist keep
# their whole [classes, ·] output block resident across the N grid, so the
# offset class count M*C is bounded by it.  The chip's compiler (v5e)
# accepted seg_mean at 16384 classes x H=32 and refused 31744; it accepted
# class_hist at [1024, 8*128] and refused [1984, 8*128] and [992, 16*128].
ACC_VMEM_BYTES = 8 * 2 ** 20


def on_tpu() -> bool:
    """Whether the default backend is the TPU — the main path's one switch
    between the Pallas kernels and the jnp references."""
    return jax.default_backend() == "tpu"


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def seg_mean_max_classes(h: int) -> int:
    """Most (offset) classes one ``seg_mean`` launch holds at width ``h``."""
    return ACC_VMEM_BYTES // (4 * _lanes(h))


def class_hist_max_classes(bins: int, bd: int = 128) -> int:
    """Most (offset) classes one ``class_hist`` launch holds at ``bins``.
    Its output block moves with the D block, so it is double-buffered."""
    return ACC_VMEM_BYTES // (2 * 4 * _lanes(bins * bd))


def _check_classes(kernel: str, num_classes: int, limit: int) -> None:
    if num_classes > limit:
        raise ValueError(
            f"{kernel}: {num_classes} classes exceed the {limit} its VMEM "
            "accumulator holds; split the launch (fewer clients per "
            "label-offset batch)")


def _pad_to(x, axis: int, mult: int, value=0):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def pairwise_dist(x, c, *, bn: int = 128, bk: int = 128, bd: int = 256,
                  interpret: bool = False):
    """[N,D] × [K,D] -> [N,K] squared distances (pads internally)."""
    n, k = x.shape[0], c.shape[0]
    bn = min(bn, max(8, n))
    bk = min(bk, max(8, k))
    bd = min(bd, max(8, x.shape[1]))
    xp = _pad_to(_pad_to(x, 0, bn), 1, bd)
    cp = _pad_to(_pad_to(c, 0, bk), 1, bd)
    out = pairwise_dist_kernel(xp, cp, bn=bn, bk=bk, bd=bd,
                               interpret=interpret)
    return out[:n, :k]


def seg_mean(feats, labels, keep, num_classes: int, *, bn: int = 256,
             interpret: bool = False):
    """[N,H] per-label means -> [C,H]."""
    n = feats.shape[0]
    _check_classes("seg_mean", num_classes,
                   seg_mean_max_classes(feats.shape[1]))
    bn = min(bn, max(8, n))
    fp = _pad_to(feats, 0, bn)
    lp = _pad_to(labels, 0, bn)
    kp = _pad_to(keep, 0, bn, value=False)
    return seg_mean_kernel(fp, lp, kp, num_classes, bn=bn,
                           interpret=interpret)


def sketch_update(labels, seg, valid, num_slots: int, width: int,
                  a: tuple, b: tuple, *, bn: int = 256,
                  interpret: bool = False):
    """[N] labels / slot ids / valid -> [M, R, W] count-min increments."""
    n = labels.shape[0]
    bn = min(bn, max(8, n))
    lp = _pad_to(labels, 0, bn)
    sp = _pad_to(seg, 0, bn)
    vp = _pad_to(valid, 0, bn, value=False)
    return sketch_update_kernel(lp, sp, vp, num_slots, width, tuple(a),
                                tuple(b), bn=bn, interpret=interpret)


def class_hist(q, labels, valid, num_classes: int, bins: int, *,
               bn: int = 256, bd: int = 128, interpret: bool = False):
    """[N,D] quantized -> [C,D,B] counts."""
    n, d = q.shape
    bn = min(bn, max(8, n))
    bd = min(bd, max(8, d))
    _check_classes("class_hist", num_classes,
                   class_hist_max_classes(bins, bd))
    qp = _pad_to(_pad_to(q, 0, bn, value=-1), 1, bd, value=-1)
    lp = _pad_to(labels, 0, bn)
    vp = _pad_to(valid, 0, bn, value=False)
    out = class_hist_kernel(qp, lp, vp, num_classes, bins, bn=bn, bd=bd,
                            interpret=interpret)
    return out[:, :d, :]
