"""Plain references for what the timed rounds produce (a summary's own
reference is ``summaries/<summary>.py``).

Written from the semantics the configurations state, in numpy and plain
``jax.numpy``; nothing here imports the program or reads what it made.
``stale_set`` takes a ``dtype``: float32 for the reference, bfloat16 for
the control (one precision lower, in the program's place).

* ``stale_set``: the clients whose P(y) moved past ``refresh_kl`` (exact
  symmetric KL in float64 over the stored and the fresh rows);
* ``nearest_violations``: clients not at their nearest centroid, and
  ``member_means``, each cluster's mean;
* ``haccs``: largest-remainder quotas over clusters, fastest available
  first (the paper's section 2, HACCS).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic.drift import sym_kl_rows


# ---------------------------------------------------------------------------
# drift scan


def stale_set(stored: np.ndarray, fresh: np.ndarray, threshold: float,
              dtype=np.float32) -> np.ndarray:
    """Ids whose symmetric KL between stored and fresh P(y) passes
    ``threshold``; rows are compared at ``dtype``."""
    p = np.asarray(stored).astype(dtype)
    q = np.asarray(fresh).astype(dtype)
    diff = np.flatnonzero(np.any(p != q, axis=1))   # equal rows: KL is 0
    kl = sym_kl_rows(p[diff].astype(np.float64), q[diff].astype(np.float64))
    return diff[kl > threshold]


# ---------------------------------------------------------------------------
# clustering and selection


def nearest_violations(x: np.ndarray, assignment: np.ndarray,
                       centroids: np.ndarray, block: int = 2048) -> int:
    """Clients whose assigned centroid is farther than the nearest by more
    than the rounding of a one-bfloat16-pass distance: |d_a - d_min| >
    2**-6 * |x| * max|c| (each distance's dot product is off by at most
    2**-8 of |x||c| for rounding both operands, twice for the -2 x.c)."""
    c = np.asarray(centroids, np.float32)
    cc = np.sum(np.square(c, dtype=np.float64), axis=1)
    cmax = math.sqrt(cc.max())
    bad = 0
    for lo in range(0, x.shape[0], block):
        xb = np.asarray(x[lo:lo + block], np.float32)
        xx = np.sum(np.square(xb, dtype=np.float64), axis=1)
        d = xx[:, None] + cc[None, :] - 2.0 * xb @ c.T
        a = np.asarray(assignment[lo:lo + block])
        gap = d[np.arange(xb.shape[0]), a] - d.min(axis=1)
        bad += int(np.sum(gap > 2.0 ** -6 * np.sqrt(xx) * cmax))
    return bad


def member_means(x: np.ndarray, assignment: np.ndarray, k: int,
                 block: int = 2048) -> np.ndarray:
    """Mean of each cluster's members (a Lloyd fixed point's centroids),
    summed in float32 blocks; an empty cluster gets an infinitely far
    centroid."""
    sums = np.zeros((k, x.shape[1]), np.float64)
    for lo in range(0, x.shape[0], block):
        onehot = np.eye(k, dtype=np.float32)[assignment[lo:lo + block]]
        sums += onehot.T @ np.asarray(x[lo:lo + block], np.float32)
    cnt = np.bincount(assignment, minlength=k)
    out = sums / np.maximum(cnt, 1)[:, None]
    out[cnt == 0] = 1e30
    return out


def quotas(counts: np.ndarray, per_round: int) -> np.ndarray:
    """Largest-remainder quotas proportional to ``counts``, capped at each
    cluster's population; capped surplus goes to clusters with room, by
    descending remainder, ties to the lower cluster id."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros_like(counts)
    per_round = min(per_round, total)
    exact = per_round * counts / total
    q = np.minimum(np.floor(exact).astype(np.int64), counts)
    while q.sum() < per_round:
        room = np.flatnonzero(counts > q)
        rank = sorted(room, key=lambda c: (-(exact[c] - q[c]), c))
        for c in rank[:per_round - q.sum()]:
            q[c] += 1
    return q


def haccs(assignment: np.ndarray, num_clusters: int, available: np.ndarray,
          speeds: np.ndarray, per_round: int) -> np.ndarray:
    """The clients HACCS picks, in the order it picks them."""
    ok = np.asarray(available, bool)
    asg = np.asarray(assignment)
    counts = np.bincount(asg[ok & (asg >= 0)], minlength=num_clusters)
    q = quotas(counts, per_round)

    def fastest(ids):
        return sorted(ids.tolist(), key=lambda c: (-speeds[c], c))
    chosen = []
    for c in range(num_clusters):
        if q[c]:
            chosen += fastest(np.flatnonzero(ok & (asg == c)))[:q[c]]
    if len(chosen) < per_round:
        rest = np.setdiff1d(np.flatnonzero(ok), chosen)
        chosen += fastest(rest)[:per_round - len(chosen)]
    return np.asarray(chosen[:per_round], np.int64)
