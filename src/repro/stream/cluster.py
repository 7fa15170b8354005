"""Online cluster maintenance (DESIGN.md §5).

Full K-means over all N client summaries every refresh round is the last
O(N·K·D·iters) scan left in the server loop.  In the low-drift regime (a
few % of clients drift per round — the non-IID drift setting) almost all
of that work recomputes assignments that cannot have changed, because the
centroids are frozen between refits.  The maintainer exploits exactly that:

  * **assign-only updates** — drifted clients are re-assigned against the
    frozen centroids with one ``pairwise_sq_dist`` call over just the
    drifted rows (the Pallas kernel path applies unchanged): O(drifted·K·D)
    per round;
  * **running inertia** — per-client nearest-centroid distances are cached,
    so the global objective J is tracked exactly under frozen centroids by
    patching only the drifted entries;
  * **split/merge re-seeding** — every ``reseed_every`` refreshes, the two
    closest centroids are merged (count-weighted mean) and the freed slot
    re-seeds at the farthest member of the worst (highest-inertia) cluster,
    followed by ONE full assign pass; the move is kept only if J improves;
  * **full recluster fallback** — when running J degrades past
    ``inertia_ratio`` × the last full-fit J, ``core.kmeans`` runs from
    scratch and re-anchors the baseline.

Quality contract (asserted by ``tests/test_stream.py``): on the low-drift
scenario, online assignments reach ≥0.9 agreement with — or lower inertia
than — a from-scratch K-means fit.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core.batched_summary import bucket_size
from repro.core.kmeans import kmeans, pairwise_sq_dist


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _assign_fn(x, cents, use_kernel: bool):
    d2 = pairwise_sq_dist(x, cents, use_kernel)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


@dataclasses.dataclass(frozen=True)
class OnlinePolicy:
    inertia_ratio: float = 1.5   # full refit when J > ratio * last full J
    inertia_slack: float = 1e-6  # absolute per-point slack on the trigger —
                                 # keeps a perfect fit (J == 0, e.g. N <= K)
                                 # from forcing a refit on any drift
    reseed_every: int = 8        # split/merge attempt cadence (refreshes)
    use_kernel: bool = False     # route distances through the Pallas kernel
    max_iters: int = 50          # full-refit Lloyd iterations


class OnlineClusterMaintainer:
    """Keeps a K-clustering of the fleet's summary matrix fresh with
    O(drifted) work per round."""

    def __init__(self, k: int, policy: OnlinePolicy | None = None):
        self.k = k
        self.policy = policy or OnlinePolicy()
        self.centroids: np.ndarray | None = None   # [K, D]
        self.assignment: np.ndarray | None = None  # [N]
        self.dists: np.ndarray | None = None       # [N] nearest sq-dist
        self.last_full_inertia = np.inf
        self.full_fits = 0
        self.reseeds = 0
        self._refreshes = 0
        self._live: np.ndarray | None = None   # rows that are real clients

    # ------------------------------------------------------------------

    @property
    def inertia(self) -> float:
        """Running J under the current (frozen) centroids."""
        return float(self.dists.sum()) if self.dists is not None else np.inf

    def _assign(self, x: np.ndarray, rows=None
                ) -> tuple[np.ndarray, np.ndarray]:
        """Nearest centroid and its squared distance for ``x[rows]`` (all
        of ``x`` when ``rows`` is None)."""
        # pad the row axis to a power-of-two bucket so the jitted assign
        # compiles O(log N) times total, not once per drift-set size
        with obs.span("recluster/gather"):
            m = x.shape[0] if rows is None else len(rows)
            xp = np.zeros((bucket_size(m), x.shape[1]), np.float32)
            xp[:m] = x if rows is None else x[rows]
        xp, cents = obs.device_put("recluster/put", (xp, self.centroids))
        with obs.span("recluster/fit"):
            a, d = _assign_fn(xp, cents, self.policy.use_kernel)
            return (np.asarray(a[:m], np.int64).copy(),
                    np.asarray(d[:m]).copy())

    def _live_mask(self, n: int, live) -> np.ndarray:
        if live is None:
            return np.ones(n, bool)
        return np.asarray(live, bool)

    def full_fit(self, x: np.ndarray, key, live=None) -> dict:
        """Fit on the live rows only (under churn the fleet matrix carries
        zero rows for absent clients — clustering them would park a
        centroid on the origin); every row still gets an assignment so
        indexing stays stable, but absent rows carry zero inertia."""
        live = self._live_mask(x.shape[0], live)
        with obs.span("recluster/gather"):
            rows = np.asarray(x[live], np.float32)
        (rows,) = obs.device_put("recluster/put", (rows,))
        with obs.span("recluster/fit"):
            res = kmeans(rows, self.k, key,
                         max_iters=self.policy.max_iters,
                         use_kernel=self.policy.use_kernel)
            self.centroids = np.array(res.centroids)   # writable copy
            fit_assignment = np.asarray(res.assignment, np.int64)
        self.assignment, self.dists = self._assign(x)
        self.assignment[live] = fit_assignment
        self.dists[~live] = 0.0
        self.last_full_inertia = float(res.inertia)    # live-row objective
        self.full_fits += 1
        self._live = live
        return {"mode": "full", "inertia": self.inertia}

    # ------------------------------------------------------------------

    def refresh(self, x: np.ndarray, drifted_ids, key, live=None) -> dict:
        """Absorb one round: ``x`` is the full [N, D] summary matrix (rows
        outside ``drifted_ids`` unchanged since the last call); ``live``
        marks the rows that are real clients this round."""
        n = x.shape[0]
        live = self._live_mask(n, live)
        if (self.centroids is None or self.assignment is None
                or self.assignment.shape[0] != n):
            return self.full_fit(x, key, live=live)
        self._refreshes += 1
        self._live = live

        drifted = np.asarray(drifted_ids, np.int64)
        if drifted.size:
            a, d = self._assign(x, drifted)
            self.assignment[drifted] = a
            self.dists[drifted] = d
        self.dists[~live] = 0.0          # absent rows carry no inertia

        threshold = (self.policy.inertia_ratio * self.last_full_inertia
                     + self.policy.inertia_slack * int(live.sum()))
        if self.inertia > threshold:
            return self.full_fit(x, key, live=live)

        if self._refreshes % self.policy.reseed_every == 0:
            return self._split_merge(x)
        return {"mode": "online", "inertia": self.inertia}

    # ------------------------------------------------------------------

    def _split_merge(self, x: np.ndarray) -> dict:
        """Merge the two closest centroids, re-seed the freed slot inside
        the worst cluster, keep the move only if J improves.  Counts and
        candidates come from live rows only — absent (zero) rows must not
        weight merges or become re-seed points."""
        k = self.k
        if k < 2:
            return {"mode": "online", "inertia": self.inertia}
        live = getattr(self, "_live", None)
        live = self._live_mask(self.assignment.shape[0], live)
        counts = np.bincount(self.assignment[live],
                             minlength=k).astype(np.float64)
        per_cluster_j = np.bincount(self.assignment, weights=self.dists,
                                    minlength=k)
        worst = int(per_cluster_j.argmax())
        cd = ((self.centroids[:, None] - self.centroids[None]) ** 2).sum(-1)
        cd[np.diag_indices(k)] = np.inf
        i, j = np.unravel_index(int(cd.argmin()), cd.shape)
        if worst in (i, j) or counts[worst] == 0:
            return {"mode": "online", "inertia": self.inertia}

        old = (self.centroids.copy(), self.assignment.copy(),
               self.dists.copy(), self.inertia)
        w = counts[i] + counts[j]
        merged = ((counts[i] * self.centroids[i]
                   + counts[j] * self.centroids[j])
                  / max(w, 1.0)).astype(self.centroids.dtype)
        members = np.flatnonzero((self.assignment == worst) & live)
        far = members[int(self.dists[members].argmax())]
        self.centroids[i] = merged
        self.centroids[j] = x[far]
        self.assignment, self.dists = self._assign(x)   # one full pass
        self.dists[~live] = 0.0
        self.reseeds += 1
        if self.inertia >= old[3]:                       # no improvement
            self.centroids, self.assignment, self.dists, _ = old
            return {"mode": "online", "inertia": self.inertia}
        return {"mode": "reseed", "inertia": self.inertia}
