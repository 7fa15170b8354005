"""Whether the timed rounds were correct: what they produced against the
plain references, once the window has closed.

Every number is printed beside its limit.  Exact comparisons have limit 0:

* ``stale_mismatch``: clients in the symmetric difference between each
  window round's stale set and the reference's, summed over rounds;
* ``select_mismatch``: window rounds whose HACCS pick differs from the
  reference's on the same clustering;
* ``registry_mismatch``: clients whose registry row (summary, P(y) or
  refresh round) differs, after the window, from the last summary the
  rounds made or took for them;
* ``misassigned``: clients not at their nearest centroid, in a seeded
  sample of window rounds (the online maintainer's centroids, or for full
  K-means the means of the members);
* ``empty_clusters`` (full K-means only): clusters without a member in
  those rounds.  K-means seeded by k-means++ on thousands of distinct rows
  ends with K groups to spread each round's picks over; one that ends with
  fewer has collapsed, and the means of its members are still a fixed
  point.  (An online maintainer may leave a cluster empty by design; its
  centroids are recorded, so a collapse there is ``misassigned``.)

Where the server computes summaries, a seeded sample of the window's
summaries (with the largest client among them) is compared by the
reference of the configuration's summary, ``summaries/<summary>.py``.

``control=True`` puts the reference, one precision step below the
configuration's, in the program's place: its registry rows, its scan and
its assignments in bfloat16, and its summaries as the summary reference's
control computes them.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench import found
from chipbench import reference as ref
from chipbench.traffic.drift import stream

ASSIGN_SAMPLE = {"computed": 4, "uploaded": 2}   # window rounds re-checked


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


class Snapshot:
    """The host-side state the check reads, taken before the program's
    device state is freed."""

    def __init__(self, server):
        reg = server.ctx.registry
        self.summaries = reg.summaries
        self.label_dists = reg.label_dists
        self.last_refresh = reg.last_refresh
        self.has_summary = reg.has_summary
        self.num_clusters = server.ctx.num_clusters


def stale_sets(traffic, kl: float, upto: int, dtype=np.float32) -> dict:
    """The reference's stale set of every round 1..upto."""
    return {r: ref.stale_set(traffic.label_dists(r - 1),
                             traffic.label_dists(r), kl, dtype=dtype)
            for r in range(1, upto + 1)}


def matrices(server, rounds):
    """The registry's summary matrix after each of ``rounds`` (sorted), as
    a correct server holds it: replayed from what the rounds produced, or
    from the uploads."""
    tr, rec = server.traffic, server.rec
    if not server.computed:
        for r in rounds:
            yield r, tr.upload_rows(r)
        return
    x = None
    done = -1
    for r in rounds:
        for q in range(done + 1, r + 1):
            batch = rec.summaries[q]
            if x is None:
                x = np.zeros((tr.speeds.shape[0],
                              next(iter(batch.values())).shape[0]), np.float32)
            ids = np.fromiter(batch, np.int64, len(batch))
            if ids.size:
                x[ids] = np.stack([batch[c] for c in ids])
        done = r
        yield r, x


def run_check(server, snap: Snapshot, window: range, seed: int,
              control: bool = False, root=found.HERE) -> dict:
    """{name: (value, limit)} for every number compared."""
    cfg, tr, rec = server.cfg, server.traffic, server.rec
    srv = cfg["server"]
    rng = stream(seed, 7)
    last = window[-1] if len(window) else 0
    out = {}

    # drift scan
    want = stale_sets(tr, srv["refresh_kl"], last)
    got = (stale_sets(tr, srv["refresh_kl"], last,
                      dtype=np.dtype(jnp.bfloat16)) if control
           else {r: np.asarray(rec.stale[r], np.int64) for r in window})
    out["stale_mismatch"] = (sum(int(np.setxor1d(want[r], got[r]).size)
                                 for r in window), 0)

    # selection, on the clustering each round selected from
    bad = 0
    for r in window:
        pick = ref.haccs(rec.assignment[r], snap.num_clusters, tr.available[r],
                         tr.speeds, srv["clients_per_round"])
        bad += int(not np.array_equal(pick, rec.selected[r]))
    out["select_mismatch"] = (bad, 0)

    # registry rows after the window
    (_, want_x), = list(matrices(server, [last]))
    got_x = _bf16(snap.summaries) if control else snap.summaries
    got_ld = _bf16(snap.label_dists) if control else snap.label_dists
    refreshed = np.zeros(tr.speeds.shape[0], np.int64)   # round 0: all
    for r in range(1, last + 1):
        refreshed[want[r]] = r
    row_bad = np.any(got_x != want_x, axis=1)
    row_bad |= np.any(got_ld != tr.label_dists(last), axis=1)
    row_bad |= snap.last_refresh != refreshed
    row_bad |= ~snap.has_summary
    out["registry_mismatch"] = (int(row_bad.sum()), 0)

    # the clustering in sampled rounds
    k = srv["num_clusters"]
    picks = sorted(rng.choice(np.asarray(window),
                              min(ASSIGN_SAMPLE[cfg["summaries"]],
                                  len(window)), replace=False).tolist())
    bad = empty = 0
    kmeans = srv["clustering"] == "kmeans"
    for r, x in matrices(server, picks):
        asg = rec.assignment[r]
        cents = (rec.centroids[r] if r in rec.centroids
                 else ref.member_means(x, asg, k))
        if control:
            xb, cb = _bf16(x), _bf16(np.asarray(cents, np.float32))
            d = (np.sum(xb * xb, 1)[:, None] + np.sum(cb * cb, 1)[None]
                 - 2 * _bf16(xb @ cb.T))
            asg = np.argmin(d, axis=1)
        bad += ref.nearest_violations(x, asg, cents)
        empty += k - np.unique(asg[asg >= 0]).size
    out["misassigned"] = (bad, 0)
    if kmeans:
        out["empty_clusters"] = (int(empty), 0)

    if server.computed:
        out.update(summary_check(server, window, rng, control, root))
    return out


def summary_check(server, window: range, rng, control: bool, root) -> dict:
    """A seeded sample of the window's summaries, with the largest client
    among them, compared by the configuration's summary reference."""
    rec, tr = server.rec, server.traffic
    pairs = [(r, c) for r in window for c in rec.summaries[r]]
    if not pairs:
        return {}
    mod = found.module("summaries", server.cfg["server"]["summary"], root)
    idx = rng.choice(len(pairs), min(mod.SAMPLE, len(pairs)), replace=False)
    sample = {pairs[i] for i in idx}
    sizes = tr.data.sizes
    sample.add(max(pairs, key=lambda p: (sizes[p[1]], p)))
    items = [(r, c, *tr.client_data(r, c), rec.summaries[r][c])
             for r, c in sorted(sample)]
    return mod.compare(server.cfg, items, control)


def passed(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())
