"""Streaming subsystem (repro/stream, DESIGN.md §5): sketch algebra and
error bounds, kernel-vs-oracle equivalence, the registry's decisions
against the per-client predicate, and online-clustering quality on the
drift scenario."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import RefreshPolicy, kmeans
from repro.kernels import ops, ref
from repro.kernels.sketch_update import cm_hash_params
from repro.stream import (
    FleetSketches,
    OnlineClusterMaintainer,
    OnlinePolicy,
    SketchSpec,
    SummaryRegistry,
    cm_estimate,
    cm_label_dist,
    cm_merge,
    cm_update_batch,
    rp_update_batch,
)

SPEC = SketchSpec(num_rows=3, width=64)


# ---------------------------------------------------------------------------
# count-min sketches


def test_sketch_update_kernel_matches_ref(rs):
    for n, m, c, r, w in [(100, 4, 10, 3, 64), (257, 7, 62, 4, 32),
                          (64, 1, 5, 2, 16)]:
        labels = jnp.asarray(rs.randint(0, c, n), jnp.int32)
        seg = jnp.asarray(rs.randint(0, m, n), jnp.int32)
        valid = jnp.asarray(rs.rand(n) > 0.2)
        a, b = cm_hash_params(r, seed=1)
        got = ops.sketch_update(labels, seg, valid, m, w, a, b,
                                 interpret=True)
        want = ref.sketch_update_ref(labels, seg, valid, m, w, a, b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)
        # counts conservation: every valid item lands once per row
        assert float(np.asarray(got).sum()) == float(valid.sum()) * r


def test_cm_merge_is_exact(rs):
    """sketch(A ∪ B) == sketch(A) + sketch(B) — the mergeability contract."""
    labels = rs.randint(0, 20, (2, 80)).astype(np.int32)
    valid = rs.rand(2, 80) > 0.1
    parts = cm_update_batch(labels, valid, SPEC)
    merged = cm_merge(parts[0], parts[1])
    whole = cm_update_batch(labels.reshape(1, -1), valid.reshape(1, -1),
                            SPEC)[0]
    np.testing.assert_array_equal(merged, whole)


def test_cm_estimate_within_count_min_bounds(rs):
    """Estimates never undercount and overcount by at most e·n/W in
    expectation-with-slack (classic Cormode–Muthukrishnan bound)."""
    n, c = 400, 30
    labels = rs.randint(0, c, (1, n)).astype(np.int32)
    valid = np.ones((1, n), bool)
    sk = cm_update_batch(labels, valid, SPEC)[0]
    exact = np.bincount(labels[0], minlength=c).astype(np.float32)
    est = cm_estimate(sk, np.arange(c), SPEC)
    assert (est >= exact - 1e-6).all()                   # never undercounts
    bound = np.e * n / SPEC.width                        # per-row bound
    assert (est - exact).max() <= bound + 1e-6


def test_cm_label_dist_close_to_exact(rs):
    n, c = 300, 10
    labels = rs.randint(0, c, (3, n)).astype(np.int32)
    valid = rs.rand(3, n) > 0.15
    sk = cm_update_batch(labels, valid, SPEC)
    for m in range(3):
        exact = np.bincount(labels[m][valid[m]], minlength=c)
        exact = exact / exact.sum()
        got = cm_label_dist(sk[m], c, SPEC)
        assert np.abs(got - exact).sum() < 0.1           # small L1 error
    empty = cm_label_dist(np.zeros_like(sk[0]), c, SPEC)
    np.testing.assert_allclose(empty, 1.0 / c)           # uniform fallback


def test_fleet_sketches_update_and_merge(rs):
    fs = FleetSketches(6, SPEC)
    labels = rs.randint(0, 8, (2, 40)).astype(np.int32)
    valid = np.ones((2, 40), bool)
    feats = rs.rand(2, 40, 12).astype(np.float32)
    fs.update_batch([1, 4], labels, valid, feats=feats)
    dists = fs.label_dists(8)
    np.testing.assert_allclose(dists.sum(-1), 1.0, atol=1e-5)
    exact = np.bincount(labels[0], minlength=8) / 40
    assert np.abs(dists[1] - exact).sum() < 0.1
    # shard merge: two half-fleets sum to the whole
    other = FleetSketches(6, SPEC)
    other.update_batch([1], labels[:1], valid[:1], feats=feats[:1],
                       reset=False)
    before = fs.label_sk[1].copy()
    fs.merge_from(other)
    np.testing.assert_array_equal(fs.label_sk[1], before * 2)
    np.testing.assert_array_equal(fs.label_sk[4],
                                  cm_update_batch(labels[1:], valid[1:],
                                                  SPEC)[0])


def test_fleet_sketches_duplicate_ids_accumulate(rs):
    """reset=False must add every occurrence of a duplicated client id."""
    fs = FleetSketches(3, SPEC)
    labels = rs.randint(0, 8, (2, 10)).astype(np.int32)
    valid = np.ones((2, 10), bool)
    fs.update_batch([1, 1], labels, valid, reset=False)
    assert fs.counts[1] == 20
    whole = cm_update_batch(labels.reshape(1, -1), valid.reshape(1, -1),
                            SPEC)[0]
    np.testing.assert_array_equal(fs.label_sk[1], whole)


# ---------------------------------------------------------------------------
# sketch algebra — property tests (skip gracefully without hypothesis)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 60))
def test_cm_merge_commutative_and_associative(seed, n):
    """merge is plain addition over non-negative integer-valued counters,
    so it must commute and associate *exactly* (no float reordering)."""
    rs = np.random.RandomState(seed)
    labels = rs.randint(0, 25, (3, n)).astype(np.int32)
    valid = rs.rand(3, n) > 0.2
    a, b, c = cm_update_batch(labels, valid, SPEC)
    np.testing.assert_array_equal(cm_merge(a, b), cm_merge(b, a))
    np.testing.assert_array_equal(cm_merge(cm_merge(a, b), c),
                                  cm_merge(a, cm_merge(b, c)))


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 50), st.integers(2, 4))
def test_cm_update_concat_equals_merged_shards(seed, n, shards):
    """update on a concatenated batch == merge of per-shard updates — the
    linearity the streaming subsystem leans on for shard/merge topologies."""
    rs = np.random.RandomState(seed)
    labels = rs.randint(0, 30, n).astype(np.int32)
    valid = rs.rand(n) > 0.15
    whole = cm_update_batch(labels[None], valid[None], SPEC)[0]
    cuts = np.linspace(0, n, shards + 1).astype(int)
    merged = np.zeros_like(whole)
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        if hi > lo:
            merged = cm_merge(
                merged, cm_update_batch(labels[None, lo:hi],
                                        valid[None, lo:hi], SPEC)[0])
    np.testing.assert_array_equal(merged, whole)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 40))
def test_rp_update_concat_equals_merged_shards(seed, n):
    """The random-projection feature sketch is linear too: sketch of a
    concatenated stream == sum of shard sketches (float tolerance — the
    projection reduction order differs between the two groupings)."""
    rs = np.random.RandomState(seed)
    feats = rs.randn(1, n, 12).astype(np.float32)
    valid = rs.rand(1, n) > 0.2
    whole = rp_update_batch(feats, valid, SPEC)[0]
    cut = n // 2
    merged = (rp_update_batch(feats[:, :cut], valid[:, :cut], SPEC)[0]
              + rp_update_batch(feats[:, cut:], valid[:, cut:], SPEC)[0])
    np.testing.assert_allclose(merged, whole, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the registry's scan == the per-client predicate, round for round


def test_registry_matches_per_client_predicate(rs):
    n, c = 40, 6
    policy = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)
    reg = SummaryRegistry(n, policy)
    want_x = np.zeros((n, 12), np.float32)
    for rnd in range(15):
        fresh = rs.dirichlet([0.4] * c, n).astype(np.float32)
        want = [cl for cl in range(n)
                if reg.needs_refresh(cl, rnd, fresh[cl])]
        assert reg.stale_clients(rnd, fresh).tolist() == want
        # refresh only a random subset of the stale set (partial rounds)
        todo = [cl for cl in want if rs.rand() > 0.3]
        summaries = rs.rand(len(todo), 12).astype(np.float32)
        reg.update_batch(todo, rnd, summaries, fresh[todo])
        want_x[todo] = summaries
    np.testing.assert_array_equal(reg.dense(), want_x)


def test_streaming_remove_evicts_stale_row(rs):
    """Regression (churn): without ``remove``, a departed client's dense
    row keeps matching the drift scan as fresh and keeps feeding its stale
    summary to clustering — it could still be clustered and selected."""
    n, c = 8, 5
    policy = RefreshPolicy(max_age_rounds=100, kl_threshold=0.05)
    reg = SummaryRegistry(n, policy)
    fresh = rs.dirichlet([0.5] * c, n).astype(np.float32)
    summaries = rs.rand(n, 6).astype(np.float32) + 1.0    # no zero rows
    reg.update_batch(list(range(n)), 0, summaries, fresh)

    # the bug: after client 3 departs, its row still looks fresh and its
    # stale summary still sits in the clustering input
    assert not reg.needs_refresh(3, 1, fresh[3])
    assert np.any(reg.dense()[3] != 0)

    reg.remove(3)
    assert not reg.has_mask()[3]
    assert reg.needs_refresh(3, 1, fresh[3])              # rejoin => stale
    assert np.all(reg.dense()[3] == 0)                    # row evicted
    with pytest.raises(AssertionError):
        reg.matrix_rows(np.arange(n))                     # fleet incomplete

    # while absent, the active mask keeps it out of the refresh set...
    active = np.ones(n, bool)
    active[3] = False
    assert not reg.stale_mask(1, fresh, active=active)[3]
    # ...and clustering over live rows no longer sees it
    have = np.flatnonzero(reg.has_mask() & active)
    assert 3 not in have
    assert reg.matrix_rows(have).shape == (n - 1, 6)
    # on rejoin it is immediately stale again
    active[3] = True
    assert reg.stale_mask(1, fresh, active=active)[3]


def test_registry_remove_matches_never_summarized(rs):
    """An evicted client leaves the registry exactly as if it had never
    been summarized: mask, refresh round, decisions and dense row."""
    n, c = 6, 4
    policy = RefreshPolicy(max_age_rounds=100, kl_threshold=0.05)
    evicted = SummaryRegistry(n, policy)
    never = SummaryRegistry(n, policy)
    fresh = rs.dirichlet([0.5] * c, n).astype(np.float32)
    s = rs.rand(n, 5).astype(np.float32)
    evicted.update_batch(np.arange(n), 0, s, fresh)
    keep = np.delete(np.arange(n), 2)
    never.update_batch(keep, 0, s[keep], fresh[keep])
    evicted.remove(2)
    np.testing.assert_array_equal(evicted.has_mask(), never.has_mask())
    np.testing.assert_array_equal(evicted.last_refresh, never.last_refresh)
    np.testing.assert_array_equal(evicted.stale_mask(1, fresh),
                                  never.stale_mask(1, fresh))
    np.testing.assert_array_equal(evicted.dense(), never.dense())


# ---------------------------------------------------------------------------
# online cluster maintenance


def _drift_scenario(rs, n=600, k=4, d=16, frac=0.05):
    centers = rs.normal(0, 10, (k, d)).astype(np.float32)
    g = rs.randint(0, k, n)
    x = centers[g] + rs.normal(0, 0.5, (n, d)).astype(np.float32)
    drifted = rs.choice(n, int(frac * n), replace=False)
    x2 = x.copy()
    g2 = g.copy()
    g2[drifted] = (g[drifted] + 1) % k
    x2[drifted] = (centers[g2[drifted]]
                   + rs.normal(0, 0.5, (drifted.size, d)).astype(np.float32))
    return x, x2, drifted, k


def _best_agreement(a, b, k):
    return max((np.asarray(perm)[np.asarray(a)] == b).mean()
               for perm in itertools.permutations(range(k)))


def test_online_matches_full_kmeans_on_drift(rs):
    """Acceptance: assign-only maintenance reaches >=0.9 agreement with (or
    lower inertia than) a from-scratch K-means after low drift."""
    x, x2, drifted, k = _drift_scenario(rs)
    m = OnlineClusterMaintainer(k, OnlinePolicy(reseed_every=100))
    assert m.refresh(x, [], jax.random.PRNGKey(0))["mode"] == "full"
    info = m.refresh(x2, drifted, jax.random.PRNGKey(1))
    assert info["mode"] == "online"                     # no refit needed
    full = kmeans(jnp.asarray(x2), k, jax.random.PRNGKey(2))
    agreement = _best_agreement(full.assignment, m.assignment, k)
    assert agreement >= 0.9 or m.inertia <= float(full.inertia) + 1e-3


def test_online_running_inertia_is_exact(rs):
    x, x2, drifted, k = _drift_scenario(rs, n=300)
    m = OnlineClusterMaintainer(k, OnlinePolicy(reseed_every=100))
    m.refresh(x, [], jax.random.PRNGKey(0))
    m.refresh(x2, drifted, jax.random.PRNGKey(1))
    # running J must equal a from-scratch evaluation at frozen centroids
    d2 = ((x2[:, None] - m.centroids[None]) ** 2).sum(-1)
    np.testing.assert_allclose(m.inertia, d2.min(1).sum(), rtol=1e-4)
    np.testing.assert_array_equal(m.assignment, d2.argmin(1))


def test_online_full_refit_on_inertia_degradation(rs):
    x, _, _, k = _drift_scenario(rs, n=300)
    m = OnlineClusterMaintainer(k, OnlinePolicy(inertia_ratio=1.2,
                                                reseed_every=100))
    m.refresh(x, [], jax.random.PRNGKey(0))
    # catastrophic drift: every point jumps far away
    x3 = x + 100.0
    info = m.refresh(x3, np.arange(x.shape[0]), jax.random.PRNGKey(1))
    assert info["mode"] == "full"
    assert m.full_fits == 2
    assert m.inertia < 1.2 * m.last_full_inertia + 1e-6


def test_online_split_merge_never_hurts(rs):
    x, x2, drifted, k = _drift_scenario(rs, n=300, frac=0.1)
    m = OnlineClusterMaintainer(k, OnlinePolicy(reseed_every=1,
                                                inertia_ratio=10.0))
    m.refresh(x, [], jax.random.PRNGKey(0))
    before = m.inertia
    info = m.refresh(x2, drifted, jax.random.PRNGKey(1))
    # reseed either improved J or was reverted — never accepted a regression
    if info["mode"] == "reseed":
        assert m.inertia < before
    assert m.assignment.shape == (300,)
    assert set(np.unique(m.assignment)) <= set(range(k))


# ---------------------------------------------------------------------------
# end-to-end: streaming + online path in the round loop


@pytest.mark.slow
def test_federated_streaming_online_path():
    from repro.data.synthetic import FederatedDataset, small_spec
    from repro.fl import FLConfig, run_federated

    data = FederatedDataset(small_spec(num_clients=14, num_classes=5, side=8,
                                       avg_samples=24), seed=5)
    cfg = FLConfig(rounds=5, clients_per_round=4, local_steps=2, summary="py",
                   clustering="online", num_clusters=3,
                   drift_start=2, drift_per_round=0.5, refresh_kl=0.05,
                   eval_every=4, seed=5)
    h = run_federated(data, cfg)
    assert h["refreshes"][0] == 14                 # all summarized round 0
    assert h["refreshes"][-1] > 14                 # drift forced refreshes
    assert h["online_cluster"]["full_fits"] >= 1
    for sel in h["selected"]:
        assert len(set(sel)) == len(sel)
