"""Sharded fleet pipeline (DESIGN.md §7): decision exactness of the
chunked device-mesh drift scan vs the streaming baseline, weighted-kmeans
merge math, hierarchical clustering quality, and round-loop wiring.

Runs on whatever mesh the host exposes — CI re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the same
assertions hold on a genuinely split fleet axis.
"""
import jax
import numpy as np
import pytest

import jax.numpy as jnp

import repro.obs as obs
from repro.core import RefreshPolicy, kmeans, weighted_kmeans
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.shard import HierarchicalClusterMaintainer, ShardedSummaryRegistry
from repro.sim import drift_fleet, make_scenario, synthetic_fleet
from repro.stream import StreamingSummaryRegistry


def _seeded_pair(n, c, seed, **shard_kw):
    policy = RefreshPolicy(max_age_rounds=10 ** 6, kl_threshold=0.05)
    fleet = synthetic_fleet(n, c, 8, seed=seed)
    stream = StreamingSummaryRegistry(n, policy)
    shard = ShardedSummaryRegistry(n, policy, **shard_kw)
    for reg in (stream, shard):
        reg.update_batch(np.arange(n), 0, fleet.summaries, fleet.label_dists)
    return fleet, stream, shard


# ---------------------------------------------------------------------------
# chunked scan: decisions equal streaming through every code path


@pytest.mark.parametrize("chunk_rows", [7, 64, 10 ** 9])
def test_chunked_scan_matches_streaming(chunk_rows):
    """Multi-chunk + zero-padded tail, single padded chunk, and one whole-
    fleet chunk all produce the streaming registry's exact stale set."""
    fleet, stream, shard = _seeded_pair(301, 10, seed=0,
                                        chunk_rows=chunk_rows)
    for rnd, frac in ((1, 0.05), (2, 0.5)):
        fresh, _ = drift_fleet(fleet.label_dists, frac, seed=rnd)
        want = stream.stale_clients(rnd, fresh)
        got = shard.stale_clients(rnd, fresh)
        np.testing.assert_array_equal(want, got)
    assert shard.chunk_rows % shard.n_shards == 0
    # two scans, each ceil(N / chunk) dispatches (tail chunk zero-padded)
    assert shard.scan_chunks == 2 * -(-301 // shard.chunk_rows)


def test_decision_margin_paths_agree():
    """Margin 0 (pure device drift) and a margin wider than every drift
    value (every row re-checked with the exact numpy math) bracket the
    default band — all three must emit the streaming stale set."""
    stale = []
    for margin in (0.0, 1e-4, 1e9):
        fleet, stream, shard = _seeded_pair(200, 6, seed=3,
                                            decision_margin=margin)
        fresh, _ = drift_fleet(fleet.label_dists, 0.1, seed=4)
        np.testing.assert_array_equal(stream.stale_clients(1, fresh),
                                      shard.stale_clients(1, fresh))
        stale.append(shard.stale_clients(1, fresh))
        if margin == 1e9:
            assert shard.rechecked_rows >= 200   # exact path exercised
        if margin == 0.0:
            assert shard.rechecked_rows == 0     # device path exercised
    np.testing.assert_array_equal(stale[0], stale[2])


def test_padding_rows_never_go_stale():
    """With zero drift the tail-padding rows (all-zero dists on both
    sides) and the real rows all stay fresh — padding cannot leak into
    decisions."""
    fleet, _, shard = _seeded_pair(45, 5, seed=7, chunk_rows=8)
    assert shard.stale_clients(1, fleet.label_dists).size == 0


def test_registry_mesh_matches_host():
    _, _, shard = _seeded_pair(20, 4, seed=1)
    assert shard.n_shards == len(jax.devices())


# ---------------------------------------------------------------------------
# weighted kmeans (the global-merge primitive)


def test_weighted_kmeans_ignores_zero_weight_rows():
    x = jnp.asarray(np.array([[0., 0.], [0.1, 0.], [10., 10.],
                              [10.1, 10.], [100., 100.]], np.float32))
    w = jnp.asarray(np.array([1., 1., 1., 1., 0.], np.float32))
    res = weighted_kmeans(x, w, 2, jax.random.PRNGKey(0))
    cents = np.sort(np.asarray(res.centroids)[:, 0])
    np.testing.assert_allclose(cents, [0.05, 10.05], atol=1e-5)
    # the zero-weight outlier still gets an assignment, adds no inertia
    assert float(res.inertia) < 0.1
    assert res.assignment.shape == (5,)


def test_weighted_kmeans_equals_duplicated_points():
    """w-weighted points ≡ points repeated w times: the fixed-point
    objective J = Σ w·min-dist² matches within float tolerance."""
    rs = np.random.RandomState(0)
    pts = (rs.randn(40, 4).astype(np.float32)
           + np.repeat(np.eye(4, dtype=np.float32) * 8, 10, 0))
    w = rs.randint(1, 5, 40).astype(np.float32)
    dup = np.repeat(pts, w.astype(int), 0)
    rw = weighted_kmeans(jnp.asarray(pts), jnp.asarray(w), 4,
                         jax.random.PRNGKey(1))
    rd = kmeans(jnp.asarray(dup), 4, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(rw.inertia), float(rd.inertia),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# hierarchical two-level clustering


def test_hierarchical_recovers_latent_groups():
    """On a well-separated 8-group fleet split across 4 shards, the
    cluster-of-clusters assignment is as pure as a flat fit."""
    fleet = synthetic_fleet(600, 10, 16, n_groups=8, group_sep=6.0,
                            noise=0.2, seed=0)
    hm = HierarchicalClusterMaintainer(8, n_shards=4, local_k=16)
    hm.refresh(fleet.summaries, np.arange(600), jax.random.PRNGKey(0))
    purity = sum(np.unique(hm.assignment[fleet.groups == g],
                           return_counts=True)[1].max()
                 for g in range(8)) / 600
    assert purity >= 0.95
    assert np.unique(hm.assignment).size == 8
    assert hm.merges == 1 and hm.full_fits == 4


def test_hierarchical_online_rounds_and_live_mask():
    """Subsequent rounds do O(drifted) local work (no extra full fits in
    the low-drift regime) and dead rows never contribute centroids."""
    fleet = synthetic_fleet(400, 8, 8, n_groups=4, group_sep=6.0, seed=2)
    hm = HierarchicalClusterMaintainer(4, n_shards=4, local_k=8)
    live = np.ones(400, bool)
    live[:100] = False                 # shard 0 fully departed
    hm.refresh(fleet.summaries, np.arange(400), jax.random.PRNGKey(0),
               live=live)
    assert hm.full_fits == 3           # skipped slice fits nothing
    fits0 = hm.full_fits
    x = fleet.summaries.copy()
    drifted = np.asarray([150, 350])
    x[drifted] += 0.01
    out = hm.refresh(x, drifted, jax.random.PRNGKey(1), live=live)
    assert out["mode"] == "hierarchical"
    assert hm.full_fits == fits0       # assign-only, no local refit
    assert hm.merges == 2


# ---------------------------------------------------------------------------
# round-loop wiring


def test_run_federated_sharded_hierarchical():
    data = FederatedDataset(small_spec(num_clients=24, num_classes=5,
                                       side=8, avg_samples=20), seed=5)
    cfg = FLConfig(rounds=3, clients_per_round=4, local_steps=2,
                   summary="py", registry="sharded",
                   clustering="hierarchical", num_clusters=3, n_shards=2,
                   hier_local_k=4, eval_every=2, seed=1)
    h = run_federated(data, cfg)
    assert len(h["round"]) == 3
    assert h["online_cluster"]["merges"] >= 1
    assert all(len(s) <= 4 for s in h["selected"])


@pytest.mark.slow
@pytest.mark.parametrize("preset", ["mobile-churn", "straggler"])
def test_sharded_hierarchical_under_scenario_presets(preset):
    """The §7 support-matrix cell (sharded × hierarchical) survives churn,
    deadlines, and heavy-tailed speeds end to end."""
    n = 24
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5,
                                       side=8, avg_samples=20), seed=2)
    cfg = FLConfig(rounds=3, clients_per_round=4, local_steps=2,
                   summary="py", registry="sharded",
                   clustering="hierarchical", num_clusters=3, n_shards=2,
                   hier_local_k=4, refresh_max_age=2, eval_every=2, seed=0)
    h = run_federated(data, cfg, scenario=make_scenario(preset, n, seed=1))
    assert len(h["round"]) == 3
    assert h["online_cluster"]["merges"] >= 1


def test_unknown_clustering_rejected():
    data = FederatedDataset(small_spec(num_clients=8, num_classes=4,
                                       side=8, avg_samples=12), seed=0)
    with pytest.raises(ValueError, match="unknown clustering"):
        run_federated(data, FLConfig(rounds=1, clustering="nope"))


# ---------------------------------------------------------------------------
# the round path's copies under a profiler session


def _within(child, parents) -> bool:
    return any(p["ts"] <= child["ts"]
               and child["ts"] + child["dur"] <= p["ts"] + p["dur"]
               for p in parents)


@pytest.mark.parametrize("clustering", ["kmeans", "online"])
def test_profiled_rounds_count_every_copy(clustering, tmp_path):
    """Two sync rounds under a profiler: every host→device copy on the
    round path is a ``*/put`` span whose bytes match those reckoned here
    from the scan's chunk arenas, the summary batches and the clustering
    input, and each stage's child spans nest inside it.  Round 1 has no
    drift, so its only copies are the scan's."""
    from repro.core import bucket_size
    n, c, k, chunk = 24, 5, 3, 10
    data = FederatedDataset(small_spec(num_clients=n, num_classes=c, side=8,
                                       avg_samples=20), seed=5)
    cfg = FLConfig(rounds=2, clients_per_round=4, local_steps=1,
                   summary="py", registry="sharded", shard_chunk_rows=chunk,
                   clustering=clustering, num_clusters=k, eval_every=2,
                   seed=1)
    n0 = len(obs.profiled().events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        h = run_federated(data, cfg)
    finally:
        jax.profiler.stop_trace()
    assert h["refreshes"] == [n, n]            # round 1: nothing stale
    events = obs.profiled().events[n0:]

    def named(name):
        return [e for e in events if e["name"] == name]

    def put_bytes(name):
        return sum(e["args"]["bytes"] for e in named(name))

    shards = len(jax.devices())
    rows = -(-chunk // shards) * shards
    chunks = -(-n // rows)
    assert put_bytes("drift_scan/put") == 2 * chunks * 2 * rows * c * 4
    sample_bytes = 8 * 8 * 1 * 4 + 4 + 1
    by_bucket = np.bincount([bucket_size(int(s)) for s in data.sizes])
    want = sum(bucket_size(m, base=1) * (b * sample_bytes + 4)  # int32 seed
               for b, m in enumerate(by_bucket) if m)
    assert put_bytes("summary/put") == want
    x_bytes = n * c * 4                        # the P(y) summaries, float32
    if clustering == "online":                 # the fit, then one assign
        x_bytes += bucket_size(n) * c * 4 + k * c * 4
    assert put_bytes("recluster/put") == x_bytes
    assert {e["name"] for e in events if e["name"].endswith("/put")} == {
        "drift_scan/put", "summary/put", "recluster/put"}
    for child, parent in (("drift_scan/put", "drift_scan/chunks"),
                          ("drift_scan/chunks", "drift_scan"),
                          ("drift_scan", "scan"),
                          ("summary/", "client_summaries"),
                          ("recluster/", "recluster")):
        kids = [e for e in events if e["name"].startswith(child)]
        assert kids and all(_within(e, named(parent)) for e in kids), child
    assert len(named("select_devices")) == 2
