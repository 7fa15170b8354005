"""Differential test harness: the summary/selection fast paths are pinned
to their exact baselines across >=20 random seeds, including under scenario
churn (clients appearing/disappearing between rounds).

  * ``streaming`` registry staleness decisions, refresh sets, and stored
    state exactly match the ``dict`` baseline round for round;
  * ``batched`` engine summaries bitwise-match the per-client
    ``timed_summary`` path (same bucket padding, same PRNG keys);
  * end-to-end: swapping registry (dict vs streaming) or engine (batched vs
    perclient) leaves the round loop's selection/refresh/accuracy traces
    identical under a churn scenario;
  * the async selection server (``server="async"``, zero ingest latency,
    sync refresh cadence — DESIGN.md §8) replays the sync trace bitwise
    for every registry backend (24-seed matrix in ``tests/test_server.py``).
"""
import jax
import numpy as np
import pytest

from repro.core import BatchedSummaryEngine, RefreshPolicy, SummaryRegistry
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.fl.client import timed_summary
from repro.shard import ShardedSummaryRegistry
from repro.sim import Scenario, make_scenario
from repro.stream import StreamingSummaryRegistry

SEEDS = range(24)          # >= 20 random seeds (acceptance floor)


# ---------------------------------------------------------------------------
# streaming registry ≡ dict baseline, under churn


@pytest.mark.parametrize("seed", SEEDS)
def test_streaming_decisions_match_dict_under_churn(seed):
    n, c, rounds = 30, 6, 10
    rs = np.random.RandomState(seed)
    policy = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)
    base = SummaryRegistry(n, policy)
    stream = StreamingSummaryRegistry(n, policy)
    scenario = make_scenario("mobile-churn", n, seed=seed)
    for rnd in range(rounds):
        plan = scenario.round_plan(rnd)
        for cl in plan.departed:
            base.remove(int(cl))
            stream.remove(int(cl))
        fresh = rs.dirichlet([0.4] * c, n).astype(np.float32)
        # baseline mask == per-client reference predicate, gated by the fleet
        want = base.stale_mask(rnd, fresh, active=plan.active)
        ref = np.asarray([base.needs_refresh(cl, rnd, fresh[cl])
                          for cl in range(n)]) & plan.active
        np.testing.assert_array_equal(want, ref)
        # streaming refresh set == dict refresh set, exactly
        got = stream.stale_clients(rnd, fresh, active=plan.active)
        np.testing.assert_array_equal(got, np.flatnonzero(want))
        # refresh a random subset (partial rounds), same on both sides
        todo = [int(cl) for cl in got if rs.rand() > 0.25]
        if todo:
            summaries = rs.rand(len(todo), 8).astype(np.float32)
            stream.update_batch(todo, rnd, summaries, fresh[todo])
            for i, cl in enumerate(todo):
                base.update(cl, rnd, summaries[i], fresh[cl])
        assert stream.refresh_count == base.refresh_count
        np.testing.assert_array_equal(stream.has_mask(), base.has_mask())
        np.testing.assert_array_equal(stream.last_refresh, base.last_refresh)
        have = np.flatnonzero(stream.has_mask())
        if have.size:
            np.testing.assert_array_equal(stream.matrix_rows(have),
                                          base.matrix_rows(have))


# ---------------------------------------------------------------------------
# sharded registry ≡ streaming baseline, under churn (DESIGN.md §7) — on
# whatever mesh the host exposes (1 device here; CI re-runs the shard
# tests on a forced 4-device host)


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_decisions_match_streaming_under_churn(seed):
    n, c, rounds = 30, 6, 10
    rs = np.random.RandomState(seed)
    policy = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)
    stream = StreamingSummaryRegistry(n, policy)
    # chunk_rows=8: the fleet spans multiple chunks + a zero-padded tail,
    # so the differential covers the chunked-scan path, not just 1 chunk
    shard = ShardedSummaryRegistry(n, policy, chunk_rows=8)
    scenario = make_scenario("mobile-churn", n, seed=seed)
    for rnd in range(rounds):
        plan = scenario.round_plan(rnd)
        for cl in plan.departed:
            stream.remove(int(cl))
            shard.remove(int(cl))
        fresh = rs.dirichlet([0.4] * c, n).astype(np.float32)
        want = stream.stale_clients(rnd, fresh, active=plan.active)
        got = shard.stale_clients(rnd, fresh, active=plan.active)
        np.testing.assert_array_equal(got, want)
        todo = [int(cl) for cl in got if rs.rand() > 0.25]
        if todo:
            summaries = rs.rand(len(todo), 8).astype(np.float32)
            stream.update_batch(todo, rnd, summaries, fresh[todo])
            shard.update_batch(todo, rnd, summaries, fresh[todo])
        assert shard.refresh_count == stream.refresh_count
        np.testing.assert_array_equal(shard.has_mask(), stream.has_mask())
        np.testing.assert_array_equal(shard.last_refresh,
                                      stream.last_refresh)
        have = np.flatnonzero(shard.has_mask())
        if have.size:
            np.testing.assert_array_equal(shard.matrix_rows(have),
                                          stream.matrix_rows(have))


# ---------------------------------------------------------------------------
# batched engine ≡ per-client path, bitwise


@pytest.fixture(scope="module")
def diff_data():
    # lognormal sizes => clients span several power-of-two buckets
    return FederatedDataset(small_spec(num_clients=16, num_classes=5,
                                       side=8, avg_samples=24), seed=9)


@pytest.fixture(scope="module")
def diff_engines(diff_data):
    C = diff_data.spec.num_classes
    return {m: BatchedSummaryEngine(m, C, bins=4) for m in ("py", "pxy")}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("method", ["py", "pxy"])
def test_batched_bitwise_matches_per_client(diff_data, diff_engines, method,
                                            seed):
    """The batched fast path is *bitwise* identical to the per-client
    baseline, for churn-shaped subsets of clients that appear/disappear
    between rounds."""
    data = diff_data
    n, C = data.spec.num_clients, data.spec.num_classes
    rs = np.random.RandomState(seed)
    engine = diff_engines[method]
    for rnd in range(2):
        present = np.flatnonzero(rs.rand(n) > 0.4)   # this round's fleet
        if present.size == 0:
            continue
        drift = float(rs.randint(0, 3)) * 0.25
        results = engine.summarize_clients(
            present, data.sizes,
            lambda c: data.client_data(c, drift),
            lambda c: rnd * 1000 + c)
        assert set(results) == set(int(c) for c in present)
        for c in present:
            feats, labels, valid = data.client_data(int(c), drift)
            s, ld, _ = timed_summary(method, feats, labels, valid, C, bins=4,
                                     key=jax.random.PRNGKey(rnd * 1000
                                                            + int(c)))
            np.testing.assert_array_equal(results[int(c)].summary, s)
            np.testing.assert_array_equal(results[int(c)].label_dist, ld)


# ---------------------------------------------------------------------------
# end-to-end: swapping the fast path leaves the round loop's trace unchanged


def _trace(h):
    # sim_time included: scenarios charge *modeled* summary costs, so the
    # clock itself must be identical across fast-path swaps
    return {k: h[k] for k in ("selected", "completed", "refreshes", "acc",
                              "n_active", "n_joined", "n_departed",
                              "dropped", "sim_time")}


def _churn_cfg(**kw):
    base = dict(rounds=5, clients_per_round=4, local_steps=2, summary="py",
                clustering="kmeans", num_clusters=3, refresh_max_age=3,
                refresh_kl=0.05, eval_every=2, seed=4)
    base.update(kw)
    return FLConfig(**base)


@pytest.fixture(scope="module")
def churn_setup():
    n = 18
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5, side=8,
                                       avg_samples=24), seed=11)
    # deadline stays on: summary costs are *modeled* (summary_cost/speed),
    # so straggler-timeout decisions are identical across fast-path swaps
    config = make_scenario("mobile-churn", n, seed=3).to_config()
    return data, config


@pytest.mark.slow
def test_streaming_registry_e2e_equals_dict_under_churn(churn_setup):
    data, sc_config = churn_setup
    h_dict = run_federated(data, _churn_cfg(registry="dict"),
                           scenario=Scenario.from_config(sc_config))
    h_stream = run_federated(data, _churn_cfg(registry="streaming"),
                             scenario=Scenario.from_config(sc_config))
    assert _trace(h_dict) == _trace(h_stream)


@pytest.mark.slow
def test_batched_engine_e2e_equals_perclient_under_churn(churn_setup):
    data, sc_config = churn_setup
    h_batched = run_federated(data, _churn_cfg(summary_engine="batched"),
                              scenario=Scenario.from_config(sc_config))
    h_per = run_federated(data, _churn_cfg(summary_engine="perclient"),
                          scenario=Scenario.from_config(sc_config))
    assert _trace(h_batched) == _trace(h_per)


@pytest.mark.slow
@pytest.mark.parametrize("registry", ["dict", "streaming", "sharded"])
def test_async_server_e2e_equals_sync_under_churn(churn_setup, registry):
    """The async selection server (zero ingest latency, sync refresh
    cadence — DESIGN.md §8) replays the sync trace bitwise under churn,
    for every registry backend.  ``tests/test_server.py`` extends this
    pin across 24 seeds and the clustering matrix."""
    data, sc_config = churn_setup
    kw = {"shard_chunk_rows": 8} if registry == "sharded" else {}
    h_sync = run_federated(data, _churn_cfg(registry=registry, **kw),
                           scenario=Scenario.from_config(sc_config))
    h_async = run_federated(data,
                            _churn_cfg(registry=registry, server="async",
                                       **kw),
                            scenario=Scenario.from_config(sc_config))
    assert _trace(h_sync) == _trace(h_async)


@pytest.mark.slow
def test_sharded_registry_e2e_equals_streaming_under_churn(churn_setup):
    """Identical refresh decisions + identical clustering input rows ⇒
    the whole round trace (selection, clock, accuracy) must match when
    only the registry implementation is swapped."""
    data, sc_config = churn_setup
    h_stream = run_federated(data, _churn_cfg(registry="streaming"),
                             scenario=Scenario.from_config(sc_config))
    h_shard = run_federated(data,
                            _churn_cfg(registry="sharded",
                                       shard_chunk_rows=8),
                            scenario=Scenario.from_config(sc_config))
    assert _trace(h_stream) == _trace(h_shard)
