"""Differential test harness: the summary/selection fast paths are pinned
to their exact baselines across >=20 random seeds, including under scenario
churn (clients appearing/disappearing between rounds).

  * the registry's staleness decisions match a plain per-client
    ``sym_kl`` loop round for round, and a multi-chunk scan with a
    zero-padded tail matches the whole-fleet scan in decisions and state;
  * ``batched`` engine summaries bitwise-match the per-client
    ``timed_summary`` baseline (same bucket padding, same PRNG keys);
  * end-to-end: a chunked scan and an all-rechecked scan leave the round
    loop's selection/refresh/accuracy traces identical under a churn
    scenario;
  * the async selection server (``server="async"``, zero ingest latency,
    sync refresh cadence — DESIGN.md §8) replays the sync trace bitwise
    for every scan layout (24-seed matrix in ``tests/test_server.py``).
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import BatchedSummaryEngine, RefreshPolicy, sym_kl
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, rounds, run_federated
from repro.fl.client import timed_summary
from repro.sim import Scenario, make_scenario
from repro.stream import SummaryRegistry
from scan_layouts import FLCONFIG

SEEDS = range(24)          # >= 20 random seeds (acceptance floor)


# ---------------------------------------------------------------------------
# registry ≡ a plain per-client sym_kl loop, under churn


@pytest.mark.parametrize("seed", SEEDS)
def test_registry_decisions_match_per_client_loop_under_churn(seed):
    n, c, rounds = 30, 6, 10
    rs = np.random.RandomState(seed)
    policy = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)
    reg = SummaryRegistry(n, policy)
    # the reference: the stored P(y) and refresh round of every client
    # that holds a summary, kept apart from the registry's arenas
    ref_ld, ref_round = {}, {}
    scenario = make_scenario("mobile-churn", n, seed=seed)
    for rnd in range(rounds):
        plan = scenario.round_plan(rnd)
        for cl in plan.departed:
            reg.remove(int(cl))
            ref_ld.pop(int(cl), None)
            ref_round.pop(int(cl), None)
        fresh = rs.dirichlet([0.4] * c, n).astype(np.float32)
        want = [cl for cl in range(n) if plan.active[cl] and (
            cl not in ref_ld
            or rnd - ref_round[cl] >= policy.max_age_rounds
            or sym_kl(ref_ld[cl], fresh[cl]) > policy.kl_threshold)]
        got = reg.stale_clients(rnd, fresh, active=plan.active)
        np.testing.assert_array_equal(got, want)
        # refresh a random subset (partial rounds), same on both sides
        todo = [int(cl) for cl in got if rs.rand() > 0.25]
        if todo:
            summaries = rs.rand(len(todo), 8).astype(np.float32)
            reg.update_batch(todo, rnd, summaries, fresh[todo])
            for i, cl in enumerate(todo):
                ref_ld[cl], ref_round[cl] = fresh[cl], rnd
        np.testing.assert_array_equal(np.flatnonzero(reg.has_mask()),
                                      sorted(ref_ld))
        for cl, r in ref_round.items():
            assert reg.last_refresh[cl] == r
            np.testing.assert_array_equal(reg.label_dists[cl], ref_ld[cl])


# ---------------------------------------------------------------------------
# padded multi-chunk scan ≡ whole-fleet scan, under churn (DESIGN.md §7) —
# on whatever mesh the host exposes (1 device here; CI re-runs the shard
# tests on a forced 4-device host)


@pytest.mark.parametrize("seed", SEEDS)
def test_padded_chunks_match_whole_fleet_under_churn(seed):
    n, c, rounds = 30, 6, 10
    rs = np.random.RandomState(seed)
    policy = RefreshPolicy(max_age_rounds=4, kl_threshold=0.08)
    whole = SummaryRegistry(n, policy)
    # chunk_rows=8: the fleet spans multiple chunks + a zero-padded tail,
    # so the differential covers the chunked-scan path, not just 1 chunk
    chunked = SummaryRegistry(n, policy, chunk_rows=8)
    assert whole.chunk_rows >= n > 3 * chunked.chunk_rows   # 1 vs 4 chunks
    scenario = make_scenario("mobile-churn", n, seed=seed)
    for rnd in range(rounds):
        plan = scenario.round_plan(rnd)
        for cl in plan.departed:
            whole.remove(int(cl))
            chunked.remove(int(cl))
        fresh = rs.dirichlet([0.4] * c, n).astype(np.float32)
        want = whole.stale_clients(rnd, fresh, active=plan.active)
        got = chunked.stale_clients(rnd, fresh, active=plan.active)
        np.testing.assert_array_equal(got, want)
        todo = [int(cl) for cl in got if rs.rand() > 0.25]
        if todo:
            summaries = rs.rand(len(todo), 8).astype(np.float32)
            whole.update_batch(todo, rnd, summaries, fresh[todo])
            chunked.update_batch(todo, rnd, summaries, fresh[todo])
        assert chunked.refresh_count == whole.refresh_count
        np.testing.assert_array_equal(chunked.has_mask(), whole.has_mask())
        np.testing.assert_array_equal(chunked.last_refresh,
                                      whole.last_refresh)
        have = np.flatnonzero(chunked.has_mask())
        if have.size:
            np.testing.assert_array_equal(chunked.matrix_rows(have),
                                          whole.matrix_rows(have))


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
@pytest.mark.parametrize("layout", ["padded", "rows"])
def test_chunked_registry_e2e_equals_all_rechecked_under_churn(
        churn_setup, layout, monkeypatch):
    """Identical refresh decisions + identical clustering input rows ⇒
    the whole round trace (selection, clock, accuracy) must match between
    a chunked device scan and a run whose every drift is re-decided by the
    exact host math (an infinite decision margin)."""
    data, sc_config = churn_setup
    h_chunk = run_federated(data, _churn_cfg(**FLCONFIG[layout]),
                            scenario=Scenario.from_config(sc_config))
    monkeypatch.setattr(rounds, "SummaryRegistry", functools.partial(
        SummaryRegistry, decision_margin=np.inf))
    h_exact = run_federated(data, _churn_cfg(),
                            scenario=Scenario.from_config(sc_config))
    assert _trace(h_chunk) == _trace(h_exact)


@pytest.mark.slow
@pytest.mark.parametrize("layout", list(FLCONFIG))
def test_async_server_e2e_equals_sync_under_churn(churn_setup, layout):
    """The async selection server (zero ingest latency, sync refresh
    cadence — DESIGN.md §8) replays the sync trace bitwise under churn,
    for every scan layout.  ``tests/test_server.py`` extends this pin
    across 24 seeds and the clustering matrix."""
    data, sc_config = churn_setup
    kw = FLCONFIG[layout]
    h_sync = run_federated(data, _churn_cfg(**kw),
                           scenario=Scenario.from_config(sc_config))
    h_async = run_federated(data, _churn_cfg(server="async", **kw),
                            scenario=Scenario.from_config(sc_config))
    assert _trace(h_sync) == _trace(h_async)
