"""Async selection server (DESIGN.md §8): unit tests for the event
engine, snapshot store, ingest queue and refresher, plus the 24-seed
differential pin — ``server="async"`` with zero ingest latency and the
sync refresh cadence produces traces bitwise-identical to
``server="sync"`` across scan layout × clustering backends under churn.
"""
import numpy as np
import pytest

from repro.core import RefreshPolicy
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.server import (
    EventQueue, IngestQueue, RegistrySnapshot, SnapshotStore, StalenessPolicy,
    Stage, capture,
)
from repro.sim import Scenario, make_scenario
from repro.stream import SummaryRegistry
from scan_layouts import FLCONFIG, MATRIX as _MATRIX

SEEDS = range(24)          # >= 20 random seeds (acceptance floor)


# ---------------------------------------------------------------------------
# event engine


def test_event_queue_orders_by_round_stage_seq():
    q = EventQueue()
    q.push(1, Stage.SELECT, "a")
    q.push(0, Stage.TRAIN, "b")
    q.push(0, Stage.MEMBERSHIP, "c")
    q.push(0, Stage.MEMBERSHIP, "d")   # FIFO within (round, stage)
    q.push(2, Stage.PUBLISH, "e")
    q.push(0, Stage.PUBLISH, "f")
    got = [q.pop().kind for _ in range(len(q))]
    assert got == ["c", "d", "f", "b", "a", "e"]


def test_event_queue_run_is_deterministic_and_total():
    order1, order2 = [], []
    for order in (order1, order2):
        q = EventQueue()

        def handler(ev, order=order, q=q):
            order.append((ev.round_idx, ev.stage, ev.seq))
            # handlers may push forward in time (background publish)
            if ev.stage == Stage.REFRESH and ev.round_idx < 2:
                q.push(ev.round_idx + 1, Stage.PUBLISH, "ev")
        for r in range(3):
            q.push(r, Stage.REFRESH, "ev")
            q.push(r, Stage.SELECT, "ev")
        n = q.run({"ev": handler})
        assert n == len(order)
    assert order1 == order2
    # pushed PUBLISH events land before the later round's REFRESH
    assert order1.index((1, Stage.PUBLISH, 6)) < order1.index(
        (1, Stage.REFRESH, 2))


def test_event_queue_unknown_kind_fails_loudly():
    q = EventQueue()
    q.push(0, Stage.SCAN, "mystery")
    with pytest.raises(KeyError, match="mystery"):
        q.run({})


# ---------------------------------------------------------------------------
# snapshots


def _registry(n=6, c=4):
    reg = SummaryRegistry(n, RefreshPolicy(4, 0.1), num_classes=c)
    reg.update_batch([0, 2], 0, np.ones((2, 3), np.float32),
                     np.full((2, c), 0.25, np.float32))
    return reg


def test_snapshot_is_immutable_and_consistent():
    reg = _registry()
    assignment = np.array([1, 0, 2, 0, 0, 1], np.int64)
    snap = capture(1, 3, reg, assignment, 3)
    # registry keeps writing the next version; the snapshot must not move
    reg.update_batch([1], 4, np.zeros((1, 3), np.float32),
                     np.full((1, 4), 0.25, np.float32))
    assignment[0] = 99
    assert snap.assignment[0] == 1
    np.testing.assert_array_equal(
        snap.has_mask, [True, False, True, False, False, False])
    assert snap.registry_version < reg.version
    with pytest.raises(ValueError):
        snap.assignment[0] = 5
    assert snap.age(5) == 2


def test_snapshot_store_publishes_atomically_and_monotonically():
    reg = _registry()
    store = SnapshotStore(capture(0, -1, reg, np.zeros(6, np.int64), 1))
    assert store.latest().version == 0
    store.publish(capture(1, 0, reg, np.zeros(6, np.int64), 1))
    assert store.latest().version == 1 and store.published == 1
    with pytest.raises(ValueError, match="must increase"):
        store.publish(capture(1, 1, reg, np.zeros(6, np.int64), 1))


# ---------------------------------------------------------------------------
# ingest queue


def test_ingest_queue_latency_fifo_and_in_flight():
    q = IngestQueue()
    fresh = np.full((8, 4), 0.25, np.float32)
    assert q.enqueue(0, 1, {}, fresh) is None          # nothing to send
    q.enqueue(0, 2, {1: np.ones(3), 4: np.ones(3)}, fresh)
    q.enqueue(1, 2, {4: np.full(3, 2.0)}, fresh)
    assert q.in_flight() == {1, 4}
    assert q.pop_ready(1) == []                        # latency not elapsed
    ready = q.pop_ready(2)
    assert [b.compute_round for b in ready] == [0]
    assert q.in_flight() == {4}
    ready = q.pop_ready(3)
    assert [b.compute_round for b in ready] == [1]
    # FIFO drain ⇒ the round-1 recompute of client 4 lands last (newest wins)
    assert float(ready[0].summaries[4][0]) == 2.0
    assert q.in_flight() == set() and len(q) == 0


def test_staleness_policy_validates():
    with pytest.raises(ValueError):
        StalenessPolicy(max_snapshot_age=0)
    with pytest.raises(ValueError):
        StalenessPolicy(drift_mass_trigger=0.0)
    assert StalenessPolicy().max_snapshot_age >= 1


# ---------------------------------------------------------------------------
# the differential pin: async (degenerate) ≡ sync, 24 seeds, churn,
# rotating through the scan layout × clustering support matrix


def _trace(h):
    return {k: h[k] for k in ("selected", "completed", "refreshes", "acc",
                              "n_active", "n_joined", "n_departed",
                              "dropped", "sim_time")}


# each seed exercises one cell; 24 seeds cover every combination 3-4x,
# including the multi-chunk and one-row scans and churn scenarios
_PRESETS = ("mobile-churn", "straggler", "diurnal")


@pytest.fixture(scope="module")
def server_data():
    return FederatedDataset(small_spec(num_clients=16, num_classes=5, side=8,
                                       avg_samples=24), seed=13)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SEEDS)
def test_async_degenerate_equals_sync_trace(server_data, seed):
    """Zero ingest latency + the sync refresh cadence ⇒ the event-driven
    server replays the sync trace bitwise (selection, refreshes, clock,
    accuracy), whatever the scan layout/clustering backend."""
    layout, clustering = _MATRIX[seed % len(_MATRIX)]
    preset = _PRESETS[seed % len(_PRESETS)]
    data = server_data
    sc = make_scenario(preset, data.spec.num_clients, seed=seed).to_config()
    base = dict(rounds=4, clients_per_round=4, local_steps=1, summary="py",
                clustering=clustering, num_clusters=3, refresh_max_age=3,
                refresh_kl=0.05, recluster_every=2, hier_local_k=3,
                eval_every=2, seed=seed, **FLCONFIG[layout])
    h_sync = run_federated(data, FLConfig(**base, server="sync"),
                           scenario=Scenario.from_config(sc))
    h_async = run_federated(data, FLConfig(**base, server="async"),
                            scenario=Scenario.from_config(sc))
    assert _trace(h_sync) == _trace(h_async)
    # the degenerate server still went through the full event machinery
    assert h_async["server"]["events"] >= 7 * base["rounds"]
    assert h_async["server"]["snapshots_published"] == base["rounds"]
    # a snapshot republished every round is always fresh
    assert h_async["snapshot_age"] == [0] * base["rounds"]


# ---------------------------------------------------------------------------
# bounded-staleness mode: no bitwise pin (that is the point), but hard
# guarantees — the staleness bound holds, and the pipeline stays sane


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["whole", "padded"])
def test_staleness_mode_bounds_snapshot_age(server_data, layout):
    data = server_data
    sc = make_scenario("mobile-churn", data.spec.num_clients,
                       seed=5).to_config()
    cfg = FLConfig(rounds=8, clients_per_round=4, local_steps=1,
                   summary="py", clustering="kmeans",
                   num_clusters=3, refresh_max_age=3, refresh_kl=0.05,
                   eval_every=4, seed=5, **FLCONFIG[layout],
                   server="async", server_refresh="staleness",
                   ingest_delay_rounds=1, snapshot_max_age=2,
                   drift_mass_trigger=0.2)
    h = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    # the bound: selection never reads a snapshot older than max age
    assert max(h["snapshot_age"]) <= cfg.snapshot_max_age
    assert min(h["snapshot_age"]) >= 0
    srv = h["server"]
    assert srv["refresh"] == "staleness"
    assert srv["snapshots_published"] >= 1
    # background work happened and its cost stayed off the critical path:
    # critical only ever charges blocking rebuilds
    assert srv["background_refreshes"] + srv["blocking_refreshes"] >= 1
    for crit, cl in zip(h["overhead_critical_s"], h["server_cluster_s"]):
        assert crit <= cl + 1e-9
    # versions strictly increase on the selection path
    versions = h["snapshot_version"]
    assert all(b >= a for a, b in zip(versions, versions[1:]))


@pytest.mark.slow
def test_async_delay_defers_refreshes(server_data):
    """With ingest latency, summaries land later: the registry sees the
    same total refresh volume trail the zero-latency run, and in-flight
    dedup keeps the server from re-issuing queued clients."""
    data = server_data
    sc = make_scenario("uniform-iid", data.spec.num_clients,
                       seed=2).to_config()
    base = dict(rounds=6, clients_per_round=4, local_steps=1, summary="py",
                clustering="kmeans", num_clusters=3,
                refresh_max_age=2, refresh_kl=0.05, eval_every=3, seed=2,
                server="async", server_refresh="staleness",
                snapshot_max_age=3, drift_mass_trigger=0.1)
    h0 = run_federated(data, FLConfig(**base, ingest_delay_rounds=0),
                       scenario=Scenario.from_config(sc))
    h2 = run_federated(data, FLConfig(**base, ingest_delay_rounds=2),
                       scenario=Scenario.from_config(sc))
    assert h2["refreshes"][0] == 0          # nothing landed yet in round 0
    assert h0["refreshes"][0] > 0
    # cumulative refresh counts: the delayed run lags, never leads
    assert all(a <= b for a, b in zip(h2["refreshes"], h0["refreshes"]))


# ---------------------------------------------------------------------------
# config validation (satellite: unknown strings must fail loudly)


def test_unknown_server_strings_rejected(server_data):
    data = server_data
    with pytest.raises(ValueError, match="unknown server"):
        run_federated(data, FLConfig(rounds=1, server="threads"))
    with pytest.raises(ValueError, match="unknown server_refresh"):
        run_federated(data, FLConfig(rounds=1, server="async",
                                     server_refresh="eventual"))


# ---------------------------------------------------------------------------
# regression: ingest latency before anything has landed (empty registry)


@pytest.mark.slow
def test_sync_refresh_mode_survives_ingest_latency(server_data):
    """server_refresh="sync" with a nonzero ingest latency: round 0's
    cadence says recluster but nothing has landed yet — must skip the
    empty fit, not crash (regression)."""
    data = server_data
    sc = make_scenario("uniform-iid", data.spec.num_clients,
                       seed=1).to_config()
    for clustering in ("kmeans", "online"):
        h = run_federated(
            data, FLConfig(rounds=4, clients_per_round=4, local_steps=1,
                           summary="py", clustering=clustering, num_clusters=3,
                           eval_every=2, seed=1, server="async",
                           ingest_delay_rounds=1),
            scenario=Scenario.from_config(sc))
        assert h["refreshes"][0] == 0          # nothing landed in round 0
        assert h["refreshes"][-1] > 0          # ...but the pipeline caught up
        assert h["snapshot_age"] == [0] * 4    # sync mode republishes fresh


@pytest.mark.slow
def test_staleness_bound_holds_before_first_batch_lands(server_data):
    """Age-triggered rebuilds on a still-empty registry must reset the
    staleness clock with a fresh (empty-view) snapshot — the bound is a
    guarantee even when ingest latency exceeds it (regression)."""
    data = server_data
    sc = make_scenario("uniform-iid", data.spec.num_clients,
                       seed=3).to_config()
    cfg = FLConfig(rounds=8, clients_per_round=4, local_steps=1,
                   summary="py", clustering="kmeans",
                   num_clusters=3, eval_every=4, seed=3, server="async",
                   server_refresh="staleness", ingest_delay_rounds=4,
                   snapshot_max_age=2, drift_mass_trigger=0.2)
    h = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    assert max(h["snapshot_age"]) <= cfg.snapshot_max_age
