"""Check-in front end (DESIGN.md §12): arrival determinism, the k-server
latency model, admission control/backpressure on the bounded ingest
queue, the SLO feedback loop into the refresher, and the two load-bearing
equivalences:

  * a zero-shed front end (unbounded queue) is a pure *observer* — the
    front-ended async run replays the plain async trace bitwise across
    the 24-seed matrix;
  * kill-and-resume through every stage boundary (including the new
    CHECKIN stage) reproduces the uninterrupted front-ended run bitwise,
    with no checkpointed arrival state (schedules are pure functions of
    (seed, round)).
"""
import numpy as np
import pytest

from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.obs.metrics import MetricRegistry
from repro.server.admission import AdmissionController
from repro.server.arrivals import ArrivalConfig, ArrivalProcess
from repro.server.events import Stage
from repro.server.frontend import CheckinFrontend
from repro.server.ingest import IngestOverflow, IngestQueue
from repro.server.snapshot import RegistrySnapshot
from repro.sim import (
    FaultPlan, Scenario, ServerKilled, make_scenario, resume_trace,
)
from scan_layouts import FLCONFIG, MATRIX as _MATRIX

SEEDS = range(24)          # >= 20 random seeds (acceptance floor)
_PRESETS = ("mobile-churn", "straggler", "diurnal")


# ---------------------------------------------------------------------------
# arrival process: pure function of (seed, round, availability)


def test_arrival_schedule_deterministic_and_sorted():
    proc = ArrivalProcess(ArrivalConfig(rate=2.0, window_s=60.0, seed=7))
    avail = np.zeros(50, bool)
    avail[::3] = True
    a = proc.schedule(4, avail)
    b = proc.schedule(4, avail.copy())
    np.testing.assert_array_equal(a.clients, b.clients)
    np.testing.assert_array_equal(a.times, b.times)
    assert np.all(np.diff(a.times) >= 0)            # time-sorted
    assert set(np.unique(a.clients)) <= set(np.flatnonzero(avail).tolist())
    assert np.all((a.times >= 0) & (a.times < 60.0))


def test_arrival_rounds_are_independent_streams():
    proc = ArrivalProcess(ArrivalConfig(rate=2.0, seed=7))
    avail = np.ones(40, bool)
    r3, r4 = proc.schedule(3, avail), proc.schedule(4, avail)
    assert (len(r3) != len(r4)
            or not np.array_equal(r3.times, r4.times))
    # regenerating a *later* round never needs the earlier ones: a fresh
    # process gives the same round-4 schedule without touching round 3
    again = ArrivalProcess(ArrivalConfig(rate=2.0, seed=7)).schedule(4, avail)
    np.testing.assert_array_equal(r4.clients, again.clients)
    np.testing.assert_array_equal(r4.times, again.times)


def test_arrival_empty_fleet():
    proc = ArrivalProcess(ArrivalConfig(rate=2.0, seed=1))
    sched = proc.schedule(0, np.zeros(10, bool))
    assert len(sched) == 0


# ---------------------------------------------------------------------------
# the k-server FIFO latency model


def _snap(n, has=None):
    has_mask = np.ones(n, bool) if has is None else np.asarray(has, bool)
    asg = np.zeros(n, np.int64)
    has_mask.setflags(write=False)
    asg.setflags(write=False)
    return RegistrySnapshot(version=1, round_idx=0, registry_version=1,
                            assignment=asg, num_clusters=1,
                            has_mask=has_mask)


def _sched(times, clients=None):
    times = np.asarray(times, np.float64)
    clients = (np.zeros(times.size, np.int64) if clients is None
               else np.asarray(clients, np.int64))
    from repro.server.arrivals import ArrivalSchedule
    return ArrivalSchedule(0, clients, times)


def test_latency_model_matches_scalar_fifo_recurrence():
    rs = np.random.RandomState(3)
    times = np.sort(rs.rand(200) * 10.0)
    k, s = 3, 0.05
    fe = CheckinFrontend(workers=k, service_s=s)
    dep = fe._departures(times, stall_s=0.4)
    # scalar reference: dep[i] = max(arr[i], dep[i-k]) + s
    a = np.maximum(times, 0.4)
    want = np.empty_like(a)
    for i in range(a.size):
        start = a[i] if i < k else max(a[i], want[i - k])
        want[i] = start + s
    # the vectorized chain re-associates the additions, so equality is
    # up to FP rounding; determinism pins only need the vectorized form
    # to equal itself run-to-run (covered by the e2e tests)
    np.testing.assert_allclose(dep, want, rtol=1e-12, atol=1e-12)


def test_idle_system_latency_is_service_time():
    fe = CheckinFrontend(workers=2, service_s=0.01)
    rep = fe.serve(_sched([0.0, 5.0, 9.0]), _snap(4), np.ones(4, bool))
    assert rep.checkins == 3
    assert rep.p50_s == pytest.approx(0.01)
    assert rep.p99_s == pytest.approx(0.01)


def test_stall_hits_tail_not_median():
    rs = np.random.RandomState(5)
    times = np.sort(rs.rand(5000) * 60.0)
    fe = CheckinFrontend(workers=4, service_s=1e-4)
    clean = fe.serve(_sched(times), _snap(2), np.ones(2, bool))
    stalled = fe.serve(_sched(times), _snap(2), np.ones(2, bool),
                       stall_s=2.0)
    assert stalled.p999_s > clean.p999_s     # blocking rebuild in the tail
    assert stalled.p50_s == pytest.approx(clean.p50_s)   # median untouched


def test_eligibility_is_snapshot_and_liveness_gather():
    has = np.array([True, False, True, True])
    active = np.array([True, True, False, True])
    fe = CheckinFrontend(workers=1, service_s=0.0)
    rep = fe.serve(_sched([0.0, 1.0, 2.0, 3.0], clients=[0, 1, 2, 3]),
                   _snap(4, has), active)
    assert rep.checkins == 4
    assert rep.eligible == 2                  # clients 0 and 3


def test_record_many_bitwise_matches_looped_record():
    a = MetricRegistry()
    b = MetricRegistry()
    rs = np.random.RandomState(11)
    vals = np.concatenate([rs.rand(500) * 1e-2, [0.0, 1e-12, 5.0, 1e4]])
    a.histogram("h").record_many(vals)
    hb = b.histogram("h")
    for v in vals:
        hb.record(float(v))
    ha = a.histogram("h")
    np.testing.assert_array_equal(ha.counts, hb.counts)
    assert ha.count == hb.count
    assert (ha.min, ha.max) == (hb.min, hb.max)
    # pairwise vs sequential accumulation: sum agrees to FP rounding
    assert ha.sum == pytest.approx(hb.sum, rel=1e-12)
    assert ha.percentiles() == hb.percentiles()


# ---------------------------------------------------------------------------
# bounded ingest queue + admission control


def test_ingest_queue_overflow_is_loud():
    q = IngestQueue(max_depth=3)
    fresh = {c: np.zeros(2, np.float32) for c in range(10)}
    q.enqueue(0, 0, {0: "s0", 1: "s1"}, fresh)
    assert q.depth() == 2 and q.capacity() == 1
    with pytest.raises(IngestOverflow, match="admission control"):
        q.enqueue(0, 0, {2: "s2", 3: "s3"}, fresh)
    q.enqueue(0, 0, {2: "s2"}, fresh)
    assert q.capacity() == 0
    got = q.pop_ready(0)
    assert sum(len(b) for b in got) == 3
    assert q.depth() == 0 and q.capacity() == 3


def test_unbounded_admission_is_strict_passthrough():
    adm = AdmissionController(max_depth=0)
    q = IngestQueue()
    summaries = {5: "s5", 2: "s2", 9: "s9"}     # insertion order preserved
    fresh = {c: np.full(2, c, np.float32) for c in summaries}
    d = adm.plan(0, q, summaries, fresh, {2})
    assert d.shed == [] and d.deferred_served == 0
    assert len(d.batches) == 1
    cr, summ, rows = d.batches[0]
    assert cr == 0 and list(summ) == [5, 2, 9]   # original order, bitwise


def test_admission_sheds_and_retries_with_priority_lane():
    adm = AdmissionController(max_depth=2, retry_after=1)
    q = IngestQueue(max_depth=2)
    fresh = {c: np.zeros(1, np.float32) for c in range(10)}
    # round 0: three offers into capacity 2; client 7 is the drifted one
    d0 = adm.plan(0, q, {3: "a", 7: "b", 4: "c"}, fresh, priority_ids={7})
    admitted0 = [c for _, summ, _ in d0.batches for c in summ]
    assert admitted0 == [7, 3]                 # priority lane first
    assert d0.shed == [4]
    assert adm.in_flight() == {4}
    for cr, summ, rows in d0.batches:
        q.enqueue(cr, 0, summ, rows, ready_round=0)
    q.pop_ready(0)
    # round 1: the deferred client is served before fresh offers
    d1 = adm.plan(1, q, {8: "d"}, fresh, priority_ids=set())
    admitted1 = [c for _, summ, _ in d1.batches for c in summ]
    assert admitted1 == [4, 8]
    assert d1.deferred_served == 1 and d1.shed == []
    assert adm.in_flight() == set()
    # deferred batch kept its original compute round
    assert sorted(cr for cr, _, _ in d1.batches) == [0, 1]


def test_admission_evicts_departed_clients():
    adm = AdmissionController(max_depth=1, retry_after=1)
    q = IngestQueue(max_depth=1)
    fresh = {c: np.zeros(1, np.float32) for c in range(4)}
    d = adm.plan(0, q, {1: "a", 2: "b"}, fresh)
    assert d.shed == [2]
    adm.evict([2])
    assert adm.in_flight() == set()


# ---------------------------------------------------------------------------
# end-to-end: the front end rides the event engine


def _trace(h):
    return {k: h[k] for k in ("selected", "completed", "refreshes", "acc",
                              "n_active", "n_joined", "n_departed",
                              "dropped", "sim_time")}


@pytest.fixture(scope="module")
def fleet_data():
    return FederatedDataset(small_spec(num_clients=16, num_classes=5, side=8,
                                       avg_samples=24), seed=13)


def _cfg(seed, layout="padded", clustering="kmeans", rounds=4, **kw):
    base = dict(rounds=rounds, clients_per_round=4, local_steps=1,
                summary="py", clustering=clustering, num_clusters=3,
                refresh_max_age=3, refresh_kl=0.05, recluster_every=2,
                hier_local_k=3, eval_every=2, seed=seed, server="async",
                **FLCONFIG[layout])
    base.update(kw)
    return FLConfig(**base)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SEEDS)
def test_noshed_frontend_pinned_to_plain_async(fleet_data, seed):
    """Unbounded queue + front end enabled ⇒ the front end is a pure
    observer: selection, refreshes, clock and accuracy replay the plain
    async run bitwise, whatever the backend."""
    layout, clustering = _MATRIX[seed % len(_MATRIX)]
    preset = _PRESETS[seed % len(_PRESETS)]
    data = fleet_data
    sc = make_scenario(preset, data.spec.num_clients, seed=seed).to_config()
    h_plain = run_federated(data, _cfg(seed, layout, clustering),
                            scenario=Scenario.from_config(sc))
    h_front = run_federated(data, _cfg(seed, layout, clustering,
                                       frontend="poisson"),
                            scenario=Scenario.from_config(sc))
    assert _trace(h_plain) == _trace(h_front)
    # and the front end actually did something this run
    assert sum(h_front["checkins"]) > 0
    assert h_front["server"]["frontend"]["shed"] == 0


def test_frontend_history_deterministic(fleet_data):
    data = fleet_data
    sc = make_scenario("diurnal", data.spec.num_clients, seed=9).to_config()
    cfg = _cfg(9, frontend="poisson", server_refresh="staleness",
               ingest_delay_rounds=1, snapshot_max_age=2,
               drift_mass_trigger=0.2, ingest_max_depth=6,
               frontend_slo_p99_s=1e-9, checkin_stall_model_s=0.25)
    h1 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    h2 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    for k in ("checkins", "checkins_shed", "checkin_p99_s"):
        assert h1[k] == h2[k]
    # the modeled stall fired on the blocking-rebuild round (arrivals
    # inside the stall window wait for service start), bitwise-identical
    # across runs; an idle round's p99 is just the 50us service time
    assert max(h1["checkin_p99_s"]) > 100 * 50e-6
    assert h1["server"]["blocking_refreshes"] > 0
    assert _trace(h1) == _trace(h2)
    fe = h1["server"]["frontend"]
    assert fe["checkins"] == sum(h1["checkins"]) > 0
    # the 1ns SLO is unmeetable: every served round breached, and the
    # refresher answered with early background builds
    assert fe["slo_breaches"] == sum(1 for c in h1["checkins"] if c)
    assert fe["slo_breaches"] > 0


def test_bounded_queue_sheds_and_still_completes(fleet_data):
    data = fleet_data
    sc = make_scenario("mobile-churn", data.spec.num_clients,
                       seed=5).to_config()
    cfg = _cfg(5, server_refresh="staleness", ingest_delay_rounds=1,
               snapshot_max_age=2, drift_mass_trigger=0.2,
               frontend="poisson", ingest_max_depth=2,
               admission_retry_after=1, rounds=6)
    h = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    fe = h["server"]["frontend"]
    assert sum(h["checkins_shed"]) == fe["shed"] > 0
    # conservation: everything offered was admitted or is still waiting
    assert fe["admitted"] + fe["still_deferred"] >= fe["deferred_served"]
    assert len(h["round"]) == cfg.rounds


def test_history_keys_exist_in_sync_mode(fleet_data):
    """The trace key set is mode-invariant (restore_context asserts the
    full set): sync runs carry empty front-end columns."""
    data = fleet_data
    h = run_federated(data, FLConfig(rounds=2, clients_per_round=4,
                                     local_steps=1, summary="py",
                                     num_clusters=3, eval_every=2, seed=0))
    assert h["checkins"] == [] and h["checkin_p99_s"] == []


# ---------------------------------------------------------------------------
# kill-and-resume through every boundary, CHECKIN included


_FRONT_STAGES = (Stage.MEMBERSHIP, Stage.DRAIN, Stage.SCAN, Stage.COMPUTE,
                 Stage.REFRESH, Stage.CHECKIN, Stage.SELECT, Stage.TRAIN)


def _kill_chain(data, cfg, sc_config, boundaries, tmpdir):
    resume, killed = False, 0
    for point in boundaries:
        try:
            h = run_federated(data, cfg,
                              scenario=Scenario.from_config(sc_config),
                              durable=None if resume else tmpdir,
                              resume_from=tmpdir if resume else None,
                              faults=FaultPlan(crash_points=(point,)))
        except ServerKilled:
            resume, killed = True, killed + 1
            continue
        return h, killed
    h = run_federated(data, cfg, scenario=Scenario.from_config(sc_config),
                      resume_from=tmpdir)
    return h, killed


@pytest.mark.parametrize("bounded", [False, True])
def test_frontend_kill_chain_every_boundary(fleet_data, tmp_path, bounded):
    """Kill at every stage boundary of every round in turn (the CHECKIN
    boundary included), resuming between kills through the mid-round
    checkpoints: the final trace AND the front-end history replay the
    uninterrupted run bitwise — arrival schedules regenerate from
    (seed, round), admission's deferred set rides the checkpoint."""
    data = fleet_data
    rounds = 3
    extra = (dict(ingest_max_depth=3, admission_retry_after=1,
                  server_refresh="staleness", ingest_delay_rounds=1,
                  snapshot_max_age=2, drift_mass_trigger=0.2)
             if bounded else {})
    cfg = _cfg(7, rounds=rounds, frontend="poisson", **extra)
    sc = make_scenario("mobile-churn", data.spec.num_clients,
                       seed=7).to_config()
    h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    boundaries = [(r, s) for r in range(rounds) for s in _FRONT_STAGES]
    h1, killed = _kill_chain(data, cfg, sc, boundaries,
                             str(tmp_path / f"b{int(bounded)}"))
    assert killed == len(boundaries), \
        f"only {killed}/{len(boundaries)} crash points fired"
    assert resume_trace(h0) == resume_trace(h1)
    for k in ("checkins", "checkins_shed", "checkin_p99_s"):
        assert h0[k] == h1[k]
    assert h0["server"]["frontend"] == h1["server"]["frontend"]
