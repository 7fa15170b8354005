"""Telemetry subsystem (DESIGN.md §10): metric algebra, trace validity,
null-object defaults, end-to-end federation observability, and the
refresher staleness-bound edges the new metrics make checkable.
"""
import json
import math
import os

import numpy as np
import pytest

import repro.obs as obs
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.obs import (
    Counter, Gauge, Histogram, MetricRegistry, NULL_REGISTRY, NULL_SPAN,
    StageMeters, Tracer,
)
from repro.obs.export import (
    metrics_records, read_metrics_jsonl, validate_chrome_trace,
    write_metrics_jsonl, write_trace,
)

# the deterministic keys of the 24-seed differential pin — telemetry
# must never move them, enabled or not.  (``sim_time`` is pinned there
# too, but it folds in a *measured* summary wall time, so it is not
# reproducible across two separate runs with or without telemetry.)
TRACE_KEYS = ("selected", "completed", "refreshes", "acc", "n_active",
              "n_joined", "n_departed", "dropped")


def _trace(h):
    return {k: h[k] for k in TRACE_KEYS if k in h}


# ---------------------------------------------------------------------------
# instruments


def test_counter_monotonic():
    c = Counter("x")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_tracks_last_and_max():
    g = Gauge("x")
    assert math.isnan(g.value) and math.isnan(g.max)   # unset is NaN, not 0
    for v in (3.0, 7.0, 2.0):
        g.set(v)
    assert g.value == 2.0 and g.max == 7.0 and g.writes == 3


def test_histogram_exact_percentiles_within_resolution():
    h = Histogram("lat_s")
    samples = [1e-4 * (1 + i / 100.0) for i in range(1000)]   # 100..200us
    for v in samples:
        h.record(v)
    rel = 10 ** (1.0 / h.per_decade) - 1.0      # bucket resolution
    for q in (50.0, 99.0, 99.9):
        exact = float(np.percentile(samples, q, method="higher"))
        got = h.percentile(q)
        assert exact * (1 - 1e-12) <= got <= exact * (1 + rel) * (1 + 1e-12)
    # tails are exact at the extremes: clamped into observed [min, max]
    assert h.min <= h.percentile(0.001) and h.percentile(100.0) == h.max
    assert h.count == 1000 and h.mean == pytest.approx(np.mean(samples))


def test_histogram_single_sample_and_out_of_range():
    h = Histogram("x", lo=1e-3, hi=1.0)
    h.record(0.05)
    assert h.percentiles() == {"p50": 0.05, "p99": 0.05, "p999": 0.05}
    h.record(1e-9)       # underflow bin
    h.record(50.0)       # overflow bin
    assert h.count == 3
    assert h.percentile(1.0) == h.lo          # underflow bin edge
    assert h.percentile(99.9) == 50.0         # overflow clamped to exact max
    empty = Histogram("y")
    assert math.isnan(empty.percentile(50.0))


def test_histogram_merge_is_union_of_streams():
    rs = np.random.RandomState(0)
    a, b, u = (Histogram("s"), Histogram("s"), Histogram("s"))
    sa, sb = rs.gamma(2.0, 1e-3, 500), rs.gamma(2.0, 5e-3, 300)
    for v in sa:
        a.record(v)
        u.record(v)
    for v in sb:
        b.record(v)
        u.record(v)
    a.merge(b)
    # merged histogram == histogram of the concatenated stream, exactly
    assert a.counts == u.counts
    assert a.count == u.count and a.sum == pytest.approx(u.sum)
    assert (a.min, a.max) == (u.min, u.max)
    assert a.percentiles() == u.percentiles()


def test_histogram_merge_rejects_layout_mismatch():
    a = Histogram("s")
    b = Histogram("s", lo=1e-6)
    with pytest.raises(ValueError, match="incompatible layouts"):
        a.merge(b)


# ---------------------------------------------------------------------------
# registry


def test_registry_kind_mismatch_fails_loudly():
    r = MetricRegistry()
    r.counter("x").inc()
    with pytest.raises(TypeError, match="is a counter, not a gauge"):
        r.gauge("x")
    assert r.counter("x").value == 1.0        # get-or-create by name


def test_registry_merge_rolls_up_shards():
    a, b = MetricRegistry(), MetricRegistry()
    a.counter("rows").inc(10)
    b.counter("rows").inc(5)
    b.counter("only_b").inc(2)
    a.gauge("age").set(1.0)
    b.gauge("age").set(4.0)
    b.gauge("age").set(2.0)
    a.histogram("lat_s").record(1e-3)
    b.histogram("lat_s").record(1e-2)
    a.merge(b)
    assert a.counter("rows").value == 15
    assert a.counter("only_b").value == 2
    assert a.gauge("age").value == 2.0 and a.gauge("age").max == 4.0
    assert a.histogram("lat_s").count == 2
    c = MetricRegistry()
    c.gauge("rows").set(1.0)
    with pytest.raises(TypeError, match="cannot merge"):
        c.merge(a)


def test_stage_meters_round_view_and_lifetime_histograms():
    r = MetricRegistry()
    m = StageMeters(r, ("scan", "cluster"))
    m.add("scan", 0.1)
    m.add("scan", 0.2)
    m.add("cluster", 0.5)
    assert m["scan"] == 0.1 + 0.2             # same accumulation order
    assert m.round_total() == (0.1 + 0.2) + 0.5
    m.reset()
    assert m["scan"] == 0.0
    assert r.histogram("server/scan_s").count == 2      # lifetime view
    assert r.histogram("server/cluster_s").count == 1


# ---------------------------------------------------------------------------
# null-object defaults: the disabled path everyone pays


def test_disabled_is_the_default_and_noop():
    assert obs.current() is obs.DISABLED
    assert not obs.enabled()
    assert obs.span("x", round=1) is NULL_SPAN
    assert obs.span("k", cat="kernel", rows=4) is NULL_SPAN
    assert obs.metrics() is NULL_REGISTRY
    with obs.span("x") as sp:
        sp.annotate(n=1)                       # all no-ops, nothing raised
    obs.instant("x", v=2)
    obs.counter_sample("x", 3.0)
    obs.metrics().counter("c").inc()
    obs.metrics().gauge("g").set(1.0)
    obs.metrics().histogram("h").record(1.0)
    assert obs.metrics().snapshot() == {}
    assert obs.current().tracer.events == []


def test_observe_scopes_and_writes_artifacts(tmp_path):
    trace_p = str(tmp_path / "trace.json")
    metrics_p = str(tmp_path / "metrics.jsonl")
    with obs.observe(trace_path=trace_p, metrics_path=metrics_p) as ob:
        assert obs.current() is ob and obs.enabled()
        with obs.span("work", cat="test", round=3) as sp:
            sp.annotate(n=7)
        obs.instant("mark", v=1)
        obs.counter_sample("depth", 4.0)
        obs.metrics().counter("c").inc(2)
        obs.metrics().histogram("h_s").record(1e-3)
        ks = obs.span("k", cat="kernel", rows=8)
        assert ks is not NULL_SPAN
        with ks:
            pass
    assert obs.current() is obs.DISABLED       # restored on exit
    trace = json.load(open(trace_p))
    assert validate_chrome_trace(trace) == []
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert {"work", "mark", "depth", "k"} <= names
    span = next(ev for ev in trace["traceEvents"] if ev["name"] == "work")
    assert span["ph"] == "X" and span["args"] == {"round": 3, "n": 7}
    recs = {r["name"]: r for r in read_metrics_jsonl(metrics_p)}
    assert recs["c"]["value"] == 2
    assert recs["h_s"]["count"] == 1


def test_metrics_jsonl_is_strict_json(tmp_path):
    r = MetricRegistry()
    r.gauge("unset_then_set").set(float("nan"))   # NaN must not leak
    r.histogram("empty_s")
    path = str(tmp_path / "m.jsonl")
    n = write_metrics_jsonl(r, path)
    assert n == len(metrics_records(r)) == 2
    for line in open(path):
        rec = json.loads(line)                    # strict JSON parses
        assert "NaN" not in line
        assert rec["name"]


# ---------------------------------------------------------------------------
# trace validation


def _spans(tracer):
    return [e for e in tracer.events if e["ph"] == "X"]


def test_validate_accepts_real_tracer_output():
    tr = Tracer()
    with tr.span("outer", round=1):
        with tr.span("inner"):
            pass
        tr.instant("tick")
    with tr.span("bg", lane=obs.LANE_BACKGROUND):
        pass
    tr.counter("depth", 3)
    assert validate_chrome_trace(tr.chrome_trace()) == []
    assert tr.span_names() == {"outer", "inner", "bg"}


def test_validate_rejects_malformed_traces():
    assert validate_chrome_trace({}) == ["traceEvents is not a list"]
    missing = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}]}
    assert any("missing" in e for e in validate_chrome_trace(missing))
    bad_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0.0, "dur": -1.0, "pid": 1, "tid": 1}]}
    assert any("bad dur" in e for e in validate_chrome_trace(bad_dur))
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 1},
    ]}
    assert any("overlaps" in e for e in validate_chrome_trace(overlap))
    # the same two spans on different lanes are fine
    two_lanes = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 2},
    ]}
    assert validate_chrome_trace(two_lanes) == []


def test_tracer_absorb_merges_timelines():
    a, b = Tracer(pid=1), Tracer(pid=2)
    with a.span("x"):
        pass
    with b.span("y"):
        pass
    a.absorb(b)
    assert {e["pid"] for e in _spans(a)} == {1, 2}
    assert validate_chrome_trace(a.chrome_trace()) == []


# ---------------------------------------------------------------------------
# spans on the profiler's clock: annotations + the profiled record


def _xplane_host_events(trace_dir) -> dict:
    """{name: [(start_ns, dur_ns), ...]} of the host-plane events in the
    ``.xplane.pb`` a profiler session wrote under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (float(e.start_ns), float(e.duration_ns)))
    return out


def test_no_observer_no_profiler_records_nothing():
    import jax
    n0 = len(obs.profiled().events)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("summary/put", bytes=8) is NULL_SPAN
    with obs.span("x", round=1) as sp:
        sp.annotate(n=2)
    host = np.arange(6, dtype=np.float32)
    (dev,) = obs.device_put("x/put", (host,))
    assert isinstance(dev, jax.Array)
    np.testing.assert_array_equal(np.asarray(dev), host)
    assert len(obs.profiled().events) == n0


def test_profiled_span_in_device_trace_and_record(tmp_path):
    """Under a profiler session with no observer, a span is both an
    annotation on the trace's host plane and an event of ``profiled()``,
    and the two durations agree."""
    import time

    import jax
    n0 = len(obs.profiled().events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("t_outer", cat="test", round=4) as sp:
            with obs.span("t_inner"):
                time.sleep(0.03)
            time.sleep(0.01)
            sp.annotate(n=3)
    finally:
        jax.profiler.stop_trace()
    with obs.span("t_after"):
        pass
    recorded = {e["name"]: e for e in obs.profiled().events[n0:]}
    assert set(recorded) == {"t_outer", "t_inner"}
    assert recorded["t_outer"]["args"] == {"round": 4, "n": 3}
    host = _xplane_host_events(tmp_path)
    for name in ("t_outer", "t_inner"):
        ((_start, dur_ns),) = host[name]
        assert dur_ns / 1e3 == pytest.approx(recorded[name]["dur"], rel=0.1)
    (o_start, o_dur), = host["t_outer"]
    (i_start, i_dur), = host["t_inner"]
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    ev_o, ev_i = recorded["t_outer"], recorded["t_inner"]
    assert ev_o["ts"] <= ev_i["ts"]
    assert ev_i["ts"] + ev_i["dur"] <= ev_o["ts"] + ev_o["dur"]


def test_profiled_span_goes_to_the_enabled_observer(tmp_path):
    import jax
    n0 = len(obs.profiled().events)
    with obs.observe() as ob:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("t_observed"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert "t_observed" in ob.tracer.span_names()
    assert len(obs.profiled().events) == n0
    assert "t_observed" in _xplane_host_events(tmp_path)


@pytest.mark.parametrize("arrays", [
    ((3, 5, np.float32), (3, np.int32)),
    ((4, 2, 2, np.float32), (4, 2, bool), (4, 2, np.uint32)),
    ((0, 7, np.float32),),
], ids=["two", "three", "empty"])
def test_device_put_counts_the_bytes_it_copies(arrays):
    """A recording ``device_put`` span carries the copied arrays' total
    bytes, reckoned here from their shapes and item sizes."""
    import jax
    host = [np.ones(a[:-1], a[-1]) for a in arrays]
    want = sum(int(np.prod(a[:-1])) * np.dtype(a[-1]).itemsize
               for a in arrays)
    with obs.observe() as ob:
        dev = obs.device_put("t/put", tuple(host))
    (ev,) = [e for e in ob.tracer.events if e["name"] == "t/put"]
    assert ev["args"] == {"bytes": want}
    assert len(dev) == len(host)
    for d, h in zip(dev, host):
        assert isinstance(d, jax.Array) and d.dtype == h.dtype
        np.testing.assert_array_equal(np.asarray(d), h)


def test_device_put_keeps_the_sharding():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
    layout = NamedSharding(mesh, P("fleet", None))
    (dev,) = obs.device_put("t/put", (np.zeros((4, 3), np.float32),), layout)
    assert dev.sharding == layout

# ---------------------------------------------------------------------------
# end-to-end federation observability


def _data(seed=13):
    return FederatedDataset(small_spec(num_clients=16, num_classes=5,
                                       side=8, avg_samples=24), seed=seed)


def _cfg(**kw):
    base = dict(rounds=6, clients_per_round=4, local_steps=1, summary="py",
                clustering="online", num_clusters=3,
                refresh_max_age=3, refresh_kl=0.05, eval_every=3, seed=5)
    base.update(kw)
    return FLConfig(**base)


def test_sync_federation_under_observe(tmp_path):
    data = _data()
    h_plain = run_federated(data, _cfg(server="sync"))
    trace_p = str(tmp_path / "trace.json")
    metrics_p = str(tmp_path / "m.jsonl")
    with obs.observe(trace_path=trace_p, metrics_path=metrics_p) as ob:
        h_obs = run_federated(data, _cfg(server="sync"))
    # observability must not move the run: differential keys identical
    assert _trace(h_plain) == _trace(h_obs)
    names = ob.tracer.span_names()
    assert {"drift_scan", "client_summaries", "registry_scatter",
            "recluster", "select_devices", "local_train",
            "evaluate"} <= names
    trace = json.load(open(trace_p))
    assert validate_chrome_trace(trace) == []
    recs = {r["name"] for r in read_metrics_jsonl(metrics_p)}
    assert "registry/scatter_rows" in recs
    # history carries the metric snapshot either way (ctx-owned registry)
    for h in (h_plain, h_obs):
        m = h["metrics"]
        assert m["server/scan_s"]["count"] == 6
        assert {"p50", "p99", "p999"} <= set(m["server/critical_s"])


def test_async_federation_under_observe(tmp_path):
    data = _data()
    cfg = _cfg(rounds=8, server="async", server_refresh="staleness",
               ingest_delay_rounds=1, snapshot_max_age=2,
               drift_mass_trigger=0.2)
    h_plain = run_federated(data, cfg)
    trace_p = str(tmp_path / "trace.json")
    with obs.observe(trace_path=trace_p) as ob:
        h_obs = run_federated(data, cfg)
    assert obs.current() is obs.DISABLED
    assert _trace(h_plain) == _trace(h_obs)
    names = ob.tracer.span_names()
    assert {"drift_scan", "client_summaries", "local_train",
            "select_devices"} <= names
    # every event-engine dispatch got its own span
    dispatches = [n for n in names if n.startswith("event/")]
    assert {"event/scan", "event/select", "event/train"} <= set(dispatches)
    trace = json.load(open(trace_p))
    assert validate_chrome_trace(trace) == []
    # ingest enqueue/drain instants + snapshot publish landed in the trace
    inames = {e["name"] for e in trace["traceEvents"] if e["ph"] == "i"}
    assert {"ingest/enqueue", "ingest/drain", "snapshot/publish"} <= inames
    # queue counters live on the observer registry (merged with the
    # ctx-owned one at finish(), so the JSONL export holds both)
    m = ob.metrics.snapshot()
    assert m["server/ingest/enqueued_batches"]["value"] > 0
    assert m["server/ingest/drained_batches"]["value"] > 0
    assert m["server/snapshots_published"]["value"] > 0
    assert m["server/scan_s"]["count"] == cfg.rounds   # ctx merged in


# ---------------------------------------------------------------------------
# refresher staleness-bound edges via the new metrics (satellite)


def test_staleness_bound_holds_in_metrics():
    h = run_federated(_data(), _cfg(
        rounds=10, server="async", server_refresh="staleness",
        ingest_delay_rounds=1, snapshot_max_age=2, drift_mass_trigger=0.2))
    m = h["metrics"]
    # the gauge's running max is the bound check — no series needed
    assert m["server/snapshot_age"]["max"] <= 2
    assert m["server/snapshot_age"]["writes"] == 10
    assert max(h["snapshot_age"]) == m["server/snapshot_age"]["max"]


def test_blocking_counter_matches_server_accounting():
    # mass trigger unreachable (1.0): every rebuild is an age-bound
    # blocking one, so the counter must match the server's own count
    # and be nonzero
    h = run_federated(_data(), _cfg(
        rounds=10, server="async", server_refresh="staleness",
        ingest_delay_rounds=1, snapshot_max_age=1, drift_mass_trigger=1.0))
    m = h["metrics"]
    blocking = m["server/refresh/blocking"]["value"]
    assert blocking == h["server"]["blocking_refreshes"] > 0
    assert m["server/refresh/blocking_build_s"]["count"] == blocking
    assert m["server/snapshot_age"]["max"] <= 1
    # the counter fired because the age bound was actually reached
    assert m["server/refresh/age_at_decision"]["max"] >= 1


class _RefresherCtx:
    """Minimal RoundContext slice the refresher consumes."""

    uses_summaries = True

    def __init__(self, registry):
        self.registry = registry
        self.metrics = MetricRegistry()
        self.assignment = np.zeros(registry.num_clients, np.int64)
        self.num_clusters = 1
        self.reclusters = 0

    def recluster_now(self, rnd, active, drifted):
        self.reclusters += 1
        return 0.0


def test_blocking_counter_increments_exactly_at_the_bound():
    import types

    from repro.core import RefreshPolicy
    from repro.server import (
        ClusterRefresher, SnapshotStore, StalenessPolicy, capture,
    )
    from repro.stream import SummaryRegistry

    n = 8
    reg = SummaryRegistry(n, RefreshPolicy(4, 0.1))
    reg.update_batch(np.arange(n), 0, np.ones((n, 3), np.float32),
                     np.full((n, 4), 0.25, np.float32))
    ctx = _RefresherCtx(reg)
    store = SnapshotStore(capture(0, 0, reg, ctx.assignment, 1))
    refresher = ClusterRefresher(
        ctx, store, mode="staleness",
        policy=StalenessPolicy(max_snapshot_age=2, drift_mass_trigger=0.5))
    plan = types.SimpleNamespace(active=np.ones(n, bool),
                                 joined=np.zeros(0, np.int64),
                                 departed=np.zeros(0, np.int64))
    blocking_c = ctx.metrics.counter("server/refresh/blocking")
    background_c = ctx.metrics.counter("server/refresh/background")

    # round 1: age 1 < bound, no drift mass -> no build, no counters
    assert refresher.step(1, plan, []) == (0.0, None)
    assert blocking_c.value == 0 and background_c.value == 0

    # round 2: age hits the bound -> exactly one blocking build, counted
    dt, snap = refresher.step(2, plan, [])
    assert snap is None and refresher.blocking_builds == 1
    assert blocking_c.value == 1 and background_c.value == 0
    assert ctx.metrics.gauge("server/refresh/age_at_decision").max == 2
    assert store.latest().round_idx == 2       # published: clock reset

    # round 3: age back under the bound, drift mass >= trigger -> one
    # background build (returned for next-round publish), blocking stays
    refresher.note_ingested(range(4))          # 4/8 = the 0.5 trigger
    dt, snap = refresher.step(3, plan, list(range(4)))
    assert dt == 0.0 and snap is not None
    assert blocking_c.value == 1 and background_c.value == 1
    assert refresher.background_builds == 1
    assert ctx.metrics.histogram(
        "server/refresh/background_build_s").count == 1


# ---------------------------------------------------------------------------
# dimensional metrics: labeled instrument families (DESIGN.md §13)


def test_empty_histogram_percentiles_are_nan():
    h = Histogram("empty_s")
    assert h.count == 0
    for q in (0.0, 50.0, 99.0, 99.9, 100.0):
        assert math.isnan(h.percentile(q))
    assert all(math.isnan(v) for v in h.percentiles().values())
    snap = h.snapshot()
    assert snap["count"] == 0


def test_family_children_land_in_the_registry():
    from repro.obs.metrics import labeled_name, split_labeled
    r = MetricRegistry()
    fam = r.family("select/fill", labels=("cluster",))
    fam.labeled(0).inc(3)
    fam.labeled(2).inc(1)
    # children are plain registry instruments under canonical names
    name = labeled_name("select/fill", ("cluster",), (0,))
    assert name == "select/fill{cluster=0}"
    assert r.counter(name).value == 3
    assert split_labeled(name) == ("select/fill", {"cluster": "0"})
    assert split_labeled("plain") == ("plain", None)
    # same child object back on every call (cache hit is the hot path)
    assert fam.labeled(0) is fam.labeled(0)
    assert set(fam.children()) == {(0,), (2,)}


def test_family_validates_label_arity_and_reserved_chars():
    r = MetricRegistry()
    fam = r.family("f", labels=("a", "b"))
    with pytest.raises(ValueError, match="got 1 value"):
        fam.labeled("x")
    with pytest.raises(ValueError, match="reserved"):
        fam.labeled("x", "y=z")
    with pytest.raises(ValueError):
        r.family("bad{name", labels=("a",))
    # re-declaring with different labels or kind fails loudly
    with pytest.raises(ValueError, match="has labels"):
        r.family("f", labels=("a",))
    with pytest.raises(TypeError, match="family"):
        r.family("f", labels=("a", "b"), kind="histogram")


def test_family_and_plain_name_collision_raises():
    r = MetricRegistry()
    r.family("x", labels=("k",))
    with pytest.raises(TypeError, match="family"):
        r.counter("x")
    r2 = MetricRegistry()
    r2.counter("y")
    with pytest.raises(TypeError, match="plain"):
        r2.family("y", labels=("k",))


def test_labeled_family_merge_is_union_of_streams():
    rs = np.random.RandomState(7)
    a, b, u = MetricRegistry(), MetricRegistry(), MetricRegistry()
    fa = a.family("lat_s", labels=("tier",), kind="histogram")
    fb = b.family("lat_s", labels=("tier",), kind="histogram")
    fu = u.family("lat_s", labels=("tier",), kind="histogram")
    for tier, n, reg_fam in (("phone", 200, fa), ("tablet", 150, fa),
                             ("phone", 100, fb), ("edge", 50, fb)):
        for v in rs.gamma(2.0, 1e-3, n):
            reg_fam.labeled(tier).record(v)
            fu.labeled(tier).record(v)
    a.merge(b)
    # merged children == histograms of the concatenated per-tier streams
    for tier in ("phone", "tablet", "edge"):
        got = a.histogram(f"lat_s{{tier={tier}}}")
        want = u.histogram(f"lat_s{{tier={tier}}}")
        assert got.counts == want.counts and got.count == want.count
        assert got.percentiles() == want.percentiles()
    # family metadata adopted on merge into a fresh registry
    c = MetricRegistry()
    c.merge(a)
    assert c.histogram("lat_s{tier=edge}").count == 50
    assert "lat_s" in c.families()


def test_family_merge_mismatched_labels_or_kind_raises():
    a, b = MetricRegistry(), MetricRegistry()
    a.family("f", labels=("x",)).labeled(1).inc()
    b.family("f", labels=("y",)).labeled(1).inc()
    with pytest.raises(ValueError, match="label"):
        a.merge(b)
    c, d = MetricRegistry(), MetricRegistry()
    c.family("g", labels=("x",)).labeled(1).inc()
    d.family("g", labels=("x",), kind="histogram").labeled(1).record(1.0)
    with pytest.raises(TypeError):
        c.merge(d)


def test_null_registry_family_noops():
    fam = NULL_REGISTRY.family("x", labels=("k",))
    fam.labeled("a").inc()
    fam.labeled("a").record(1.0)
    fam.labeled("a").set(2.0)
    assert NULL_REGISTRY.snapshot() == {}


# ---------------------------------------------------------------------------
# atomic artifact writes + torn-tail tolerance (satellite)


def test_metrics_export_is_atomic(tmp_path, monkeypatch):
    import repro.obs.export as export
    r = MetricRegistry()
    r.counter("c").inc(5)
    path = str(tmp_path / "m.jsonl")
    write_metrics_jsonl(r, path)
    assert not os.path.exists(path + ".tmp")   # replaced, not left behind
    first = open(path).read()

    # a crash mid-write must not clobber the previous artifact
    real_replace = os.replace

    def boom(src, dst):
        raise RuntimeError("killed mid-write")

    monkeypatch.setattr(export.os, "replace", boom)
    r.counter("c").inc(1)
    with pytest.raises(RuntimeError):
        write_metrics_jsonl(r, path)
    monkeypatch.setattr(export.os, "replace", real_replace)
    assert open(path).read() == first          # old artifact intact


def test_read_metrics_jsonl_tolerates_torn_tail(tmp_path):
    r = MetricRegistry()
    r.counter("a").inc(1)
    r.counter("b").inc(2)
    path = str(tmp_path / "m.jsonl")
    write_metrics_jsonl(r, path)
    body = open(path).read()
    # torn last line (crash mid-append): dropped, rest parses
    open(path, "w").write(body + '{"name": "c", "val')
    recs = {rec["name"] for rec in read_metrics_jsonl(path)}
    assert recs == {"a", "b"}
    # torn line in the middle: corruption, raises
    lines = body.splitlines()
    open(path, "w").write(lines[0][: len(lines[0]) // 2] + "\n"
                          + "\n".join(lines[1:]) + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        read_metrics_jsonl(path)


def test_metrics_records_annotate_labeled_children():
    r = MetricRegistry()
    r.family("fill", labels=("cluster",)).labeled(3).inc(2)
    r.counter("plain").inc()
    recs = {rec["name"]: rec for rec in metrics_records(r)}
    assert recs["fill{cluster=3}"]["family"] == "fill"
    assert recs["fill{cluster=3}"]["labels"] == {"cluster": "3"}
    assert "family" not in recs["plain"]
