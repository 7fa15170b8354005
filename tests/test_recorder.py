"""Flight recorder + selection-provenance explain (DESIGN.md §13).

The load-bearing claim is **pinned reconstruction**: ``explain`` answers
"why was this client (not) selected" by replaying the recorded policy
inputs, and the replay must reproduce the recorded ``selected`` list
byte for byte — checked here live across the 24-seed differential
matrix (scan layout × clustering × churn preset, sync / async / async+
front-end servers).  A reconstruction that merely *resembles* the
decision would make ``why``'s attributions plausible-but-wrong; exact
equality is what makes them trustworthy.

Also pinned: recording never moves the run (history trace identical
with the recorder on vs off), and the record stream is replay-
deterministic under kill-and-resume (the resumed run's deduped flight
records equal the uninterrupted run's).
"""
import json
import os

import numpy as np
import pytest

import repro.obs as obs
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.obs.explain import (
    Flight, format_why, reconstruct_selection, why,
)
from repro.obs.recorder import (
    FlightRecorder, NULL_RECORDER, pack_bool, pack_floats, pack_ints,
    read_flight, unpack_bool, unpack_floats, unpack_ints,
)
from repro.server.events import Stage
from repro.sim import FaultPlan, Scenario, ServerKilled, make_scenario
from scan_layouts import FLCONFIG, MATRIX as _MATRIX

SEEDS = range(24)
_PRESETS = ("mobile-churn", "straggler", "diurnal")

# the server-shape axis of the pin: plain sync loop, pipelined async
# with the staleness refresher, and async behind the bounded-ingest
# check-in front end (shed/defer decisions in the record)
_SERVERS = ("sync", "async", "frontend")

TRACE_KEYS = ("selected", "completed", "refreshes", "acc", "n_active",
              "n_joined", "n_departed", "dropped")


def _trace(h):
    return {k: h[k] for k in TRACE_KEYS if k in h}


@pytest.fixture(scope="module")
def recorder_data():
    return FederatedDataset(small_spec(num_clients=16, num_classes=5,
                                       side=8, avg_samples=24), seed=13)


def _cfg(seed, server="sync", layout="padded", clustering="online",
         rounds=4, **kw):
    base = dict(rounds=rounds, clients_per_round=4, local_steps=1,
                summary="py", clustering=clustering, num_clusters=3,
                refresh_max_age=3, refresh_kl=0.05, recluster_every=2,
                hier_local_k=3, eval_every=2, seed=seed, **FLCONFIG[layout])
    if server == "sync":
        base["server"] = "sync"
    else:
        base.update(server="async", server_refresh="staleness",
                    ingest_delay_rounds=1, snapshot_max_age=2,
                    drift_mass_trigger=0.2)
    if server == "frontend":
        base.update(frontend="poisson", frontend_slo_p99_s=0.002,
                    ingest_max_depth=4)
    base.update(kw)
    return FLConfig(**base)


# ---------------------------------------------------------------------------
# packed-array codecs: byte-exact round trips


def test_codecs_roundtrip_exact():
    rs = np.random.RandomState(3)
    mask = rs.rand(77) < 0.4
    np.testing.assert_array_equal(unpack_bool(pack_bool(mask)), mask)
    ints = rs.randint(-2**62, 2**62, 33)
    np.testing.assert_array_equal(unpack_ints(pack_ints(ints)), ints)
    # float64 round trip is bitwise — near-ties in speed rankings must
    # sort identically after decode
    floats = rs.standard_normal(50)
    floats[7] = np.nextafter(floats[8], np.inf)     # 1-ulp near-tie
    got = unpack_floats(pack_floats(floats))
    assert got.tobytes() == floats.tobytes()
    # empty arrays survive too
    np.testing.assert_array_equal(
        unpack_ints(pack_ints(np.zeros(0, np.int64))), np.zeros(0))


def test_recorder_streams_header_once_and_appends(tmp_path):
    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    rec.record("round", round=0, selected=[1, 2])
    rec.close()
    rec2 = FlightRecorder(path)                 # resume: append mode
    rec2.record("round", round=0, selected=[3])   # re-executed round
    rec2.record("round", round=1, selected=[4])
    rec2.close()
    records = read_flight(path)
    assert [r["type"] for r in records] == ["header", "round", "round",
                                            "round"]
    assert records[0]["schema"] == 1
    fl = Flight(records)
    assert fl.schema == 1
    # last record wins for the re-executed round
    assert fl.round_record(0)["selected"] == [3]
    assert fl.rounds() == [0, 1]
    with pytest.raises(KeyError, match="no round record"):
        fl.round_record(9)


def test_read_flight_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    rec.record("round", round=0)
    rec.close()
    body = open(path).read()
    open(path, "w").write(body + '{"type": "rou')     # crash mid-append
    assert [r["type"] for r in read_flight(path)] == ["header", "round"]
    lines = body.splitlines()
    open(path, "w").write(lines[0][:5] + "\n" + "\n".join(lines[1:]) + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        read_flight(path)


def test_null_recorder_is_inert():
    assert not NULL_RECORDER.enabled
    assert NULL_RECORDER.record("round", round=0) is None
    assert NULL_RECORDER.records == ()
    NULL_RECORDER.close()
    # the module-level accessor returns it whenever no observer is armed
    assert obs.recorder() is NULL_RECORDER


# ---------------------------------------------------------------------------
# the recorder never moves the run


@pytest.mark.parametrize("server", ["sync", "frontend"])
def test_history_identical_with_recorder_on_vs_off(recorder_data, server):
    data = recorder_data
    sc = make_scenario("mobile-churn", 16, seed=3).to_config()
    cfg = _cfg(3, server=server)
    h_off = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    with obs.observe(flight=True) as ob:
        h_on = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    assert _trace(h_off) == _trace(h_on)
    assert len(ob.flight.records) > 0


# ---------------------------------------------------------------------------
# the 24-seed reconstruction pin (acceptance criterion)


def _explainable(fl, rec, rnd):
    """Every client's ``why`` must agree with the recorded decision."""
    n = rec["active"]["n"]
    selected = set(int(c) for c in rec["selected"])
    for client in range(n):
        w = why(client, rnd, fl)
        assert w["selected"] == (client in selected)
        assert w["outcome"].startswith("selected") == (client in selected)
        assert isinstance(format_why(w), str)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SEEDS)
def test_reconstruction_pins_selection_24seed(recorder_data, seed):
    """``reconstruct_selection`` must equal the recorded ``selected``
    list exactly, every round, for every matrix cell — live against the
    run that produced the record, not a canned fixture."""
    layout, clustering = _MATRIX[seed % len(_MATRIX)]
    preset = _PRESETS[seed % len(_PRESETS)]
    server = _SERVERS[seed % len(_SERVERS)]
    data = recorder_data
    sc = make_scenario(preset, data.spec.num_clients, seed=seed).to_config()
    cfg = _cfg(seed, server=server, layout=layout, clustering=clustering)
    with obs.observe(flight=True) as ob:
        h = run_federated(data, cfg, scenario=Scenario.from_config(sc))
    fl = Flight(ob.flight.records)
    assert fl.rounds() == list(range(cfg.rounds))
    for rnd in fl.rounds():
        rec = fl.round_record(rnd)
        got = reconstruct_selection(rec)
        assert got == [int(c) for c in rec["selected"]], (
            f"seed {seed} ({layout}/{clustering}/{preset}/{server}) "
            f"round {rnd}: replay {got} != recorded {rec['selected']}")
        # the record agrees with the history trace it rode along with
        assert [int(c) for c in rec["selected"]] == \
            [int(c) for c in h["selected"][rnd]]
    # full-fleet why() consistency on the last round of each run
    _explainable(fl, fl.round_record(cfg.rounds - 1), cfg.rounds - 1)


def test_reconstruction_pins_oort_policy(recorder_data):
    """The utility-ranking branch: explore set + exploit top-k replay."""
    data = recorder_data
    sc = make_scenario("mobile-churn", 16, seed=11).to_config()
    cfg = _cfg(11, server="sync", selection="oort", rounds=5)
    with obs.observe(flight=True) as ob:
        run_federated(data, cfg, scenario=Scenario.from_config(sc))
    fl = Flight(ob.flight.records)
    assert fl.rounds() == list(range(cfg.rounds))
    for rnd in fl.rounds():
        rec = fl.round_record(rnd)
        assert reconstruct_selection(rec) == [int(c) for c in
                                              rec["selected"]]


def test_reconstruction_refuses_unknown_policy():
    rec = {"policy": "mystery", "per_round": 2, "selected": [0],
           "active": pack_bool(np.ones(4, bool)),
           "available": pack_bool(np.ones(4, bool))}
    with pytest.raises(NotImplementedError, match="mystery"):
        reconstruct_selection(rec)


# ---------------------------------------------------------------------------
# drill-down context rides along


def test_why_carries_admission_refresh_and_checkin_context(recorder_data):
    data = recorder_data
    sc = make_scenario("mobile-churn", 16, seed=5).to_config()
    cfg = _cfg(5, server="frontend", rounds=6)
    with obs.observe(flight=True) as ob:
        run_federated(data, cfg, scenario=Scenario.from_config(sc))
    fl = Flight(ob.flight.records)
    kinds = {r["type"] for r in ob.flight.records}
    assert {"round", "admission", "checkin", "queue"} <= kinds
    # find a round where admission shed someone and check the lane story
    shed_round = next((r for r in fl.rounds()
                       if (fl.get("admission", r) or {}).get("shed")), None)
    assert shed_round is not None, "bounded queue never shed — dead cell"
    adm = fl.get("admission", shed_round)
    client = int(adm["shed"][0])
    w = why(client, shed_round, fl)
    assert w["admission"]["shed"] is True
    assert w["admission"]["lane"] in ("priority", "normal")
    assert w["admission"]["retry_round"] == shed_round + adm["retry_after"]
    assert "checkin" in w and "breached" in w["checkin"]
    assert "SHED" in format_why(w)


# ---------------------------------------------------------------------------
# replay determinism under kill-and-resume


def test_flight_replay_deterministic_under_kill_and_resume(
        recorder_data, tmp_path):
    """A run killed at stage boundaries and resumed must leave a flight
    file whose deduped records equal the uninterrupted run's — the
    recorder inherits the durability story instead of breaking it."""
    data = recorder_data
    sc = make_scenario("mobile-churn", 16, seed=9).to_config()
    cfg = _cfg(9, server="sync", rounds=3)
    flight_a = str(tmp_path / "uninterrupted.jsonl")
    with obs.observe(flight_path=flight_a):
        h0 = run_federated(data, cfg, scenario=Scenario.from_config(sc))

    flight_b = str(tmp_path / "killed.jsonl")
    durable = str(tmp_path / "durable")
    with obs.observe(flight_path=flight_b):
        with pytest.raises(ServerKilled):
            run_federated(data, cfg, scenario=Scenario.from_config(sc),
                          durable=durable,
                          faults=FaultPlan(crash_points=((1, Stage.TRAIN),)))
    with obs.observe(flight_path=flight_b):   # append to the same file
        h1 = run_federated(data, cfg, scenario=Scenario.from_config(sc),
                           resume_from=durable)
    assert _trace(h0) == _trace(h1)

    fa = Flight(read_flight(flight_a))
    fb = Flight(read_flight(flight_b))
    assert fb.rounds() == fa.rounds()
    # the killed file holds *more* raw lines (re-executed rounds), but
    # dedup must collapse them to the identical per-round records
    assert len(read_flight(flight_b)) > len(fa.rounds())
    for rnd in fa.rounds():
        for kind in ("round", "refresh"):
            ra, rb = fa.get(kind, rnd), fb.get(kind, rnd)
            assert json.dumps(ra, sort_keys=True) == \
                json.dumps(rb, sort_keys=True), (kind, rnd)
        assert reconstruct_selection(fb.round_record(rnd)) == \
            [int(c) for c in fb.round_record(rnd)["selected"]]


def test_same_seed_rerun_yields_identical_decision_records(recorder_data):
    """Flight records carry no wall-clock values (check-in latency
    fields excepted — compared on decision fields only), so two runs of
    the same seed produce identical record streams."""
    data = recorder_data
    sc = make_scenario("straggler", 16, seed=4).to_config()
    cfg = _cfg(4, server="frontend")
    streams = []
    for _ in range(2):
        with obs.observe(flight=True) as ob:
            run_federated(data, cfg, scenario=Scenario.from_config(sc))
        streams.append(list(ob.flight.records))
    a, b = streams
    assert len(a) == len(b)
    nondet = {"p50_s", "p99_s", "p999_s", "stall_s"}   # stall-derived
    for ra, rb in zip(a, b):
        ka = {k: v for k, v in ra.items() if k not in nondet}
        kb = {k: v for k, v in rb.items() if k not in nondet}
        assert json.dumps(ka, sort_keys=True) == \
            json.dumps(kb, sort_keys=True)
