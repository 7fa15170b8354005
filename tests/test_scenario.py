"""Fleet scenario engine (repro/sim, DESIGN.md §6): config round-trip,
deterministic replay, churn/availability/drift semantics, deadline
straggler-timeout behavior, and the preset x scan layout x clustering support
matrix running end-to-end."""
import numpy as np
import pytest

from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, run_federated
from repro.fl.rounds import LegacySystemScenario
from repro.fl.system import SystemSpec
from repro.sim import (
    DATA_HINTS, PRESET_NAMES, Scenario, ScenarioConfig, make_scenario,
)
from scan_layouts import FLCONFIG

PLAN_FIELDS = ("active", "available", "speeds", "drift", "joined",
               "departed", "fail_u", "upload_cost")


def _plan_trace(scenario, rounds):
    return [scenario.round_plan(r) for r in range(rounds)]


def _assert_traces_equal(a, b):
    assert len(a) == len(b)
    for pa, pb in zip(a, b):
        for f in PLAN_FIELDS:
            np.testing.assert_array_equal(getattr(pa, f), getattr(pb, f),
                                          err_msg=f"round {pa.round_idx}: {f}")
        assert pa.deadline == pb.deadline


# ---------------------------------------------------------------------------
# determinism / replay


def test_config_dict_round_trip():
    sc = make_scenario("mobile-churn", 32, seed=5)
    cfg = ScenarioConfig.from_dict(sc.to_config())
    assert cfg == sc.config
    assert cfg.tiers == sc.config.tiers           # tuples survive the trip


@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_replay_identical_plans(preset):
    a = make_scenario(preset, 40, seed=3)
    b = Scenario.from_config(a.to_config())
    trace_b = _plan_trace(b, 12)
    _assert_traces_equal(_plan_trace(a, 12), trace_b)
    # reset() rewinds to the exact same stream
    a.reset()
    _assert_traces_equal(_plan_trace(a, 12), trace_b)


def test_round_plan_out_of_order_raises():
    sc = make_scenario("uniform-iid", 8, seed=0)
    sc.round_plan(0)
    with pytest.raises(RuntimeError):
        sc.round_plan(2)
    sc.round_plan(1)                               # sequential is fine


def test_run_federated_replay_identical():
    """Same seeded scenario config twice => identical round-by-round
    selection, summary, and metric traces (Date/PRNG discipline)."""
    n = 14
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5, side=8,
                                       avg_samples=24), seed=6)
    config = make_scenario("mobile-churn", n, seed=8).to_config()
    cfg = FLConfig(rounds=4, clients_per_round=4, local_steps=2, summary="py",
                   clustering="kmeans", num_clusters=3,
                   eval_every=2, seed=3)
    h1 = run_federated(data, cfg, scenario=Scenario.from_config(config))
    h2 = run_federated(data, cfg, scenario=Scenario.from_config(config))
    for k in ("selected", "completed", "refreshes", "acc", "dropped",
              "n_active", "n_joined", "n_departed", "sim_time"):
        assert h1[k] == h2[k], k
    np.testing.assert_allclose(h1["kl_coverage"], h2["kl_coverage"], atol=0)


# ---------------------------------------------------------------------------
# scenario semantics


def test_churn_joins_departs_and_never_empties():
    sc = make_scenario("mobile-churn", 60, seed=2)
    joins = departs = 0
    for r in range(30):
        plan = sc.round_plan(r)
        joins += plan.joined.size
        departs += plan.departed.size
        assert plan.active.sum() >= 1
        # availability implies membership
        assert not (plan.available & ~plan.active).any()
    assert joins > 0 and departs > 0


def test_diurnal_availability_waves():
    sc = make_scenario("diurnal", 400, seed=1)
    rates = [p.available.mean() for p in _plan_trace(sc, 12)]
    assert max(rates) > 2.5 * min(rates)       # day/night swing is real


def test_staggered_drift_schedule():
    sc = make_scenario("pathological-noniid", 30, seed=4)
    plans = _plan_trace(sc, 16)
    d = np.stack([p.drift for p in plans])     # [T, N]
    assert (d >= 0).all() and (d <= 1).all()
    assert (np.diff(d, axis=0) >= -1e-12).all()    # monotone per client
    assert d[0].sum() == 0.0                       # starts pre-drift
    assert d[-1].max() > 0.5                       # drift really happened
    # staggered: clients reach a given level at different rounds
    assert np.unique(d[8]).size > 1


def test_battery_gates_availability():
    cfg = ScenarioConfig(num_clients=20, seed=0, battery=True,
                         tiers=(("phone-low", 1.0),), base_availability=1.0)
    sc = Scenario(cfg)
    plan = sc.round_plan(0)
    assert plan.available.sum() > 0
    # drain everyone far below one participation's cost
    for _ in range(10):
        sc.note_selected(np.flatnonzero(plan.active))
    assert (sc._battery < 1.0).all()
    plan1 = sc.round_plan(1)
    # recharge (0.8/round for phone-low) cannot cover drain of 1.0 => gated
    assert plan1.available.sum() < plan.active.sum()


# ---------------------------------------------------------------------------
# round loop semantics under scenarios


def test_deadline_drops_stragglers_and_caps_round_time():
    n = 16
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5, side=8,
                                       avg_samples=24), seed=7)
    sc = make_scenario("straggler", n, seed=5, deadline=6.0)
    cfg = FLConfig(rounds=5, clients_per_round=6, local_steps=4, summary="py",
                   num_clusters=3, eval_every=4, seed=5)
    h = run_federated(data, cfg, scenario=sc)
    assert sum(h["dropped"]) > 0               # someone missed the deadline
    round_times = np.diff([0.0] + h["sim_time"])
    assert (round_times <= 6.0 + 1e-9).all()   # server never waits past it
    # rounds where someone dropped are charged the full deadline
    for dt, dropped, sel in zip(round_times, h["dropped"], h["selected"]):
        if dropped and sel:
            assert abs(dt - 6.0) < 1e-9


def test_departed_clients_are_never_selected():
    n = 20
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5, side=8,
                                       avg_samples=24), seed=8)
    config = make_scenario("mobile-churn", n, seed=9).to_config()
    cfg = FLConfig(rounds=6, clients_per_round=5, local_steps=1, summary="py",
                   clustering="kmeans", num_clusters=3,
                   eval_every=5, seed=6)
    h = run_federated(data, cfg, scenario=Scenario.from_config(config))
    # replay the scenario to recover the per-round membership
    replay = Scenario.from_config(config)
    for r, sel in enumerate(h["selected"]):
        plan = replay.round_plan(r)
        assert set(sel) <= set(np.flatnonzero(plan.active).tolist()), \
            f"round {r} selected an absent client"
    assert sum(h["n_departed"]) > 0            # churn actually happened


def test_joined_clients_get_summarized_and_participate():
    n = 24
    data = FederatedDataset(small_spec(num_clients=n, num_classes=5, side=8,
                                       avg_samples=24), seed=9)
    sc = make_scenario("mobile-churn", n, seed=11, deadline=None,
                       dropout_prob=0.0)
    cfg = FLConfig(rounds=8, clients_per_round=5, local_steps=1, summary="py",
                   clustering="kmeans", num_clusters=3,
                   refresh_max_age=100, eval_every=7, seed=7)
    h = run_federated(data, cfg, scenario=sc)
    assert sum(h["n_joined"]) > 0
    # mid-run joiners trigger refreshes beyond the initial fleet size
    assert h["refreshes"][-1] > h["n_active"][0]


# ---------------------------------------------------------------------------
# support matrix: presets x (scan layout x clustering), end-to-end

# full support matrix: every scan layout x clustering cell (DESIGN.md §6)
COMBOS = [(layout, clus) for layout in ("whole", "padded")
          for clus in ("kmeans", "minibatch", "online")]


@pytest.fixture(scope="module")
def matrix_data():
    return FederatedDataset(small_spec(num_clients=12, num_classes=4, side=6,
                                       avg_samples=16), seed=12)


@pytest.mark.slow
@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_preset_runs_all_registry_clustering_combos(matrix_data, preset):
    data = matrix_data
    assert preset in DATA_HINTS
    for layout, clustering in COMBOS:
        sc = make_scenario(preset, data.spec.num_clients, seed=2)
        cfg = FLConfig(rounds=2, clients_per_round=3, local_steps=1,
                       summary="py", clustering=clustering, num_clusters=2,
                       hidden=16, eval_every=1, seed=2, **FLCONFIG[layout])
        h = run_federated(data, cfg, scenario=sc)
        assert len(h["acc"]) == 2
        assert h["refreshes"][-1] > 0
        assert np.isfinite(h["sim_time"][-1])
        for sel in h["selected"]:
            assert len(set(sel)) == len(sel)


def test_system_spec_and_scenario_are_mutually_exclusive():
    data = FederatedDataset(small_spec(num_clients=8, num_classes=4, side=6,
                                       avg_samples=16), seed=3)
    cfg = FLConfig(rounds=1, clients_per_round=2, local_steps=1, summary="py",
                   num_clusters=2, hidden=16, seed=3)
    with pytest.raises(ValueError, match="mutually exclusive"):
        run_federated(data, cfg, SystemSpec(speed_sigma=2.0),
                      scenario=make_scenario("uniform-iid", 8, seed=3))


def test_batch_label_dists_bitwise_match_per_client():
    """The round loop's vectorized drift signal must equal the per-client
    reference exactly, or staleness decisions would drift from PR-2."""
    data = FederatedDataset(small_spec(num_clients=50, num_classes=7), seed=4)
    rs = np.random.RandomState(0)
    for drift in (0.0, 0.4, rs.rand(50)):
        d = np.broadcast_to(np.asarray(drift, np.float64), (50,))
        per = np.stack([data.client_label_dist(c, float(d[c]))
                        for c in range(50)])
        np.testing.assert_array_equal(data.client_label_dists(drift), per)


# ---------------------------------------------------------------------------
# legacy adapter


def test_legacy_config_round_trip_is_loud_and_exact():
    """history['scenario'] from a legacy run must not silently rebuild a
    different fleet: sim.Scenario rejects it, LegacySystemScenario
    reconstructs the identical adapter."""
    legacy = LegacySystemScenario(8, SystemSpec(speed_sigma=0.5), seed=3,
                                  drift_start=2, drift_per_round=0.1)
    cfg = legacy.to_config()
    with pytest.raises(ValueError):
        Scenario.from_config(cfg)
    rebuilt = LegacySystemScenario.from_config(cfg)
    for r in range(3):
        a, b = legacy.round_plan(r), rebuilt.round_plan(r)
        np.testing.assert_array_equal(a.available, b.available)
        np.testing.assert_array_equal(a.speeds, b.speeds)
        np.testing.assert_array_equal(a.drift, b.drift)


def test_legacy_scenario_reset_replays_system_stream():
    legacy = LegacySystemScenario(8, SystemSpec(), seed=1, drift_start=0,
                                  drift_per_round=0.0)
    trace = [legacy.round_plan(r) for r in range(4)]
    legacy.reset()
    replay = [legacy.round_plan(r) for r in range(4)]
    for a, b in zip(trace, replay):
        np.testing.assert_array_equal(a.available, b.available)
        np.testing.assert_array_equal(a.speeds, b.speeds)


def test_explicit_legacy_scenario_with_custom_spec():
    """Passing a LegacySystemScenario explicitly (custom SystemSpec) must
    work — run_federated resets any supplied scenario before round 0."""
    data = FederatedDataset(small_spec(num_clients=10, num_classes=4, side=6,
                                       avg_samples=16), seed=2)
    sc = LegacySystemScenario(10, SystemSpec(speed_sigma=0.5), seed=1,
                              drift_start=0, drift_per_round=0.0)
    sc.round_plan(0)                       # pre-stepped: reset must rewind
    cfg = FLConfig(rounds=2, clients_per_round=3, local_steps=1, summary="py",
                   num_clusters=2, hidden=16, eval_every=1, seed=1)
    h = run_federated(data, cfg, scenario=sc)
    assert len(h["acc"]) == 2


def test_legacy_history_carries_scenario_metadata():
    data = FederatedDataset(small_spec(num_clients=10, num_classes=4, side=6,
                                       avg_samples=16), seed=1)
    cfg = FLConfig(rounds=2, clients_per_round=3, local_steps=1, summary="py",
                   num_clusters=2, hidden=16, eval_every=1, seed=1)
    h = run_federated(data, cfg)
    assert h["scenario"]["name"] == "legacy-system"
    assert h["n_active"] == [10, 10]
    assert h["dropped"] == [0, 0]
    assert h["dropped_rounds"] == 0
