"""FL runtime: aggregation properties, selection, scheduler, tiny e2e round
loop (real training) — the paper's workflow end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import RefreshPolicy, SelectionConfig, cluster_quotas, \
    select_devices, sym_kl
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig, fedavg, run_federated
from repro.fl.system import SystemModel, SystemSpec
from repro.stream import SummaryRegistry
from repro.utils.tree import tree_weighted_sum


# ---------------------------------------------------------------------------
# fedavg


@settings(deadline=None, max_examples=20)
@given(st.lists(st.floats(1, 100), min_size=1, max_size=5),
       st.integers(0, 2 ** 31 - 1))
def test_fedavg_weighted_mean_property(sizes, seed):
    rs = np.random.RandomState(seed)
    base = {"w": jnp.asarray(rs.normal(size=(3, 2)), jnp.float32)}
    deltas = [{"w": jnp.asarray(rs.normal(size=(3, 2)), jnp.float32)}
              for _ in sizes]
    out = fedavg(base, deltas, sizes)
    want = np.asarray(base["w"]) + sum(
        (s / sum(sizes)) * np.asarray(d["w"]) for s, d in zip(sizes, deltas))
    np.testing.assert_allclose(np.asarray(out["w"]), want, rtol=1e-5, atol=1e-5)


def test_fedavg_identity_when_no_updates():
    base = {"w": jnp.ones((2, 2))}
    out = fedavg(base, [], [])
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


# ---------------------------------------------------------------------------
# selection


def test_cluster_quotas_sum_and_bounds(rs):
    assignment = rs.randint(0, 5, 100)
    q = cluster_quotas(assignment, 5, 12)
    assert q.sum() == 12
    counts = np.bincount(assignment, minlength=5)
    assert (q <= counts).all()


def test_haccs_selection_covers_clusters(rs):
    n = 60
    assignment = np.repeat(np.arange(3), 20)
    speeds = rs.lognormal(0, 0.5, n)
    avail = np.ones(n, bool)
    sel = select_devices(assignment, 3, speeds, avail,
                         SelectionConfig(9, "haccs"), np.random.default_rng(0))
    assert len(sel) == 9
    # proportional: each cluster of equal size gets 3
    got = np.bincount(assignment[sel], minlength=3)
    np.testing.assert_array_equal(got, [3, 3, 3])
    # picks fastest available within each cluster
    for c in range(3):
        members = np.flatnonzero(assignment == c)
        fastest = members[np.argsort(-speeds[members])][:3]
        assert set(sel[assignment[sel] == c]) == set(fastest)


def test_selection_respects_availability(rs):
    n = 20
    assignment = np.zeros(n, np.int64)
    avail = np.zeros(n, bool)
    avail[:5] = True
    sel = select_devices(assignment, 1, rs.rand(n), avail,
                         SelectionConfig(8, "haccs"), np.random.default_rng(0))
    assert set(sel).issubset(set(range(5)))


# ---------------------------------------------------------------------------
# scheduler


def test_registry_refresh_logic():
    reg = SummaryRegistry(3, RefreshPolicy(max_age_rounds=5, kl_threshold=0.2))
    p = np.array([0.5, 0.5])
    assert reg.needs_refresh(0, 0, p)            # never computed
    reg.update_batch([0], 0, np.zeros((1, 4)), p[None])
    assert not reg.needs_refresh(0, 1, p)        # fresh
    assert reg.needs_refresh(0, 6, p)            # aged out
    drifted = np.array([0.95, 0.05])
    assert sym_kl(p, drifted) > 0.2
    assert reg.needs_refresh(0, 1, drifted)      # drift trips the KL test


def test_system_model_round_time():
    sm = SystemModel(4, SystemSpec(speed_sigma=0.0, availability=1.0), seed=0)
    sm.speeds = np.array([1.0, 2.0, 4.0, 0.5])
    t = sm.round_time(np.array([0, 1]), local_steps=10)
    assert abs(t - 10.0) < 1e-9                  # straggler = slowest selected
    t2 = sm.round_time(np.array([0, 1]), 10, summary_times={0: 7.0})
    assert abs(t2 - 17.0) < 1e-9                 # refresh charged on critical path


# ---------------------------------------------------------------------------
# end-to-end mini federation


@pytest.mark.slow
def test_federated_loop_learns_and_tracks_time():
    data = FederatedDataset(small_spec(num_clients=24, num_classes=6, side=8,
                                       avg_samples=40), seed=1)
    cfg = FLConfig(rounds=6, clients_per_round=5, local_steps=5,
                   summary="encoder", num_clusters=3, coreset_k=24,
                   recluster_every=3, eval_every=5, seed=1)
    h = run_federated(data, cfg)
    assert h["acc"][-1] > 0.5                 # learned something non-trivial
    assert h["sim_time"][-1] > 0
    assert h["refreshes"][-1] >= 24           # every client summarized once
    # selected devices exist and are unique per round
    for sel in h["selected"]:
        assert len(set(sel)) == len(sel)


@pytest.mark.slow
def test_summary_refresh_reacts_to_drift():
    data = FederatedDataset(small_spec(num_clients=12, num_classes=5, side=8,
                                       avg_samples=32), seed=2)
    cfg = FLConfig(rounds=6, clients_per_round=4, local_steps=2,
                   summary="py", num_clusters=3, refresh_max_age=100,
                   refresh_kl=0.05, drift_start=3, drift_per_round=0.5,
                   eval_every=5, seed=2)
    h = run_federated(data, cfg)
    before = h["refreshes"][2]
    after = h["refreshes"][-1]
    assert before == 12            # initial summaries only
    assert after > before          # drift forced re-summarization


# ---------------------------------------------------------------------------
# config validation: unknown backend strings must fail loudly (regression —
# this pins registry= / summary_engine= / clustering= / server=;
# repro.server config strings are pinned in tests/test_server.py)


@pytest.mark.parametrize("field,value,msg", [
    ("registry", "redis", "unknown registry"),
    ("summary_engine", "turbo", "unknown summary_engine"),
    ("clustering", "louvain", "unknown clustering"),
    ("server", "threads", "unknown server"),
])
def test_unknown_backend_strings_rejected(field, value, msg):
    data = FederatedDataset(small_spec(num_clients=6, num_classes=3, side=8,
                                       avg_samples=12), seed=0)
    with pytest.raises(ValueError, match=msg):
        run_federated(data, FLConfig(rounds=1, **{field: value}))
