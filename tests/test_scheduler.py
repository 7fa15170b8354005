"""Refresh-policy edge cases (core/scheduler.py) and selection quota
rounding (core/selection.py)."""
import numpy as np
import pytest

from repro.core import RefreshPolicy, batch_sym_kl, cluster_quotas, sym_kl
from repro.stream import SummaryRegistry


# ---------------------------------------------------------------------------
# sym_kl on degenerate distributions


def test_sym_kl_zero_vectors_is_zero():
    # eps floor turns all-zero inputs into uniform; divergence must be 0
    z = np.zeros(6, np.float32)
    assert sym_kl(z, z) == pytest.approx(0.0, abs=1e-6)
    assert np.isfinite(sym_kl(z, np.full(6, 1 / 6, np.float32)))


def test_sym_kl_one_hot_vs_uniform_positive_and_symmetric():
    one_hot = np.zeros(8, np.float32)
    one_hot[3] = 1.0
    uniform = np.full(8, 1 / 8, np.float32)
    d = sym_kl(one_hot, uniform)
    assert np.isfinite(d) and d > 0.5
    assert sym_kl(uniform, one_hot) == pytest.approx(d, rel=1e-6)
    assert sym_kl(one_hot, one_hot) == pytest.approx(0.0, abs=1e-6)


def test_sym_kl_disjoint_one_hots_finite():
    a = np.zeros(4, np.float32)
    b = np.zeros(4, np.float32)
    a[0] = 1.0
    b[3] = 1.0
    d = sym_kl(a, b)
    assert np.isfinite(d) and d > 1.0       # eps keeps the logs finite


def test_batch_sym_kl_matches_scalar_loop(rs):
    p = rs.dirichlet([0.3] * 7, 50).astype(np.float32)
    q = rs.dirichlet([0.3] * 7, 50).astype(np.float32)
    got = batch_sym_kl(p, q)
    want = np.asarray([sym_kl(p[i], q[i]) for i in range(50)])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # degenerate rows don't poison the batch
    p[0] = 0.0
    assert np.isfinite(batch_sym_kl(p, q)).all()


# ---------------------------------------------------------------------------
# refresh precedence: never-computed > max_age > kl_threshold


def test_refresh_precedence_age_beats_small_kl():
    reg = SummaryRegistry(2, RefreshPolicy(max_age_rounds=3,
                                           kl_threshold=0.5))
    p = np.array([0.5, 0.5], np.float32)
    reg.update_batch([0], 0, np.zeros((1, 4)), p[None])
    assert not reg.needs_refresh(0, 2, p)      # fresh, identical P(y)
    assert reg.needs_refresh(0, 3, p)          # aged out despite KL == 0
    assert reg.needs_refresh(1, 0, p)          # never computed, always stale


def test_refresh_kl_fires_only_past_threshold():
    reg = SummaryRegistry(1, RefreshPolicy(max_age_rounds=100,
                                           kl_threshold=0.2))
    p = np.array([0.5, 0.5], np.float32)
    reg.update_batch([0], 0, np.zeros((1, 4)), p[None])
    near = np.array([0.55, 0.45], np.float32)
    far = np.array([0.97, 0.03], np.float32)
    assert sym_kl(p, near) <= 0.2 < sym_kl(p, far)
    assert not reg.needs_refresh(0, 1, near)
    assert reg.needs_refresh(0, 1, far)


def test_vectorized_stale_scan_equals_per_client_loop(rs):
    n, c = 25, 5
    reg = SummaryRegistry(n, RefreshPolicy(max_age_rounds=4,
                                           kl_threshold=0.1))
    for rnd in range(10):
        fresh = rs.dirichlet([0.5] * c, n).astype(np.float32)
        want = [cl for cl in range(n)
                if reg.needs_refresh(cl, rnd, fresh[cl])]
        assert reg.stale_clients(rnd, fresh).tolist() == want
        for cl in want:
            if rs.rand() > 0.4:
                reg.update_batch([cl], rnd, rs.rand(1, 6), fresh[cl][None])


# ---------------------------------------------------------------------------
# cluster_quotas largest-remainder rounding


def test_cluster_quotas_exact_proportions():
    a = np.repeat([0, 1, 2], [50, 30, 20])
    q = cluster_quotas(a, 3, 10)
    np.testing.assert_array_equal(q, [5, 3, 2])


def test_cluster_quotas_largest_remainder_breaks_ties():
    # exact shares 10 * [7, 6, 5] / 18 = [3.889, 3.333, 2.778]: floor gives
    # [3, 3, 2], the 2 leftover seats go to the largest remainders (0 and 2)
    a = np.repeat([0, 1, 2], [7, 6, 5])
    q = cluster_quotas(a, 3, 10)
    np.testing.assert_array_equal(q, [4, 3, 3])
    assert q.sum() == 10


def test_cluster_quotas_sum_and_capacity(rs):
    for _ in range(20):
        k = rs.randint(2, 8)
        a = rs.randint(0, k, rs.randint(k, 60))
        per_round = rs.randint(1, 15)
        q = cluster_quotas(a, k, per_round)
        counts = np.bincount(a, minlength=k)
        assert (q <= counts).all()              # capped at cluster size
        assert q.sum() <= per_round
        if per_round <= a.size:
            assert q.sum() == per_round         # fully allocated when possible


def test_cluster_quotas_ignores_noise_and_empty():
    assert cluster_quotas(np.full(5, -1), 3, 4).tolist() == [0, 0, 0]
    a = np.array([-1, -1, 0, 0, 2])
    q = cluster_quotas(a, 3, 3)
    assert q.sum() == 3 and q[1] == 0           # noise excluded, empty gets 0
