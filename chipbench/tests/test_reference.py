"""The plain references against the program's own code at a small size on
the CPU: the same inputs give the same answers.  (The references import
nothing of the program; only these tests do.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as ref
from chipbench.summaries import encoder as enc


def test_encoder_params_and_forward_match_the_program():
    from repro.models.cnn import CNNConfig, build_cnn, cnn_apply
    prog = build_cnn(CNNConfig(in_channels=1, feature_dim=32),
                     jax.random.PRNGKey(7))
    mine = enc.encoder_params(1, 32)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
            for path, v in jax.tree_util.tree_leaves_with_path(prog)}
    assert set(flat) == set(mine)
    for k in mine:
        np.testing.assert_array_equal(flat[k], mine[k])
    x = np.random.default_rng(0).random((5, 12, 12, 1), np.float32)
    got = np.asarray(enc.encode(mine, x, operand=enc.EXACT))
    np.testing.assert_allclose(got, np.asarray(cnn_apply(prog, x)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,c", [(5, 3), (37, 6), (96, 4), (130, 10)])
def test_coreset_matches_the_program(n, c):
    from repro.core.coreset import coreset_indices
    labels = np.random.default_rng(n).integers(0, c, n).astype(np.int32)
    key = jax.random.PRNGKey(n * 100003 + c)
    idx, kept = enc.coreset(labels, c, 64, key)
    b = enc._pow2(n)
    lab = np.zeros(b, np.int32)
    lab[:n] = labels
    p_idx, p_kept = coreset_indices(jnp.asarray(lab),
                                    jnp.arange(b) < n, c, 64, key)
    np.testing.assert_array_equal(idx, np.asarray(p_idx))
    np.testing.assert_array_equal(kept, np.asarray(p_kept))


@pytest.mark.parametrize("n,c", [(96, 4), (130, 10), (500, 62)])
def test_exact_quotas_are_largest_remainder(n, c):
    labels = np.random.default_rng(n).integers(0, c, n).astype(np.int32)
    idx, kept = enc.coreset(labels, c, 64, jax.random.PRNGKey(1),
                            exact_quotas=True)
    got = np.bincount(labels[idx][kept], minlength=c)
    exact = 64 * np.bincount(labels, minlength=c) / n
    assert got.sum() == min(64, n)
    assert np.all((got == np.floor(exact)) | (got == np.ceil(exact)))


@pytest.mark.parametrize("seed", range(6))
def test_haccs_matches_the_program(seed):
    from repro.policies import ClientStats, PolicyContext, make_policy
    rng = np.random.default_rng(seed)
    n, k = 200, 8
    asg = rng.integers(0, k, n)
    avail = rng.random(n) < 0.5
    speeds = np.round(rng.lognormal(0, 0.5, n), 1)     # with ties
    ctx = PolicyContext(round_idx=0, per_round=10, assignment=asg,
                        num_clusters=k, speeds=speeds, available=avail,
                        rng=np.random.RandomState(0), active=np.ones(n, bool),
                        label_dists=None, data_sizes=np.ones(n),
                        stats=ClientStats(n))
    want = make_policy("haccs").select(ctx)
    np.testing.assert_array_equal(ref.haccs(asg, k, avail, speeds, 10), want)


def test_nearest_violations_counts_misassigned_rows():
    x = np.array([[0.0, 0.0], [10.0, 0.0], [0.1, 0.0]])
    cents = np.array([[0.0, 0.0], [10.0, 0.0]])
    assert ref.nearest_violations(x, np.array([0, 1, 0]), cents) == 0
    assert ref.nearest_violations(x, np.array([1, 1, 0]), cents) == 1


def test_stated_precision_rounds_operands_and_control_is_coarser():
    # one bfloat16 pass stays near float32; the control's float8 operands
    # and bfloat16 arrays move the features an order of magnitude further
    params = enc.encoder_params(1, 32)
    x = np.random.default_rng(1).random((64, 12, 12, 1), np.float32)
    exact = np.asarray(enc.encode(params, x, operand=enc.EXACT))
    stated = np.asarray(enc.encode(params, x, operand="bfloat16"))
    control = np.asarray(enc.encode(params, x, dtype=jnp.bfloat16,
                                    operand=enc.CONTROL), np.float32)

    def rms(a):
        return np.sqrt(np.mean((a - exact) ** 2) / np.mean(exact ** 2))
    assert 0 < rms(stated) < 0.02
    assert rms(control) > 5 * rms(stated)


def test_empty_clusters_and_member_means():
    x = np.array([[0.0], [1.0], [10.0], [11.0]])
    means = ref.member_means(x, np.array([0, 0, 1, 1]), 3)
    np.testing.assert_allclose(means[:2, 0], [0.5, 10.5])
    assert means[2, 0] >= 1e30
