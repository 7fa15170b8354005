"""Pallas kernels vs pure-jnp oracles: shape & dtype sweeps (interpret mode
on CPU, asked for explicitly — the kernels target TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.sketch_update import cm_hash_params


@pytest.mark.parametrize("n,k,d", [(64, 8, 32), (130, 7, 300), (257, 16, 64),
                                   (1000, 12, 97)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_dist(rs, n, k, d, dtype):
    x = jnp.asarray(rs.normal(size=(n, d)), dtype)
    c = jnp.asarray(rs.normal(size=(k, d)), dtype)
    got = ops.pairwise_dist(x, c, interpret=True)
    want = ref.pairwise_dist_ref(x, c)
    tol = 1e-3 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol * d ** 0.5, rtol=tol)
    assert got.dtype == jnp.float32
    assert float(jnp.min(got)) >= 0.0


@pytest.mark.parametrize("n,h,c", [(100, 64, 10), (513, 32, 62), (64, 16, 3),
                                   (1024, 128, 600)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_mean(rs, n, h, c, dtype):
    f = jnp.asarray(rs.normal(size=(n, h)), dtype)
    lab = jnp.asarray(rs.randint(0, c, n), jnp.int32)
    keep = jnp.asarray(rs.rand(n) > 0.2)
    got = ops.seg_mean(f, lab, keep, c, interpret=True)
    want = ref.seg_mean_ref(f, lab, keep, c)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("n,d,c,b", [(100, 20, 7, 8), (257, 50, 62, 16),
                                     (64, 7, 3, 4)])
def test_class_hist(rs, n, d, c, b):
    q = jnp.asarray(rs.randint(0, b, (n, d)), jnp.int32)
    lab = jnp.asarray(rs.randint(0, c, n), jnp.int32)
    v = jnp.asarray(rs.rand(n) > 0.1)
    got = ops.class_hist(q, lab, v, c, b, interpret=True)
    want = ref.class_hist_ref(q, lab, v, c, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)
    # counts conservation: total entries == valid * D
    assert float(got.sum()) == float(v.sum()) * d


def test_seg_mean_all_dropped(rs):
    f = jnp.asarray(rs.normal(size=(32, 8)), jnp.float32)
    lab = jnp.zeros(32, jnp.int32)
    keep = jnp.zeros(32, bool)
    got = ops.seg_mean(f, lab, keep, 4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 0.0)


# ---------------------------------------------------------------------------
# the main path's kernel switch (kernels.ops.on_tpu) seen from the CPU


def _ops_calls():
    f = jnp.ones((16, 8), jnp.float32)
    lab = jnp.zeros(16, jnp.int32)
    ok = jnp.ones(16, bool)
    a, b = cm_hash_params(2)
    return {
        "pairwise_dist": lambda: ops.pairwise_dist(f, f[:4]),
        "seg_mean": lambda: ops.seg_mean(f, lab, ok, 4),
        "class_hist": lambda: ops.class_hist(lab.reshape(2, 8), lab[:2],
                                             ok[:2], 4, 4),
        "sketch_update": lambda: ops.sketch_update(lab, lab, ok, 2, 16,
                                                   a, b),
    }


@pytest.mark.parametrize("name", ["pairwise_dist", "seg_mean", "class_hist",
                                  "sketch_update"])
def test_ops_do_not_interpret_unless_asked(name):
    """Off the TPU a wrapper called without ``interpret=True`` compiles for
    the backend (and Pallas refuses the CPU) instead of silently taking
    the interpreter."""
    assert not ops.on_tpu()
    with pytest.raises(ValueError, match="interpret mode"):
        _ops_calls()[name]()


@pytest.mark.parametrize("clustering", ["online", "hierarchical"])
def test_round_context_takes_reference_path_on_cpu(clustering):
    from repro.data.synthetic import FederatedDataset, small_spec
    from repro.fl import FLConfig
    from repro.fl.rounds import RoundContext
    data = FederatedDataset(small_spec(num_clients=8, num_classes=4, side=8,
                                       avg_samples=16), seed=0)
    ctx = RoundContext(data, FLConfig(clustering=clustering), None)
    assert ctx.use_kernel is False
    assert ctx.engine.use_kernel is False
    assert ctx.maintainer.policy.use_kernel is False


@pytest.mark.parametrize("method,classes,bins,want", [
    ("encoder", 62, 8, 256),       # 256 * 62 = 15872 <= 16384 offset rows
    ("encoder", 600, 8, 16),
    ("pxy", 62, 8, 16),            # 16 * 62 = 992 <= 1024
    ("pxy", 62, 16, 8),            # 8 * 62 = 496 <= 512
])
def test_engine_bounds_clients_by_offset_classes(method, classes, bins, want):
    from repro.core.batched_summary import BatchedSummaryEngine
    kw = dict(encoder_fn=lambda x: x, bins=bins, encoder_dim=32)
    eng = BatchedSummaryEngine(method, classes, use_kernel=True, **kw)
    assert eng.max_batch == want
    # the bound holds for the power-of-two padded client axis too
    limit = (ops.seg_mean_max_classes(32) if method == "encoder"
             else ops.class_hist_max_classes(bins))
    assert eng.max_batch * classes <= limit < 2 * eng.max_batch * classes
    # the reference path keeps its own defaults
    ref = BatchedSummaryEngine(method, classes, **kw)
    assert ref.max_batch == (16 if method == "pxy" else 256)


def test_label_space_too_large_for_one_launch_fails_loudly():
    from repro.core.batched_summary import BatchedSummaryEngine
    with pytest.raises(ValueError, match="classes exceed"):
        BatchedSummaryEngine("pxy", 2000, bins=8, use_kernel=True)
    q = jnp.zeros((8, 8), jnp.int32)
    with pytest.raises(ValueError, match="classes exceed"):
        ops.class_hist(q, q[:, 0], q[:, 0] == 0, 10000, 8, interpret=True)
