"""Full clustering's input (``RoundContext.recluster_now``): the registry's
live ``dense()`` buffer goes to the device as it is, and under churn the live
rows are picked there.  Whatever the fleet, the clustering must see exactly
``registry.matrix_rows(have_ids)``, the reference for what rows clustering
should see, and must leave the registry's buffer as it found it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import dbscan, kmeans, minibatch_kmeans
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl import FLConfig
from repro.fl.rounds import RoundContext

N, C, D, K, RND = 24, 4, 10, 3, 2


def _reference(cfg, rows):
    """Today's branch over ``matrix_rows``: the same functions, the same
    key, the rows gathered on the host."""
    X = jnp.asarray(rows)
    if cfg.clustering == "dbscan":
        med = float(jnp.median(jnp.sqrt(
            jnp.sum(jnp.square(X - X.mean(0)), -1))))
        res = dbscan(X, eps=med * 0.5, min_samples=3)
        return np.asarray(res.labels, np.int64), max(int(res.num_clusters), 1)
    fn = minibatch_kmeans if cfg.clustering == "minibatch" else kmeans
    res = fn(X, K, jax.random.PRNGKey(cfg.seed + RND), use_kernel=False)
    return np.asarray(res.assignment, np.int64), K


@pytest.mark.parametrize("fleet", ["whole", "churned"])
@pytest.mark.parametrize("registry", ["dict", "streaming", "sharded"])
@pytest.mark.parametrize("clustering", ["kmeans", "minibatch", "dbscan"])
def test_recluster_input_is_matrix_rows(clustering, registry, fleet,
                                        tmp_path):
    data = FederatedDataset(small_spec(num_clients=N, num_classes=C, side=8,
                                       avg_samples=16), seed=0)
    cfg = FLConfig(registry=registry, clustering=clustering, num_clusters=K,
                   summary="py", seed=3)
    ctx = RoundContext(data, cfg, None)
    rng = np.random.default_rng(7)
    centers = rng.normal(size=(K, D)) * 4.0
    summaries = (centers[rng.integers(0, K, N)]
                 + rng.normal(size=(N, D))).astype(np.float32)
    fresh = rng.dirichlet(np.ones(C), N).astype(np.float32)
    ctx.ingest(0, {c: summaries[c] for c in range(N)}, fresh)
    active = np.ones(N, bool)
    if fleet == "churned":
        for c in (1, 9, 17):               # departed: rows zeroed
            ctx.registry.remove(c)
        active[[4, 5, 20]] = False         # inactive this round
    have_ids = np.flatnonzero(ctx.registry.has_mask() & active)
    want_asg, want_k = _reference(cfg, ctx.registry.matrix_rows(have_ids))
    before = ctx.registry.dense().copy()

    n0 = len(obs.profiled().events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctx.recluster_now(RND, active, np.arange(N))
    finally:
        jax.profiler.stop_trace()
    events = obs.profiled().events[n0:]

    want = np.full(N, -1, np.int64)
    want[have_ids] = want_asg
    np.testing.assert_array_equal(ctx.assignment, want)
    assert ctx.num_clusters == want_k
    # the registry's buffer was the copy's source, never written
    assert ctx.registry.dense().tobytes() == before.tobytes()

    (gather,) = [e for e in events if e["name"] == "recluster/gather"]
    take = 0 if fleet == "whole" else len(have_ids)
    assert gather["args"] == {"rows": len(have_ids), "device_take": take}
    (put,) = [e for e in events if e["name"] == "recluster/put"]
    assert put["args"]["bytes"] == N * D * 4   # the whole buffer, both ways
