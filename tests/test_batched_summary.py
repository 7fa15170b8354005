"""Fleet-scale batched summary engine: numerical equivalence with the
per-client ``timed_summary`` path (same bucket padding, same PRNG seeds ⇒
same keys), dispatch accounting, kernel-backed batched paths, and registry
bookkeeping."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import (
    BatchedSummaryEngine, RefreshPolicy, SummaryRegistry,
    batched_per_label_mean, batched_pxy_histogram, bucket_size,
)
from repro.core.batched_summary import seed_keys
from repro.data.synthetic import FederatedDataset, small_spec
from repro.fl.client import timed_summary
from repro.models.cnn import CNNConfig, build_cnn, cnn_apply


@pytest.fixture(scope="module")
def data():
    # lognormal sizes => ragged clients spanning several power-of-two buckets
    spec = small_spec(num_clients=24, num_classes=6, side=8, avg_samples=40)
    return FederatedDataset(spec, seed=1)


@pytest.fixture(scope="module")
def enc_fn():
    enc = build_cnn(CNNConfig(in_channels=1, feature_dim=16),
                    jax.random.PRNGKey(7))
    return jax.jit(lambda x: cnn_apply(enc, x))


def _items(data, drift=0.0):
    return [(c, *data.client_data(c, drift), 1000 + c)
            for c in range(data.spec.num_clients)]


@pytest.mark.parametrize("method", ["py", "pxy", "encoder"])
@pytest.mark.parametrize("drift", [0.0, 0.35])
def test_batched_matches_per_client(data, enc_fn, method, drift):
    spec = data.spec
    engine = BatchedSummaryEngine(method, spec.num_classes, encoder_fn=enc_fn,
                                  coreset_k=16, bins=8)
    results = engine.summarize(_items(data, drift))
    assert engine.stats.clients == spec.num_clients
    # buckets exist => strictly fewer dispatches than clients
    assert engine.stats.dispatches < spec.num_clients
    for c in range(spec.num_clients):
        feats, labels, valid = data.client_data(c, drift)
        s, ld, dt = timed_summary(method, feats, labels, valid,
                                  spec.num_classes, encoder_fn=enc_fn,
                                  coreset_k=16, bins=8,
                                  key=jax.random.PRNGKey(1000 + c))
        np.testing.assert_allclose(results[c].summary, s, atol=1e-5)
        np.testing.assert_allclose(results[c].label_dist, ld, atol=1e-6)
        assert results[c].seconds > 0.0


def test_ragged_sizes_span_buckets(data):
    buckets = {bucket_size(int(n)) for n in data.sizes}
    assert len(buckets) > 1           # the fixture really is ragged
    engine = BatchedSummaryEngine("py", data.spec.num_classes)
    engine.summarize(_items(data))
    assert engine.stats.dispatches == len(buckets)


def test_amortized_time_sums_to_batch_wall(data):
    engine = BatchedSummaryEngine("py", data.spec.num_classes)
    results = engine.summarize(_items(data))
    total = sum(r.seconds for r in results.values())
    assert abs(total - engine.stats.wall_s) < 1e-6


def test_registry_bookkeeping_unchanged(data, enc_fn):
    """Refreshing through the engine leaves the SummaryRegistry in the same
    state (counts, ages, stored summaries) as the per-client loop."""
    spec = data.spec
    policy = RefreshPolicy(max_age_rounds=10, kl_threshold=0.05)
    reg_a = SummaryRegistry(spec.num_clients, policy)
    reg_b = SummaryRegistry(spec.num_clients, policy)
    fresh = {c: data.client_label_dist(c) for c in range(spec.num_clients)}
    rnd = 0

    stale_a = reg_a.stale_clients(rnd, fresh)
    for c in stale_a:
        feats, labels, valid = data.client_data(c)
        s, _, dt = timed_summary("encoder", feats, labels, valid,
                                 spec.num_classes, encoder_fn=enc_fn,
                                 coreset_k=16, bins=8,
                                 key=jax.random.PRNGKey(1000 + c))
        reg_a.update(c, rnd, s, fresh[c])

    engine = BatchedSummaryEngine("encoder", spec.num_classes,
                                  encoder_fn=enc_fn, coreset_k=16, bins=8)
    stale_b = reg_b.stale_clients(rnd, fresh)
    assert stale_b == stale_a
    for c, res in engine.summarize(_items(data)).items():
        reg_b.update(c, rnd, res.summary, fresh[c])

    assert reg_b.refresh_count == reg_a.refresh_count
    np.testing.assert_array_equal(reg_b.last_refresh, reg_a.last_refresh)
    np.testing.assert_allclose(reg_b.matrix(), reg_a.matrix(), atol=1e-5)
    # neither registry considers anyone stale right after the refresh
    assert reg_b.stale_clients(rnd + 1, fresh) == []


@pytest.mark.parametrize("fn,extra", [
    (batched_pxy_histogram, {"bins": 4}),
    (batched_per_label_mean, {}),
])
def test_label_offset_kernel_paths_match(rs, fn, extra):
    """The Pallas-backed batched path (one kernel launch over M*C offset
    classes) matches the vmapped pure-jnp formulation."""
    m, n, d, C = 3, 16, 12, 5
    labels = jnp.asarray(rs.randint(0, C, (m, n)), jnp.int32)
    valid = jnp.asarray(rs.rand(m, n) > 0.2)
    x = rs.rand(m, n, d) if fn is batched_pxy_histogram \
        else rs.randn(m, n, d)
    x = jnp.asarray(x, jnp.float32)
    ref = fn(x, labels, valid, C, use_kernel=False, **extra)
    ker = fn(x, labels, valid, C, use_kernel=True, interpret=True,
             **extra)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


def test_lazy_summarize_clients_matches_eager(data, enc_fn):
    """The memory-bounded loader path (used by fl/rounds.py) produces the
    same results and dispatch accounting as the eager items path."""
    spec = data.spec
    kw = dict(encoder_fn=enc_fn, coreset_k=16, bins=8)
    eager = BatchedSummaryEngine("encoder", spec.num_classes, **kw)
    lazy = BatchedSummaryEngine("encoder", spec.num_classes, **kw)
    res_a = eager.summarize(_items(data))
    res_b = lazy.summarize_clients(
        range(spec.num_clients), data.sizes,
        lambda c: data.client_data(c),
        lambda c: 1000 + c)
    assert lazy.stats.dispatches == eager.stats.dispatches
    assert set(res_b) == set(res_a)
    for c in res_a:
        np.testing.assert_allclose(res_b[c].summary, res_a[c].summary,
                                   atol=1e-5)


def test_max_batch_chunks_dispatches():
    spec = small_spec(num_clients=12, num_classes=4, side=6, avg_samples=16)
    data = FederatedDataset(spec, seed=3)
    engine = BatchedSummaryEngine("py", spec.num_classes, max_batch=2)
    engine.summarize(_items(data))
    assert engine.stats.clients == 12
    assert engine.stats.dispatches >= 6     # ceil(group/2) per bucket


@pytest.mark.parametrize("seed", [0, 5_000_011, 1_300 * 100_003 + 2_799,
                                  2**31 - 1, -1, 2**31])
def test_dispatch_keys_are_prng_key_of_seed(data, enc_fn, seed):
    """The key the dispatch makes from a client's int32 seed is
    ``jax.random.PRNGKey(seed)`` bit for bit over ``[0, 2**31)``, so the
    encoder coreset is the per-client path's; a seed outside that range
    raises instead of wrapping."""
    c = int(np.argmax(data.sizes))          # the most samples: k < n
    feats, labels, valid = data.client_data(c)
    engine = BatchedSummaryEngine("encoder", data.spec.num_classes,
                                  encoder_fn=enc_fn, coreset_k=16, bins=8)
    if not 0 <= seed < 2**31:
        with pytest.raises(ValueError, match="outside"):
            engine.summarize([(c, feats, labels, valid, seed)])
        with pytest.raises(ValueError, match="outside"):
            engine.summarize_clients([c], data.sizes, data.client_data,
                                     lambda _c: seed)
        assert engine.stats.dispatches == 0
        return
    want = np.asarray(jax.random.PRNGKey(seed))
    # the dispatch's key step, on the int32 array it is handed (padded 0s)
    seeds = np.array([seed, 0], np.int32)
    got = np.asarray(jax.jit(seed_keys)(seeds))
    np.testing.assert_array_equal(got[0], want)
    res = engine.summarize_clients([c], data.sizes, data.client_data,
                                   lambda _c: seed)[c]
    s, _, _ = timed_summary("encoder", feats, labels, valid,
                            data.spec.num_classes, encoder_fn=enc_fn,
                            coreset_k=16, bins=8,
                            key=jax.random.PRNGKey(seed))
    np.testing.assert_allclose(res.summary, s, atol=1e-5)
    # the coreset depends on the key: another seed picks another one
    other = engine.summarize([(c, feats, labels, valid, seed ^ 1)])[c]
    assert np.max(np.abs(other.summary - s)) > 1e-3


def _guard_load(monkeypatch):
    """Disallow every host<->device transfer inside ``summary/load``."""
    real = obs.span

    @contextlib.contextmanager
    def span(name, *args, **kw):
        with real(name, *args, **kw) as sp:
            if name != "summary/load":
                yield sp
                return
            with jax.transfer_guard("disallow"):
                yield sp

    monkeypatch.setattr(obs, "span", span)


def test_load_makes_no_device_round_trip(data, enc_fn, monkeypatch):
    """The engine's load loop reads client data and integer seeds and
    touches no device: no key is made on the device and read back per
    client.  The guard is live: a device round trip in the load trips it."""
    spec = data.spec
    ids = list(range(spec.num_clients))
    engine = BatchedSummaryEngine("encoder", spec.num_classes,
                                  encoder_fn=enc_fn, coreset_k=16, bins=8)
    engine.summarize_clients(ids, data.sizes, data.client_data,
                             lambda c: 1000 + c)          # compiles, unguarded
    _guard_load(monkeypatch)
    results = engine.summarize_clients(ids, data.sizes, data.client_data,
                                       lambda c: 1000 + c)
    assert set(results) == set(ids)

    def load_with_key(c):          # what a per-client PRNGKey readback does
        np.asarray(jax.random.PRNGKey(c))
        return data.client_data(c)

    with pytest.raises(Exception, match="Disallowed"):
        engine.summarize_clients(ids, data.sizes, load_with_key,
                                 lambda c: 1000 + c)


# ---------------------------------------------------------------------------
# the engine's spans under a profiler session: copies, padding, nesting


def _profiled(fn, trace_dir):
    """Run ``fn`` under a profiler session; the spans it recorded."""
    n0 = len(obs.profiled().events)
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return obs.profiled().events[n0:]


@pytest.mark.parametrize("max_batch", [3, 256])
def test_profiled_rounds_count_copies_and_padding(data, max_batch, tmp_path):
    """Two summary rounds under a profiler: every ``summary/put`` carries
    the bytes of its padded batch, ``summary/assemble`` its slots and
    real samples, as reckoned here from the bucket arithmetic, and the
    engine's spans nest inside the caller's."""
    spec = data.spec
    engine = BatchedSummaryEngine("py", spec.num_classes, max_batch=max_batch)
    ids = list(range(spec.num_clients))
    sample_bytes = int(np.prod(spec.feature_shape)) * 4 + 4 + 1  # f32, i32, bool

    def two_rounds():
        for rnd in range(2):
            with obs.span("client_summaries", round=rnd):
                engine.summarize_clients(
                    ids, data.sizes, data.client_data,
                    lambda c: rnd * 1000 + c)

    events = _profiled(two_rounds, tmp_path)
    by_bucket: dict = {}
    for c in ids:
        by_bucket.setdefault(bucket_size(int(data.sizes[c])), []).append(c)
    want_bytes = want_slots = want_puts = 0
    for b, cids in by_bucket.items():
        for lo in range(0, len(cids), max_batch):
            mp = bucket_size(len(cids[lo:lo + max_batch]), base=1)
            want_bytes += mp * b * sample_bytes + mp * 4  # + int32 seeds
            want_slots += mp * b
            want_puts += 1

    def named(name):
        return [e for e in events if e["name"] == name]

    puts = named("summary/put")
    assert len(puts) == 2 * want_puts == len(named("summary/execute"))
    assert sum(e["args"]["bytes"] for e in puts) == 2 * want_bytes
    assemble = named("summary/assemble")
    assert sum(e["args"]["slots"] for e in assemble) == 2 * want_slots
    assert sum(e["args"]["filled"] for e in assemble) == \
        2 * int(data.sizes.sum())
    assert len(named("summary/load")) == 2 * want_puts
    parents = named("client_summaries")
    assert len(parents) == 2
    for e in events:
        if e["name"].startswith("summary/"):
            assert any(p["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= p["ts"] + p["dur"]
                       for p in parents), e["name"]
