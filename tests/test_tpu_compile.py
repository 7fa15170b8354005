"""Compile the main path's kernels at real widths for a described TPU v5e.

Nothing runs: each test lowers a kernel (or a whole jitted stage) for a
chip described by ``get_topology_desc`` and compiles it with the TPU
compiler installed next to JAX, which refuses what the chip would refuse —
a block that does not tile, more VMEM than a kernel may take.  Widths are
the FEMNIST-shaped fleet's (C = 62 classes, 28×28×1 images, an encoder of
H = 32, coreset k = 64, P(X|y) with D = 784 and B = 8 bins).

The topology is described inside a fixture and nowhere else: only one
process may load the TPU library, so a description made at import time
would break every other test worker.  Keep every such compile in this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.batched_summary import BatchedSummaryEngine
from repro.core.kmeans import kmeans
from repro.kernels import ops
from repro.kernels.sketch_update import cm_hash_params
from repro.models.cnn import CNNConfig, build_cnn, cnn_apply
from repro.stream.cluster import _assign_fn
from repro.stream.registry import _drift_scan

C, H, K_CORESET = 62, 32, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_seg_mean_label_offset(one_chip):
    m = 256                                   # the encoder engine's max_batch
    n = m * K_CORESET
    text = _compile(lambda f, l, k: ops.seg_mean(f, l, k, m * C),
                    _shape(one_chip, (n, H), jnp.float32),
                    _shape(one_chip, (n,), jnp.int32),
                    _shape(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_class_hist_label_offset(one_chip):
    m, bucket, d, bins = 16, 512, 28 * 28, 8  # the pxy engine's max_batch
    n = m * bucket
    text = _compile(lambda q, l, v: ops.class_hist(q, l, v, m * C, bins),
                    _shape(one_chip, (n, d), jnp.int32),
                    _shape(one_chip, (n,), jnp.int32),
                    _shape(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_pairwise_dist(one_chip):
    n, k, d = 2800, 8, C * H + C
    text = _compile(ops.pairwise_dist,
                    _shape(one_chip, (n, d), jnp.float32),
                    _shape(one_chip, (k, d), jnp.float32))
    assert "tpu_custom_call" in text


def test_sketch_update(one_chip):
    m, rows, width = 256, 4, 128
    n = m * 128
    a, b = cm_hash_params(rows)
    text = _compile(
        lambda l, s, v: ops.sketch_update(l, s, v, m, width, a, b),
        _shape(one_chip, (n,), jnp.int32), _shape(one_chip, (n,), jnp.int32),
        _shape(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_encoder_summary_dispatch(one_chip):
    """The batched engine's whole jitted dispatch, built as the round loop
    builds it on the chip (kernels on)."""
    enc = build_cnn(CNNConfig(in_channels=1, feature_dim=H),
                    jax.random.PRNGKey(7))
    engine = BatchedSummaryEngine(
        "encoder", C, encoder_fn=jax.jit(lambda x: cnn_apply(enc, x)),
        coreset_k=K_CORESET, bins=8, use_kernel=True, encoder_dim=H)
    m, bucket = engine.max_batch, 128
    assert m == 256
    text = engine._fn.lower(
        _shape(one_chip, (m, bucket, 28, 28, 1), jnp.float32),
        _shape(one_chip, (m, bucket), jnp.int32),
        _shape(one_chip, (m, bucket), jnp.bool_),
        _shape(one_chip, (m,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_kmeans_recluster(one_chip):
    """Full Lloyd over the whole fleet's summaries (the online maintainer's
    full fit), distances through the kernel."""
    n, d = 2800, C * H + C
    text = _compile(lambda x, key: kmeans(x, 8, key, use_kernel=True),
                    _shape(one_chip, (n, d), jnp.float32),
                    _shape(one_chip, (2,), jnp.uint32))
    assert "tpu_custom_call" in text


def test_online_assign(one_chip):
    n, d = 4096, C * H + C                    # bucketed drifted rows
    text = _compile(lambda x, c: _assign_fn(x, c, True),
                    _shape(one_chip, (n, d), jnp.float32),
                    _shape(one_chip, (8, d), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_dev", [1, 4])
def test_drift_scan(topo, n_dev):
    rows = 131072
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("fleet",))
    sharding = NamedSharding(mesh, P("fleet"))
    arena = jax.ShapeDtypeStruct((rows, C), jnp.float32, sharding=sharding)
    compiled = _drift_scan(mesh, rows, C).lower(arena, arena).compile()
    assert compiled.as_text()
