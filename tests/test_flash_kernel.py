"""Pallas flash-attention kernel vs the attention oracle (interpret mode),
plus banded-attention equivalence for sliding-window layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_kernel
from repro.models.attention import _attend_blockwise, attend


@pytest.mark.parametrize("B,H,KV,S,D,causal,window", [
    (2, 4, 2, 256, 64, True, 0),
    (1, 4, 1, 512, 32, True, 100),
    (2, 2, 2, 256, 64, False, 0),
    (1, 8, 4, 128, 16, True, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_matches_oracle(rs, B, H, KV, S, D, causal, window,
                                     dtype):
    q = jnp.asarray(rs.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rs.normal(size=(B, S, KV, D)), dtype)
    v = jnp.asarray(rs.normal(size=(B, S, KV, D)), dtype)
    pos = jnp.arange(S)
    want = attend(q, k, v, pos, pos, causal=causal, window=window)
    got = flash_attention_kernel(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        bq=64, bk=64, interpret=True).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_banded_equals_full_scan_windowed(rs):
    """banded=True must be numerically identical for window layers."""
    B, S, KV, G, D = 1, 4096, 2, 2, 16
    q = jnp.asarray(rs.normal(size=(B, S, KV, G, D)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(B, S, KV, D)), jnp.float32)
    pos = jnp.arange(S)
    kw = dict(causal=True, window=512, scale=D ** -0.5, q_block=1024,
              kv_block=512)
    full = _attend_blockwise(q, k, v, pos, pos, banded=False, **kw)
    band = _attend_blockwise(q, k, v, pos, pos, banded=True, **kw)
    np.testing.assert_allclose(np.asarray(band), np.asarray(full),
                               atol=2e-5, rtol=1e-4)


def test_banded_gradients_match(rs):
    B, S, KV, G, D = 1, 2048 * 2, 1, 2, 16
    q = jnp.asarray(rs.normal(size=(B, S, KV, G, D)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(B, S, KV, D)), jnp.float32)
    pos = jnp.arange(S)
    kw = dict(causal=True, window=300, scale=D ** -0.5, q_block=1024,
              kv_block=512)

    def loss(banded):
        def f(q, k, v):
            return jnp.sum(jnp.sin(_attend_blockwise(
                q, k, v, pos, pos, banded=banded, **kw)))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_full = loss(False)
    g_band = loss(True)
    for a, b in zip(g_full, g_band):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5,
                                   rtol=1e-4)
