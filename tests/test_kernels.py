"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("shape", [(17,), (1024,), (257, 3), (8, 128),
                                   (1000, 33), (2, 3, 5, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sqdiff_norm_sweep(shape, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, shape).astype(dtype)
    y = jax.random.normal(k2, shape).astype(dtype)
    got = ops.sqdiff_norm(x, y)
    want = ref.sqdiff_norm_ref(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3 if dtype == jnp.bfloat16 else 1e-5)


@given(n=st.integers(1, 5000))
@settings(max_examples=20, deadline=None)
def test_sqdiff_norm_property(n):
    x = jnp.arange(n, dtype=jnp.float32) / n
    got = float(ops.sqdiff_norm(x, jnp.zeros_like(x)))
    want = float(jnp.sum(x * x))
    assert abs(got - want) <= 1e-4 * max(want, 1.0)


@pytest.mark.parametrize("n", [2 * 32768 + 1000, 3 * 32768])
def test_flat_kernels_span_blocks(n):
    """Several 32k-element blocks, the last one ragged or whole: the flat
    kernels mask the ragged end out of their sums and drop its writes."""
    ks = jax.random.split(KEY, 4)
    x, y = (jax.random.normal(k, (n,)) for k in ks[:2])
    np.testing.assert_allclose(float(ops.sqdiff_norm(x, y)),
                               float(ref.sqdiff_norm_ref(x, y)), rtol=1e-5)
    for a, b in zip(ops.fused_stats(x, y), ref.fused_stats_ref(x, y)):
        np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    m = jax.random.normal(ks[2], (n,))
    v = jnp.abs(jax.random.normal(ks[3], (n,)))
    kw = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              c1=0.7, c2=0.4, clip_scale=0.37)
    got = ops.fused_adamw_stats(x, y, m, v, **kw)
    want = ref.adamw_stats_ref(x, y, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(100,), (1024,), (31, 67)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_adamw_sweep(shape, dtype):
    keys = jax.random.split(KEY, 4)
    p = jax.random.normal(keys[0], shape).astype(dtype)
    g = jax.random.normal(keys[1], shape).astype(dtype)
    m = jax.random.normal(keys[2], shape).astype(jnp.float32)
    v = jnp.abs(jax.random.normal(keys[3], shape)).astype(jnp.float32)
    kw = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              c1=0.7, c2=0.4)
    got = ops.fused_adamw(p, g, m, v, **kw)
    want = ref.adamw_ref(p, g, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,d", [(1, 128), (37, 256), (200, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (rows, d)).astype(dtype)
    s = jax.random.normal(k2, (d,)).astype(dtype)
    got = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("b,t,h,kvh,d,causal,window,softcap", [
    (2, 256, 4, 2, 64, True, 0, 0.0),
    (1, 512, 4, 4, 64, True, 128, 0.0),
    (2, 256, 8, 2, 32, True, 0, 50.0),       # gemma2-style softcap
    (1, 256, 2, 2, 64, False, 0, 0.0),        # encoder (bidirectional)
    (1, 384, 4, 1, 64, True, 256, 30.0),      # MQA + window + cap
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, t, h, kvh, d, causal, window, softcap, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, t, h, d)).astype(dtype)
    k = jax.random.normal(k2, (b, t, kvh, d)).astype(dtype)
    v = jax.random.normal(k3, (b, t, kvh, d)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=128, block_kv=128)
    kx = jnp.repeat(k, h // kvh, axis=2)
    vx = jnp.repeat(v, h // kvh, axis=2)
    want = ref.attention_ref(q, kx, vx, causal=causal, window=window,
                             softcap=softcap)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_attention_matches_model_attention():
    """Kernel must agree with the model's own attention math end to end."""
    from repro.models.attention import _sdpa, causal_mask
    k1, k2, k3 = jax.random.split(KEY, 3)
    b, t, h, kvh, d = 2, 256, 8, 4, 64
    q = jax.random.normal(k1, (b, t, h, d))
    k = jax.random.normal(k2, (b, t, kvh, d))
    v = jax.random.normal(k3, (b, t, kvh, d))
    want = _sdpa(q, k, v, causal_mask(t, t), 0.0)
    got = ops.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
