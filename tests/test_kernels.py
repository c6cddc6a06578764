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


FLASH_CASES = [
    (2, 256, 4, 2, 64, True, 0, 0.0),
    (1, 512, 4, 4, 64, True, 128, 0.0),
    (2, 256, 8, 2, 32, True, 0, 50.0),       # gemma2-style softcap
    (1, 256, 2, 2, 64, False, 0, 0.0),        # encoder (bidirectional)
    (1, 384, 4, 1, 64, True, 256, 30.0),      # MQA + window + cap
]
FLASH_ARGS = "b,t,h,kvh,d,causal,window,softcap"
FLASH_TOL = {jnp.float32: 3e-5, jnp.bfloat16: 3e-2}


def _qkv(b, t, h, kvh, d, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    return (jax.random.normal(k1, (b, t, h, d)).astype(dtype),
            jax.random.normal(k2, (b, t, kvh, d)).astype(dtype),
            jax.random.normal(k3, (b, t, kvh, d)).astype(dtype))


def _assert_close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize(FLASH_ARGS, FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, t, h, kvh, d, causal, window, softcap, dtype):
    q, k, v = _qkv(b, t, h, kvh, d, dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=128, block_kv=128)
    kx = jnp.repeat(k, h // kvh, axis=2)
    vx = jnp.repeat(v, h // kvh, axis=2)
    want = ref.attention_ref(q, kx, vx, causal=causal, window=window,
                             softcap=softcap)
    _assert_close(got, want, FLASH_TOL[dtype])


def _sdpa_core(q, k, v, causal, window, softcap):
    from repro.models.attention import _sdpa, causal_mask
    t = q.shape[1]
    mask = causal_mask(t, t, 0, window) if causal else None
    return _sdpa(q, k, v, mask, softcap)


@pytest.mark.parametrize(FLASH_ARGS, FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_sweep(b, t, h, kvh, d, causal, window, softcap,
                                    dtype):
    """The kernels' backward (dK/dV summed over each kv head's query
    group, dQ) against autodiff through the model's jnp `_sdpa`."""
    q, k, v = _qkv(b, t, h, kvh, d, dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape).astype(dtype)

    def loss(core):
        return lambda q, k, v: jnp.sum(
            core(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    got = jax.grad(loss(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, block_q=128,
        block_kv=128)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _sdpa_core(
        q, k, v, causal, window, softcap)), (0, 1, 2))(q, k, v)
    for g, x in zip(got, (q, k, v)):
        assert g.dtype == x.dtype and g.shape == x.shape
    for a, b_ in zip(got, want):
        _assert_close(a, b_, FLASH_TOL[dtype])


@pytest.mark.parametrize(FLASH_ARGS, FLASH_CASES)
def test_flash_attention_lse_residual(b, t, h, kvh, d, causal, window,
                                      softcap):
    """The forward's logsumexp residual is the log-sum-exp of the scaled,
    capped, masked scores."""
    from repro.kernels import flash_attention as fa
    from repro.models.attention import causal_mask
    q, k, v = _qkv(b, t, h, kvh, d, jnp.float32)
    qf, kf, vf, c = fa._flat(q, k, v, causal, window, softcap, 128, 128,
                             None)
    _, lse = fa._forward(*fa._operands(qf, kf, vf, c), c)
    got = lse.reshape(b, h, t)
    kx = jnp.repeat(k, h // kvh, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, kx) / jnp.sqrt(d)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    if causal:
        logits = jnp.where(causal_mask(t, t, 0, window), logits, -jnp.inf)
    _assert_close(got, jax.nn.logsumexp(logits, axis=-1),
                  FLASH_TOL[jnp.float32])


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


def _attend_case(t=256, h=4, kvh=2, d=32):
    from repro.models.attention import init_attention
    params = init_attention(jax.random.PRNGKey(1), 64, h, kvh, d, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, 64))
    return params, x, jnp.arange(t)[None, :]


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 128, 30.0), (False, 0, 0.0)])
def test_attend_full_dispatch(monkeypatch, causal, window, softcap):
    """`attend_full` takes the flash kernels where the backend is TPU
    (here in interpret mode) and the jnp path on CPU, with the same
    values and gradients; `flat_dispatch_info` reports which."""
    from repro.models.attention import attend_full
    params, x, pos = _attend_case()

    def run():
        def loss(params, x):      # new per run: traces are cached by function
            out = attend_full(params, x, pos, rope_theta=1e4, softcap=softcap,
                              window=window, causal=causal)
            return jnp.sum(out * jnp.cos(out))
        jaxpr = str(jax.make_jaxpr(loss)(params, x))
        val, grads = jax.value_and_grad(loss, (0, 1))(params, x)
        return "pallas_call" in jaxpr, val, grads

    assert ops.flat_dispatch_info()["attention"] == "jnp"
    on_jnp, val_j, grads_j = run()
    monkeypatch.setattr(ops, "_backend_is_tpu", lambda: True)
    assert ops.flat_dispatch_info()["attention"] == "pallas-flash"
    on_kernel, val_k, grads_k = run()
    assert on_kernel and not on_jnp
    _assert_close(val_k, val_j, 1e-4)
    for a, b in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_j)):
        _assert_close(a, b, FLASH_TOL[jnp.float32])


@pytest.mark.parametrize("t,s,block", [
    (2048, 2048, 1024), (1536, 1536, 512), (384, 384, 128), (640, 640, 128),
    (200, 200, None), (256, 512, None)])
def test_flash_block(t, s, block):
    """The kernels take self-attention whose length a block divides."""
    assert ops.flash_block(t, s) == block


def test_attention_core_on_a_mesh(subproc):
    """On a 2x2 (data, model) mesh of Auto axes the kernels run on each
    device's batch and head shard (GSPMD cannot partition a Mosaic call)
    and agree with the jnp path."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.kernels import ops
ks = jax.random.split(jax.random.PRNGKey(0), 4)
q = jax.random.normal(ks[0], (2, 256, 4, 32))
k, v = (jax.random.normal(kk, (2, 256, 2, 32)) for kk in ks[1:3])
w = jax.random.normal(ks[3], q.shape)
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
def loss(q, k, v):
    out = ops.attention_core(q, k, v, causal=True, window=0, softcap=0.0)
    return jnp.sum(out * w)
f = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))
with jax.set_mesh(mesh):
    want = f(q, k, v)
    ops._backend_is_tpu = lambda: True
    g = jax.jit(jax.value_and_grad(lambda *a: loss(*a), (0, 1, 2)))
    jaxpr = str(jax.make_jaxpr(g)(q, k, v))
    got = g(q, k, v)
assert "pallas_call" in jaxpr and "shard_map" in jaxpr, jaxpr
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                               atol=3e-5)
print("OK")
""", devices=4)
    assert "OK" in out


# MLA's widths (query/key 192 = 128 + 64, value 128) scaled down, and an
# explicit softmax scale (YaRN's factor on 1/sqrt of the query width)
MLA_CASES = [(1, 256, 2, 48, 32, 0.3), (2, 256, 4, 24, 16, 1.59 / 24 ** 0.5)]


def _mla_qkv(b, t, h, dqk, dv):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    return (jax.random.normal(k1, (b, t, h, dqk)),
            jax.random.normal(k2, (b, t, h, dqk)),
            jax.random.normal(k3, (b, t, h, dv)),
            jax.random.normal(k4, (b, t, h, dv)))


@pytest.mark.parametrize("b,t,h,dqk,dv,scale", MLA_CASES)
def test_flash_attention_value_width_and_scale(b, t, h, dqk, dv, scale):
    """The kernels at a value width unequal to the query/key width and an
    explicit scale, forward and the three gradients, against the jnp path
    (`sdpa_self`, the CPU path of `attention_core`)."""
    from repro.models.attention import sdpa_self
    q, k, v, w = _mla_qkv(b, t, h, dqk, dv)

    def loss(core):
        return lambda q, k, v: jnp.sum(core(q, k, v) * w)

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, scale=scale,
                                   block_q=128, block_kv=128)

    def jnp_core(q, k, v):
        return sdpa_self(q, k, v, causal=True, window=0, softcap=0.0,
                         scale=scale)

    out = flash(q, k, v)
    assert out.shape == (b, t, h, dv)
    _assert_close(out, jnp_core(q, k, v), FLASH_TOL[jnp.float32])
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(jnp_core), (0, 1, 2))(q, k, v)
    for g, x in zip(got, (q, k, v)):
        assert g.shape == x.shape
    for a, b_ in zip(got, want):
        _assert_close(a, b_, FLASH_TOL[jnp.float32])


def test_flash_attention_default_scale_is_unchanged():
    """Equal widths and no scale: the same configuration as before the
    value width and scale became arguments, so the equal-width callers
    (every dense model's attention) lower to the same kernels."""
    from repro.kernels import flash_attention as fa
    q, k, v = _qkv(1, 256, 4, 2, 64, jnp.float32)
    *_, c = fa._flat(q, k, v, True, 0, 0.0, 128, 128, None)
    *_, c_explicit = fa._flat(q, k, v, True, 0, 0.0, 128, 128, None,
                              1.0 / 64 ** 0.5)
    assert c == c_explicit and c.scale == 1.0 / 8.0


@pytest.mark.parametrize("sizes", [(50, 0, 70, 42), (192, 0, 0, 0),
                                   (0, 0, 0, 0), (0, 0, 130, 30)])
def test_grouped_matmul_kernels_match_ragged_dot(sizes):
    """The megablox kernels (interpret mode; operands rounded to bf16 as on
    the MXU) against `jax.lax.ragged_dot` over the same bf16-rounded
    operands, forward and both gradients, rows past the groups masked as
    the expert layer masks them."""
    from repro.kernels.grouped_matmul import grouped_matmul
    k1, k2, k3 = jax.random.split(KEY, 3)
    m, kk, n = 192, 128, 200
    x = jax.random.normal(k1, (m, kk)).astype(jnp.bfloat16).astype(
        jnp.float32)
    w = jax.random.normal(k2, (4, kk, n)).astype(jnp.bfloat16).astype(
        jnp.float32)
    c = jax.random.normal(k3, (m, n))
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(m) < sum(sizes))[:, None]

    def loss(mm):
        return lambda x, w: jnp.sum(
            jnp.where(valid, mm(jnp.where(valid, x, 0), w), 0) * c)

    kern = loss(lambda x, w: grouped_matmul(x, w, gs, interpret=True))
    want = loss(lambda x, w: jax.lax.ragged_dot(x, w, gs))
    _assert_close(kern(x, w), want(x, w), 1e-3 * (1 + abs(float(want(x, w)))))
    for a, b_ in zip(jax.grad(kern, (0, 1))(x, w),
                     jax.grad(want, (0, 1))(x, w)):
        # the cotangent enters the MXU as bf16 too
        _assert_close(a, b_, 0.05 * (1 + float(jnp.max(jnp.abs(b_)))))


def test_grouped_matmul_dispatch(monkeypatch):
    """ragged_dot on the CPU; the megablox kernels where the backend is
    TPU; `flat_dispatch_info` reports which."""
    x = jnp.ones((128, 128))
    w = jnp.ones((2, 128, 128))
    gs = jnp.asarray([100, 28], jnp.int32)
    assert ops.flat_dispatch_info()["grouped_matmul"] == "ragged-dot"
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x, w: ops.grouped_matmul(x, w, gs))(x, w))
    monkeypatch.setattr(ops, "_backend_is_tpu", lambda: True)
    assert ops.flat_dispatch_info()["grouped_matmul"] == "pallas-megablox"
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda x, w: ops.grouped_matmul(x, w, gs))(x, w))
