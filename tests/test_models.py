"""Per-architecture smoke tests: reduced variants, one forward/train step on
CPU, shapes + finiteness; decode == full-forward equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, get_smoke_config, get_config
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, init_adamw, adamw_update

KEY = jax.random.PRNGKey(0)


def make_smoke_batch(cfg, b=2, t=32):
    text_len = t - (cfg.frontend.num_prefix_tokens
                    if cfg.frontend.kind == "vision_stub" else 0)
    batch = {
        "tokens": jax.random.randint(KEY, (b, text_len), 0, cfg.vocab_size),
        "labels": jax.random.randint(KEY, (b, text_len), 0, cfg.vocab_size),
    }
    if cfg.frontend.kind == "vision_stub":
        batch["patch_embeds"] = jnp.ones(
            (b, cfg.frontend.num_prefix_tokens, cfg.d_model), cfg.act_dtype)
    if cfg.frontend.kind == "audio_stub":
        batch["frames"] = jnp.ones(
            (b, cfg.encoder.num_frames, cfg.d_model), cfg.act_dtype)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = make_smoke_batch(cfg)
    loss, metrics = model.loss(params, batch)
    assert jnp.isfinite(loss), arch
    logits = model.logits(params, batch)
    assert logits.shape == (*batch["tokens"].shape, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits))
    # one full train step: grads finite, params change, loss finite after
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for leaf in jax.tree.leaves(grads):
        assert jnp.all(jnp.isfinite(leaf)), arch
    opt = init_adamw(params)
    new_params, _, gnorm = adamw_update(params, grads, opt, AdamWConfig(), 1e-3)
    assert float(gnorm) > 0
    loss2, _ = model.loss(new_params, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if get_smoke_config(a).frontend.kind == "none"])
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    b, t = 2, 16
    tokens = jax.random.randint(KEY, (b, t), 0, cfg.vocab_size)
    full = model.logits(params, {"tokens": tokens, "labels": tokens})
    cache = model.init_cache(b, t)
    step = jax.jit(model.decode_step)
    outs = []
    for i in range(t):
        lg, cache = step(params, cache, tokens[:, i], jnp.int32(i))
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_ring_decode_long_context_mode(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    cache = model.init_cache(2, 8, ring=True)
    tok = jnp.zeros((2,), jnp.int32)
    if cfg.frontend.kind == "audio_stub":
        cache["cross_prefix"] = [jax.tree.map(jnp.ones_like, c)
                                 for c in cache["cross_prefix"]]
        cache["cross_scanned"] = [jax.tree.map(jnp.ones_like, c)
                                  for c in cache["cross_scanned"]]
    # position far beyond the ring length must still be finite
    lg, cache = model.decode_step(params, cache, tok, jnp.int32(37), ring=True)
    assert jnp.all(jnp.isfinite(lg)), arch


def test_scan_vs_unroll_identical():
    cfg = get_smoke_config("gemma2-27b")
    model_scan = build_model(cfg.replace(scan_layers=True, num_layers=4))
    model_unroll = build_model(cfg.replace(scan_layers=False, num_layers=4))
    params = model_scan.init(KEY)
    batch = make_smoke_batch(cfg)
    l1, _ = model_scan.loss(params, batch)
    l2, _ = model_unroll.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_chunked_attention_matches_direct():
    """The flash-style jnp q-chunked path must equal direct attention."""
    from repro.models import attention as A
    k1, k2, k3 = jax.random.split(KEY, 3)
    b, t, h, kvh, d = 2, 2048, 4, 2, 32
    q = jax.random.normal(k1, (b, t, h, d))
    k = jax.random.normal(k2, (b, t, kvh, d))
    v = jax.random.normal(k3, (b, t, kvh, d))
    out_chunk = A._sdpa_chunked(q, k, v, softcap=0.0, causal=True, window=0)
    mask = A.causal_mask(t, t)
    out_direct = A._sdpa(q, k, v, mask, 0.0)
    np.testing.assert_allclose(np.asarray(out_chunk), np.asarray(out_direct),
                               rtol=2e-5, atol=2e-5)


def test_full_config_param_counts():
    """Full (non-smoke) configs must match their model cards (DESIGN §4)."""
    expect = {
        "dbrx-132b": (131.6e9, 0.02),
        "deepseek-v2-236b": (235.6e9, 0.02),
        "gemma2-27b": (27.2e9, 0.02),
        "nemotron-4-15b": (15.6e9, 0.05),
        "phi3-mini-3.8b": (3.8e9, 0.05),
        "recurrentgemma-9b": (9.4e9, 0.05),
        "mamba2-370m": (0.37e9, 0.05),
        "llama3.2-1b": (1.24e9, 0.02),
        "whisper-base": (0.072e9, 0.1),
    }
    for arch, (want, tol) in expect.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < tol, (arch, got, want)


def test_moe_aux_loss_and_balance():
    from repro.models.moe import init_moe, moe_apply
    from repro.models.config import MoEConfig
    m = MoEConfig(num_experts=4, top_k=2, d_expert=32)
    p = init_moe(jax.random.PRNGKey(1), 16, m, "swiglu", jnp.float32)
    x = jax.random.normal(KEY, (2, 8, 16))
    out, stats = moe_apply(p, x, m)
    assert out.shape == x.shape
    # the sequence-level balance loss is >= coef * 1.0, its value at balance
    assert float(stats.aux) >= m.router_aux_coef * (1 - 1e-6)
    assert float(stats.rows) == 2 * 8 * 2


def _moe_dense(p, x, m):
    """Plain per-token form of the expert layer: every held expert applied
    to every token, weighted by its gate (0 where not among the token's
    top k), plus the shared experts."""
    from repro.models import moe
    probs, experts, gates = moe.route(p, x, m)
    full = jnp.sum(jax.nn.one_hot(experts, m.num_experts) * gates[..., None],
                   axis=-2)                                  # (b, t, E)
    y = moe._shared(p, x) if "shared" in p else jnp.zeros_like(x)
    for e in range(m.held):
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        y = y + full[..., e:e + 1] * (h @ p["w_down"][e])
    return y


def _moe_case(m, d=16, key=1):
    from repro.models.moe import init_moe
    p = init_moe(jax.random.PRNGKey(key), d, m, "swiglu", jnp.float32)
    x = jax.random.normal(KEY, (2, 24, d))
    return p, x


def test_dropless_moe_keeps_every_token_when_all_route_to_one_expert():
    """Every token's top k holds expert 0: all 48 tokens go to it, where
    the old capacity (1.25·n·k/E = 30) would have dropped 18, and the
    layer equals its plain per-token form."""
    from repro.models.config import MoEConfig
    from repro.models.moe import moe_apply
    m = MoEConfig(num_experts=4, top_k=2, d_expert=32, num_shared_experts=1,
                  shared_d_expert=8)
    p, x = _moe_case(m)
    x = jnp.abs(x)
    p["router"] = p["router"].at[:, 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, stats = moe_apply(p, x, m)
        want = _moe_dense(p, x, m)
    assert float(stats.rows) == 2 * 24 * 2
    # expert 0 holds all 48 of the 96 pairs: twice the mean of 24
    assert float(stats.load_max) == 2.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("normalize", [False, True])
def test_moe_gates_normalised_only_where_the_config_says(normalize):
    from repro.models import moe
    from repro.models.config import MoEConfig
    m = MoEConfig(num_experts=8, top_k=3, d_expert=16,
                  normalize_gates=normalize, routed_scaling_factor=2.0)
    p, x = _moe_case(m)
    probs, experts, gates = moe.route(p, x, m)
    top = jnp.take_along_axis(probs, experts, axis=-1)
    if normalize:
        want = 2.0 * top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        want = 2.0 * top          # DeepSeek-V2: the router's probabilities
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want),
                               rtol=1e-6)
    assert bool(jnp.all(jnp.sum(gates, -1) < 2.0)) != normalize


def test_moe_expert_stacks_stay_split_over_the_model_axis(subproc):
    """On a 2x2 (data, model) mesh of Auto axes each device holds its half
    of the expert stacks and of the shared width (as `param_pspecs` lays
    them out) and nothing gathers them; the layer, its counters and its
    gradients equal the unsharded layer's."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from repro.distributed.params import param_pspecs
from repro.models.config import MoEConfig
from repro.models.moe import init_moe, moe_apply
m = MoEConfig(num_experts=8, top_k=3, d_expert=16, num_shared_experts=2,
              shared_d_expert=8, normalize_gates=False)
p = init_moe(jax.random.PRNGKey(1), 32, m, "swiglu", jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 32))
w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
def loss(p, x):
    y, st = moe_apply(p, x, m)
    return jnp.sum(y * w) + st.aux, st
f = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))
with jax.default_matmul_precision("highest"):
    want = f(p, x)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        ps = jax.device_put(p, jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_pspecs(p, mesh)))
        assert ps["w_up"].sharding.spec[0] == "model"
        assert ps["shared"]["w_up"].sharding.spec[1] == "model"
        hlo = f.lower(ps, x).compile().as_text()
        got = f(ps, x)
assert "all-gather" not in hlo, [l for l in hlo.splitlines()
                                 if "all-gather" in l][:3]
# each data shard dispatches its own two sequences: the load ratio is the
# larger of theirs
(gv, gst), gg = got
(wv, wst), wg = want
with jax.default_matmul_precision("highest"):
    halves = [float(moe_apply(p, x[i:i + 2], m)[1].load_max) for i in (0, 2)]
assert float(gst.load_max) == max(halves), (gst.load_max, halves)
# each of the 4 devices buffers its own worst case: 32 tokens x 3 pairs
assert float(gst.buffer_rows) == 4 * 32 * 3, gst.buffer_rows
got = (gv, gst._replace(load_max=0.0, buffer_rows=0.0), gg)
want = (wv, wst._replace(load_max=0.0, buffer_rows=0.0), wg)
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
print("OK")
""", devices=4)
    assert "OK" in out


def test_expert_shares_add_up_to_the_uncut_layer():
    """A 64-expert layer over 8 chips: chip j holds experts 8j..8j+7 (its
    router columns first).  The routed parts of all 8 shares, plus the
    shared experts counted once, add up to the uncut layer; the pairs the
    shares compute add up to every pair."""
    from repro.models import moe
    from repro.models.config import MoEConfig
    full = MoEConfig(num_experts=64, top_k=6, d_expert=16,
                     num_shared_experts=2, shared_d_expert=16,
                     normalize_gates=False)
    share = MoEConfig(num_experts=64, top_k=6, d_expert=16,
                      num_shared_experts=2, shared_d_expert=16,
                      normalize_gates=False, held_experts=8)
    p, x = _moe_case(full, d=32)
    with jax.default_matmul_precision("highest"):
        want, stats = moe.moe_apply(p, x, full)
        shared = moe._shared(p, x)
        total, rows = shared, 0.0
        for j in range(8):
            cols = jnp.concatenate([jnp.arange(8 * j, 8 * j + 8),
                                    jnp.arange(0, 8 * j),
                                    jnp.arange(8 * j + 8, 64)])
            pj = dict(p, router=p["router"][:, cols],
                      **{w: p[w][8 * j:8 * j + 8]
                         for w in ("w_gate", "w_up", "w_down")})
            yj, sj = moe.moe_apply(pj, x, share)
            total = total + (yj - shared)
            rows += float(sj.rows)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    assert rows == float(stats.rows) == 2 * 24 * 6


# 2 of 16 experts held, top 2, 1,024 tokens: the buffer's worst case is
# 2,048 rows, cut into 4 chunks of 512
CHUNKED = dict(num_experts=16, top_k=2, d_expert=32, held_experts=2,
               num_shared_experts=1, shared_d_expert=8)


def _routed_to_held(m, filled: int, d=16):
    """The layer's weights and (4, 256, d) tokens of which the first
    `filled` send both their pairs to held experts 0 and 1 and the rest
    none: 2·filled held pairs."""
    from repro.models.moe import init_moe
    p = init_moe(jax.random.PRNGKey(1), d, m, "swiglu", jnp.float32)
    p["router"] = p["router"].at[:, 0].set(1.0).at[:, 1].set(0.9)
    x = jnp.abs(jax.random.normal(KEY, (4 * 256, d)))
    x = jnp.where(jnp.arange(4 * 256)[:, None] < filled, x, -x)
    return p, x.reshape(4, 256, d)


@pytest.mark.parametrize("filled", [128, 384, 640, 1024],
                         ids=["one-chunk", "two-chunks", "three-chunks",
                              "all-to-held-fallback"])
def test_chunked_moe_equals_the_worst_case_buffer(monkeypatch, filled):
    """The layer runs the first ceil(pairs / 512) chunks of its buffer;
    output, counters and gradients (tokens, router, the three expert
    stacks) equal the layer forced to one worst-case buffer of 2,048
    rows, down to all pairs on the held experts, where every chunk runs."""
    from repro.models import moe
    from repro.models.config import MoEConfig
    m = MoEConfig(**CHUNKED)
    p, x = _routed_to_held(m, filled)
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def loss(p, x):
        y, st = moe.moe_apply(p, x, m)
        return jnp.sum(y * w) + st.aux, (y, st)

    with jax.default_matmul_precision("highest"):
        (_, (y, st)), g = jax.jit(
            jax.value_and_grad(loss, (0, 1), has_aux=True))(p, x)
        # undifferentiated, as in serving's prefill
        plain = jax.jit(lambda p, x: moe.moe_apply(p, x, m)[0])(p, x)
        monkeypatch.setattr(moe, "buffer_chunks",
                            lambda n, k, held, e: (n * min(k, held), 1))
        (_, (wy, wst)), wg = jax.jit(
            jax.value_and_grad(loss, (0, 1), has_aux=True))(p, x)
    assert float(st.rows) == float(wst.rows) == 2 * filled
    assert float(st.buffer_rows) == 512 * -(-2 * filled // 512)
    assert float(wst.buffer_rows) == 2048
    for out in (y, plain):
        np.testing.assert_allclose(np.asarray(out), np.asarray(wy),
                                   rtol=1e-5, atol=1e-6)
    assert float(st.aux) == float(wst.aux)
    assert float(st.load_max) == float(wst.load_max)
    (gp, gx), (wgp, wgx) = g, wg
    for a, b in [(gx, wgx)] + [(gp[k], wgp[k]) for k in
                               ("router", "w_gate", "w_up", "w_down")]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("shape,want", [
    ((8192, 6, 8, 64), (12288, 4)),     # the DeepSeek-V2-Lite cell
    ((1024, 2, 2, 16), (512, 4)),
    ((8192, 6, 64, 64), (49152, 1)),    # every expert held
    ((64, 3, 4, 8), (192, 1)),          # the smoke block: 1.5n < 1.875n
    ((8, 6, 8, 64), (48, 1)),           # a decode step
], ids=["deepseek-cell", "constructed", "all-held", "smoke", "decode"])
def test_buffer_chunks_follow_the_shapes(shape, want):
    from repro.models.moe import buffer_chunks
    assert buffer_chunks(*shape) == want


@pytest.mark.parametrize("held", [16, 2], ids=["one-chunk", "chunked"])
def test_one_chunk_layer_traces_without_control_flow(held):
    """Where the shapes give one chunk the layer is the single worst-case
    buffer, with no `cond` and no custom gradient; where they give more,
    the chunks after the first run under a `cond`."""
    from repro.models.config import MoEConfig
    from repro.models.moe import moe_apply
    m = MoEConfig(**dict(CHUNKED, held_experts=held))
    p, x = _routed_to_held(m, 384)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(moe_apply(p, x, m)[0]), (0, 1)))(p, x))
    fwd = str(jax.make_jaxpr(lambda p, x: moe_apply(p, x, m))(p, x))
    chunked = held < m.num_experts
    assert ("cond[" in text) == chunked
    assert ("custom_vjp_call" in fwd) == chunked


def test_chunked_moe_under_the_model_axis(subproc):
    """On a 2x2 (data, model) mesh each device cuts its own buffer: the
    device that holds experts 0-3 runs both of its chunks for the pairs
    every token sends to experts 0 and 1, the other one chunk; the layer
    and its gradients equal the unsharded layer's with one worst-case
    buffer."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from repro.distributed.params import param_pspecs
from repro.models import moe
from repro.models.config import MoEConfig
from repro.models.moe import buffer_chunks, init_moe, moe_apply
m = MoEConfig(num_experts=32, top_k=2, d_expert=16, held_experts=8,
              num_shared_experts=1, shared_d_expert=8)
# per device: 512 tokens, 4 of 32 experts held
assert buffer_chunks(512, 2, 4, 32) == (512, 2)
p = init_moe(jax.random.PRNGKey(1), 32, m, "swiglu", jnp.float32)
p["router"] = p["router"].at[:, 0].set(1.0).at[:, 1].set(0.9)
x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (4, 256, 32)))
w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
def loss(p, x):
    y, st = moe_apply(p, x, m)
    return jnp.sum(y * w) + st.aux, st
f = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))
with jax.default_matmul_precision("highest"):
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        ps = jax.device_put(p, jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_pspecs(p, mesh)))
        got = f(ps, x)
    moe.buffer_chunks = lambda n, k, held, e: (n * min(k, held), 1)
    want = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(p, x)
(gv, gst), gg = got
(wv, wst), wg = want
assert float(gst.rows) == float(wst.rows) == 2 * 1024
# two data shards: 2 chunks of 512 on the first model device, 1 on the other
assert float(gst.buffer_rows) == 2 * (1024 + 512), gst.buffer_rows
for a, b in zip(jax.tree.leaves((gv, gg)), jax.tree.leaves((wv, wg))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
print("OK")
""", devices=4)
    assert "OK" in out


def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V2-Lite's YaRN (RoPE width 64, θ 10000, factor 40 over the
    original 4,096 positions, β_fast 32, β_slow 1, mscale = mscale_all_dim
    = 0.707), worked out from the published formula: the correction
    dimensions are ⌊10.47⌋ = 10 and ⌈22.51⌉ = 23, so frequencies 0-10 keep
    θ^(-2i/64), 23-31 are divided by 40, and i = 16 sits 6/13 of the way;
    the softmax scale is (0.1·0.707·ln 40 + 1)² / √192 = 0.1147214; cos
    and sin keep a factor of 1."""
    from repro.models import embeddings, mla
    from repro.configs import get_config
    m = get_config("deepseek-v2-lite-16b").mla
    f = np.asarray(embeddings.yarn_frequencies(64, 1e4, 40.0, 4096, 32.0,
                                               1.0))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(f[10], 0.05623413, rtol=1e-6)
    np.testing.assert_allclose(f[16], 0.0055, rtol=1e-5)
    assert mla.softmax_scale(m) == pytest.approx(0.11472139, rel=1e-7)
    assert embeddings.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608038)
    x = jax.random.normal(KEY, (1, 8, 2, 64))
    pos = jnp.arange(8)[None]
    np.testing.assert_allclose(
        np.asarray(mla._rope(x, pos, m, 1e4)),
        np.asarray(embeddings.apply_rope(x, pos, 1e4, freqs=jnp.asarray(f))),
        rtol=1e-6)


def test_mla_rope_base_comes_from_the_config():
    from repro.models import mla
    from repro.models.config import MLAConfig
    m = MLAConfig()
    x = jax.random.normal(KEY, (1, 8, 2, 64))
    pos = jnp.arange(8)[None]
    a = mla._rope(x, pos, m, 10000.0)
    b = mla._rope(x, pos, m, 500000.0)
    assert not np.allclose(np.asarray(a), np.asarray(b))
