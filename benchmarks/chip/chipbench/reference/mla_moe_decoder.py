"""Plain float32 reference of a DeepSeek-V2 decoder on one chip's share
(DeepSeek-V2-Lite's block: multi-head latent attention with YaRN RoPE,
a dense SwiGLU first layer, then layers of routed and shared SwiGLU
experts, RMSNorm, untied output head) and its training objective.

Written from the published description alone (arXiv:2405.04434 and the
model's published configuration and modelling code), in straightforward
`jax.numpy`: no kernels, no sorting or grouping of tokens, no flat
buffers, no scans over microbatches, no sharding rules.

Attention is MLA in its expanded form: the query (no query compression)
splits into a part without position (128 per head) and a RoPE part (64);
the keys' part without position and the values come up from the
compressed latent c_kv (rank 512, RMSNorm'd), and one RoPE key of 64,
shared by all heads, comes from the hidden state.  RoPE is YaRN's:
frequencies ramp from extrapolated (θ^(-2i/64)) to interpolated (divided
by the factor) between the correction dimensions of `beta_fast` and
`beta_slow` rotations over the original context; cos and sin are scaled
by mscale(factor, mscale) / mscale(factor, mscale_all_dim); the softmax
scale is mscale(factor, mscale_all_dim)² / √192.  The published code
de-interleaves the RoPE columns before rotating by halves, a fixed
permutation of the RoPE columns of the query and shared-key projections:
a layout of the weights, not a departure.

Experts: the router scores all of the published experts (f32 softmax)
and picks each token's top k of them (greedy); a token's gate for expert
e is its probability where e is among its top k and 0 elsewhere,
renormalised over the top k only where `norm_topk_prob` says, times the
routed scaling factor.  The chip holds experts [0, n_routed_experts):
each held expert is applied to every token and weighted by its gate, so
what the absent experts would add is left out, as on the chip.  The
shared experts are applied to every token once.

The loss is mean next-token cross entropy plus DeepSeek-V2's
sequence-level balance loss: per sequence and expert layer,
α·(E/k)·Σ_e (count_e/T)·mean_t p_{t,e}, averaged over sequences.  A block's
loss sum carries each row's balance loss times the row's valid count, so
(loss sum) / (valid count) over a step's rows is the trainer's objective.

The weights are drawn from the seed in the trainer's documented order
(six keys off the root; embedding, the dense first layer, the stacked
expert layers, final norm, output head; 0.02-scaled normals for every
matrix, ones for every norm scale), so the same seed gives the same
weights without reading any.

Departures from the published model: none.  The module is a
configuration's `"reference"` (see the package's doc).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# query rows of one block of the reference's score matrices, and positions
# of one block of its logits: each block is recomputed in the backward, so
# the reference fits one chip beside its weights, moments and gradient sums
Q_BLOCK = 512
T_BLOCK = 1024


def sizes(model: dict) -> dict:
    """The file's `model` with the sizes the reference derives: the
    experts routed over (`router_experts`, the published count) and held
    (`n_routed_experts`, the chip's share), the query/key width and the
    YaRN settings."""
    m = dict(model)
    rs = m.get("rope_scaling") or {}
    m["held_experts"] = m["n_routed_experts"]
    m["num_experts"] = m.get("router_experts", m["n_routed_experts"])
    m["qk_head_dim"] = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    m["n_dense"] = m["first_k_dense_replace"]
    m["n_moe"] = m["num_hidden_layers"] - m["n_dense"]
    m["yarn"] = {"factor": float(rs.get("factor", 1.0)),
                 "original_max": int(rs.get(
                     "original_max_position_embeddings",
                     m["max_position_embeddings"])),
                 "beta_fast": float(rs.get("beta_fast", 32)),
                 "beta_slow": float(rs.get("beta_slow", 1)),
                 "mscale": float(rs.get("mscale", 1.0)),
                 "mscale_all_dim": float(rs.get("mscale_all_dim", 0.0))}
    return m


def check_program(cfg, m: dict):
    """The trainer's ModelConfig must be the file's model, width for
    width: MLA's widths, rank and YaRN, the experts routed over and held,
    top-k, expert and shared widths, gate normalisation and the dense
    prefix."""
    bad = {}

    def want(name, got, expect):
        if got != expect:
            bad[name] = (got, expect)

    want("d_model", cfg.d_model, m["hidden_size"])
    want("d_ff", cfg.d_ff, m["intermediate_size"])
    want("num_heads", cfg.num_heads, m["num_attention_heads"])
    want("num_kv_heads", cfg.num_kv_heads, m["num_key_value_heads"])
    want("num_layers", cfg.num_layers, m["num_hidden_layers"])
    want("vocab_size", cfg.vocab_size, m["vocab_size"])
    want("rope_theta", cfg.rope_theta, m["rope_theta"])
    want("tie_embeddings", cfg.tie_embeddings, m["tie_word_embeddings"])
    want("mlp_kind", cfg.mlp_kind, "swiglu" if m["hidden_act"] == "silu"
         else m["hidden_act"])
    want("prefix_pattern", cfg.prefix_pattern, ("mla",) * m["n_dense"])
    want("block_pattern", cfg.block_pattern, ("mla",))
    a, y = cfg.mla, m["yarn"]
    if a is None:
        bad["mla"] = (None, "MLA")
    else:
        want("kv_lora_rank", a.kv_lora_rank, m["kv_lora_rank"])
        want("q_lora_rank", a.q_lora_rank, m["q_lora_rank"] or 0)
        want("qk_nope_head_dim", a.qk_nope_head_dim, m["qk_nope_head_dim"])
        want("qk_rope_head_dim", a.qk_rope_head_dim, m["qk_rope_head_dim"])
        want("v_head_dim", a.v_head_dim, m["v_head_dim"])
        want("rope_factor", a.rope_factor, y["factor"])
        want("original_max_position", a.original_max_position,
             y["original_max"])
        want("beta_fast", a.beta_fast, y["beta_fast"])
        want("beta_slow", a.beta_slow, y["beta_slow"])
        want("mscale", a.mscale, y["mscale"])
        want("mscale_all_dim", a.mscale_all_dim, y["mscale_all_dim"])
    e = cfg.moe
    if e is None:
        bad["moe"] = (None, "experts")
    else:
        want("num_experts", e.num_experts, m["num_experts"])
        want("held", e.held, m["held_experts"])
        want("top_k", e.top_k, m["num_experts_per_tok"])
        want("d_expert", e.d_expert, m["moe_intermediate_size"])
        want("num_shared_experts", e.num_shared_experts,
             m["n_shared_experts"])
        want("shared_d_expert", e.shared_d_expert, m["moe_intermediate_size"])
        want("normalize_gates", e.normalize_gates, m["norm_topk_prob"])
        want("routed_scaling_factor", e.routed_scaling_factor,
             m["routed_scaling_factor"])
        want("router_aux_coef", e.router_aux_coef, m["aux_loss_alpha"])
        want("first_dense", e.first_dense, m["n_dense"])
    if m["scoring_func"] != "softmax" or m["topk_method"] != "greedy" \
            or not m["seq_aux"]:
        bad["routing"] = ("softmax/greedy/seq_aux", (
            m["scoring_func"], m["topk_method"], m["seq_aux"]))
    if bad:
        raise ValueError(f"the trainer's model differs from the file: {bad}")


# ---------------------------------------------------------------- counts --

def _mla_params(m: dict) -> int:
    """MLA weights of one layer that enter a matrix product."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return (d * h * m["qk_head_dim"] + d * r + d * rope
            + r * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _shared_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["n_shared_experts"] \
        * m["moe_intermediate_size"]


def param_count(m: dict) -> int:
    """Every parameter held: embedding, layers (with their norm scales:
    two per layer and the latent's), final norm and the untied head; the
    expert layers count their router and held experts."""
    d, v = m["hidden_size"], m["vocab_size"]
    attn = _mla_params(m) + m["kv_lora_rank"] + 2 * d
    dense = 3 * d * m["intermediate_size"]
    moe = (d * m["num_experts"] + m["held_experts"] * _expert_params(m)
           + _shared_params(m))
    return (2 * v * d + m["n_dense"] * (attn + dense)
            + m["n_moe"] * (attn + moe) + d)


def flops_per_token(m: dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token, recomputation
    not counted: 6 per weight of every matrix product a token meets, and
    causal attention's two products (scores at the query/key width, output
    at the value width), whose query at position i meets i + 1 keys.  The
    routed experts count at their expected share of a token: of its top k
    of E experts, k·held/E are held here (0.75 on DeepSeek-V2-Lite's cut),
    the rest computed on other chips."""
    d, v, h = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    layers = m["num_hidden_layers"]
    held_share = m["num_experts_per_tok"] * m["held_experts"] \
        / m["num_experts"]
    moe = (d * m["num_experts"] + held_share * _expert_params(m)
           + _shared_params(m))
    matmul = (layers * _mla_params(m)
              + m["n_dense"] * 3 * d * m["intermediate_size"]
              + m["n_moe"] * moe + v * d)
    attn_fwd = layers * 2 * h * (m["qk_head_dim"] + m["v_head_dim"]) \
        * (seq_len + 1) / 2
    return 6.0 * matmul + 3.0 * attn_fwd


# ------------------------------------------------------------------ init --

def _normal(key, shape, dtype):
    # the barrier keeps jit from folding the scale into the draw's
    # constants, so a jitted draw gives the bits of an eager one
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape))
    return (0.02 * z).astype(dtype)


def _attn_init(key, c, dtype):
    keys = jax.random.split(key, 8)
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    return {"w_q": _normal(keys[1], (d, h, c["qk_head_dim"]), dtype),
            "w_dkv": _normal(keys[2], (d, r), dtype),
            "kv_norm": {"scale": jnp.ones((r,), dtype)},
            "w_krope": _normal(keys[4], (d, c["qk_rope_head_dim"]), dtype),
            "w_uk": _normal(keys[5], (r, h, c["qk_nope_head_dim"]), dtype),
            "w_uv": _normal(keys[6], (r, h, c["v_head_dim"]), dtype),
            "w_o": _normal(keys[7], (h, c["v_head_dim"], d), dtype)}


def _layer_init(key, c, dtype, experts: bool):
    k_attn, k_mlp, _, _, _, _ = jax.random.split(key, 6)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    if experts:
        k_r, k_g, k_u, k_d, k_s = jax.random.split(k_mlp, 5)
        ks1, ks2, ks3 = jax.random.split(k_s, 3)
        held, width = c["held_experts"], c["n_shared_experts"] * f
        mlp = {"router": _normal(k_r, (d, c["num_experts"]), dtype),
               "w_gate": _normal(k_g, (held, d, f), dtype),
               "w_up": _normal(k_u, (held, d, f), dtype),
               "w_down": _normal(k_d, (held, f, d), dtype),
               "shared": {"w_gate": _normal(ks1, (d, width), dtype),
                          "w_up": _normal(ks2, (d, width), dtype),
                          "w_down": _normal(ks3, (width, d), dtype)}}
    else:
        k1, k2, k3 = jax.random.split(k_mlp, 3)
        ff = c["intermediate_size"]
        mlp = {"w_gate": _normal(k1, (d, ff), dtype),
               "w_up": _normal(k2, (d, ff), dtype),
               "w_down": _normal(k3, (ff, d), dtype)}
    return {"pre_norm": {"scale": jnp.ones((d,), dtype)},
            "attn": _attn_init(k_attn, c, dtype),
            "mlp_norm": {"scale": jnp.ones((d,), dtype)},
            "mlp": mlp}


def seed_key(seed: int) -> jax.Array:
    """The root key of `seed`, made outside any jit: a key passed to a
    jitted `init` as an argument lets every seed share one compiled
    program, where a seed closed over would compile one per seed."""
    return jax.random.PRNGKey(seed)


def init(key: jax.Array, c: dict, dtype=jnp.float32) -> dict:
    """Weights for `c` (the configuration's `model` sizes) from the root
    key of the seed (`seed_key`)."""
    k_embed, k_prefix, k_blocks, _, k_unembed, _ = jax.random.split(key, 6)
    d, v = c["hidden_size"], c["vocab_size"]
    dense_keys = jax.random.split(k_prefix, c["n_dense"])
    (k_layers,) = jax.random.split(k_blocks, 1)
    layer_keys = jnp.stack(list(jax.random.split(k_layers, c["n_moe"])))
    return {
        "embed": {"table": _normal(k_embed, (v, d), dtype)},
        "prefix_blocks": [_layer_init(k, c, dtype, False)
                          for k in dense_keys],
        "blocks": [jax.vmap(lambda k: _layer_init(k, c, dtype, True))(
            layer_keys)],
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "unembed": {"table": _normal(k_unembed, (v, d), dtype)},
    }


# --------------------------------------------------------------- forward --

def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations, dim, theta, original_max):
    return (dim * math.log(original_max / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_frequencies(c: dict) -> jax.Array:
    """The RoPE part's inverse frequencies, YaRN-scaled where the model
    says."""
    dim, theta, y = c["qk_rope_head_dim"], c["rope_theta"], c["yarn"]
    base = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if y["factor"] <= 1:
        return base
    low = max(math.floor(_correction_dim(y["beta_fast"], dim, theta,
                                         y["original_max"])), 0)
    high = min(math.ceil(_correction_dim(y["beta_slow"], dim, theta,
                                         y["original_max"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base / y["factor"] * ramp + base * (1.0 - ramp)


def softmax_scale(c: dict) -> float:
    y = c["yarn"]
    scale = 1.0 / math.sqrt(c["qk_head_dim"])
    if y["factor"] > 1 and y["mscale_all_dim"]:
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, c):
    """Rotate-half RoPE over the last axis; x: (b, t, heads, rope)."""
    y = c["yarn"]
    t = x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * rope_frequencies(c)
    m = _yarn_mscale(y["factor"], y["mscale"]) / _yarn_mscale(
        y["factor"], y["mscale_all_dim"])
    cos = (jnp.cos(ang) * m)[None, :, None, :]
    sin = (jnp.sin(ang) * m)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _scores_block(q_nope, q_rope, k_nope, k_rope, v, q0, scale):
    """Causal attention of the query rows [q0, q0 + rows) over all keys:
    q_nope (b, rows, h, n), q_rope (b, rows, h, r), k_nope (b, s, h, n),
    k_rope (b, s, r), v (b, s, h, dv)."""
    s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
         + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope))
    s = s.astype(jnp.float32) * scale
    rows, keys = q_nope.shape[1], k_nope.shape[1]
    causal = jnp.arange(keys)[None, :] <= (q0 + jnp.arange(rows))[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshv->bthv", probs, v)


def _mla(a, h, c):
    eps = c["rms_norm_eps"]
    n = c["qk_nope_head_dim"]
    q = jnp.einsum("btd,dhk->bthk", h, a["w_q"])
    q_nope, q_rope = q[..., :n], _rope(q[..., n:], c)
    c_kv = _rmsnorm(jnp.einsum("btd,dr->btr", h, a["w_dkv"]),
                    a["kv_norm"]["scale"], eps)
    k_rope = _rope(jnp.einsum("btd,dr->btr", h, a["w_krope"])[:, :, None],
                   c)[:, :, 0]
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv, a["w_uk"])
    v = jnp.einsum("btr,rhv->bthv", c_kv, a["w_uv"])
    b, t = h.shape[:2]
    scale = softmax_scale(c)
    rows = min(Q_BLOCK, t)

    # one block of query rows at a time, recomputed in the backward
    @jax.checkpoint
    def block(_, xs):
        qn, qr, q0 = xs
        return None, _scores_block(qn, qr, k_nope, k_rope, v, q0, scale)

    def blocks(x):
        return jnp.moveaxis(x.reshape(b, t // rows, rows, *x.shape[2:]),
                            1, 0)
    _, out = jax.lax.scan(block, None, (blocks(q_nope), blocks(q_rope),
                                        jnp.arange(0, t, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, *out.shape[3:])
    return jnp.einsum("bthv,hvd->btd", out, a["w_o"])


def _swiglu(h, w_gate, w_up, w_down):
    return jnp.einsum("btf,fd->btd", jax.nn.silu(
        jnp.einsum("btd,df->btf", h, w_gate))
        * jnp.einsum("btd,df->btf", h, w_up), w_down)


def _experts(p, h, c):
    """(the held experts' gate-weighted output plus the shared experts',
    each row's balance loss)."""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.einsum(
        "btd,de->bte", h.astype(jnp.float32),
        p["router"].astype(jnp.float32)), axis=-1)
    _, top = jax.lax.top_k(probs, k)
    chosen = jnp.sum(jax.nn.one_hot(top, e, dtype=jnp.float32), axis=2)
    gates = probs * chosen
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * c["routed_scaling_factor"]
    swiglu = jax.checkpoint(_swiglu)
    y = swiglu(h, p["shared"]["w_gate"], p["shared"]["w_up"],
               p["shared"]["w_down"])
    for i in range(c["held_experts"]):
        y = y + gates[..., i:i + 1].astype(h.dtype) * swiglu(
            h, p["w_gate"][i], p["w_up"][i], p["w_down"][i])
    t = h.shape[1]
    balance = (e / k) * jnp.sum(jnp.sum(chosen, axis=1) / t
                                * jnp.mean(probs, axis=1), axis=-1)
    return y, c["aux_loss_alpha"] * balance


def _layer(p, x, c, experts: bool):
    eps = c["rms_norm_eps"]
    x = x + _mla(p["attn"], _rmsnorm(x, p["pre_norm"]["scale"], eps), c)
    h = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
    if experts:
        y, balance = _experts(p["mlp"], h, c)
        return x + y, balance
    m = p["mlp"]
    return x + jax.checkpoint(_swiglu)(h, m["w_gate"], m["w_up"],
                                       m["w_down"]), \
        jnp.zeros((x.shape[0],), jnp.float32)


def loss_sum(params, tokens, labels, c):
    """(Σ next-token cross entropy over valid labels + Σ over rows of the
    row's balance loss times its valid count, valid count) for one block
    of rows; labels < 0 are masked."""
    x = params["embed"]["table"][tokens]
    balance = jnp.zeros((tokens.shape[0],), jnp.float32)
    for p in params["prefix_blocks"]:
        x, _ = jax.checkpoint(lambda p, x: _layer(p, x, c, False))(p, x)
    layer = jax.checkpoint(lambda p, x: _layer(p, x, c, True))

    def body(carry, p):
        x, bal = carry
        x, b = layer(p, x)
        return (x, bal + b), None

    (x, balance), _ = jax.lax.scan(body, (x, balance), params["blocks"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"], c["rms_norm_eps"])

    @jax.checkpoint
    def nll(x, labels, table):
        logits = jnp.einsum("btd,vd->btv", x, table).astype(jnp.float32)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold)
                       * (labels >= 0))

    total = sum(nll(x[:, t0:t0 + T_BLOCK], labels[:, t0:t0 + T_BLOCK],
                    params["unembed"]["table"])
                for t0 in range(0, x.shape[1], T_BLOCK))
    count = jnp.sum(labels >= 0, axis=-1).astype(jnp.float32)
    return total + jnp.sum(balance * count), jnp.sum(count)


def block_grad(c):
    """(params, tokens, labels) -> (loss sum, valid count, grad of the loss
    sum), all in the params' dtype; the caller jits it."""
    def f(params, tokens, labels):
        (s, n), g = jax.value_and_grad(
            lambda p: loss_sum(p, tokens, labels, c), has_aux=True)(params)
        return s, n, g
    return f


def sqnorm(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def leaf_norms(tree) -> dict:
    """Path -> L2 norm of each leaf, as host floats."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32))))) for k, v in flat}


def host_leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float32)
            for k, v in flat}
