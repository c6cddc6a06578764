"""Plain float32 reference of a dense pre-norm decoder (Phi-3-mini's block:
RoPE attention with as many key/value heads as query heads, SwiGLU MLP,
RMSNorm, untied output head) and its training step.

Written from the published architecture alone, in straightforward
`jax.numpy`: no kernels, no flat buffers, no scans over microbatches, no
sharding rules.  The weights are drawn from the seed in the trainer's
documented order (six keys off the root; embedding, stacked layers, final
norm, output head; 0.02-scaled normals for every matrix, ones for every
norm scale), so the same seed gives the same weights without reading any.

The training step is Algorithm 1's: valid-token-weighted mean gradient
over the step's rows, the norm test's two statistics over the step's
variance groups, global-norm clipping and AdamW with bias correction and
decoupled weight decay.

Departures from the published Phi-3-mini, each as the trainer runs it:
RMSNorm epsilon 1e-6 (published 1e-5); no attention window (published
2047, which at 2048 positions masks only key 0 for the last query).

The module is a configuration's `"reference"` (see the package's doc): it
also derives the sizes, checks the trainer's model against them and counts
the parameters and operations.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes(model: dict) -> dict:
    """The file's `model`, with the head size the config leaves implicit."""
    m = dict(model)
    m["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    return m


def check_program(cfg, m: dict):
    """The trainer's ModelConfig must be the file's model, width for width."""
    pairs = {"d_model": "hidden_size", "d_ff": "intermediate_size",
             "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads",
             "num_layers": "num_hidden_layers", "vocab_size": "vocab_size",
             "head_dim": "head_dim", "rope_theta": "rope_theta"}
    bad = {a: (getattr(cfg, a), m[b]) for a, b in pairs.items()
           if getattr(cfg, a) != m[b]}
    if cfg.tie_embeddings != m["tie_word_embeddings"]:
        bad["tie_embeddings"] = (cfg.tie_embeddings, m["tie_word_embeddings"])
    if bad:
        raise ValueError(f"the trainer's model differs from the file: {bad}")


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer that enter a matrix product."""
    d, h, kv, dh, ff = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * ff if m["hidden_act"] == "silu" else 2 * d * ff
    return attn + mlp


def param_count(m: dict) -> int:
    """Every parameter: embedding, layers (with their two norm scales),
    final norm and (untied) output head."""
    d, v, n = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    head = 0 if m.get("tie_word_embeddings") else v * d
    return v * d + head + n * (layer_matmul_params(m) + 2 * d) + d


def flops_per_token(m: dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token, recomputation not
    counted: 6 per weight of every matrix product (layers and output head;
    the embedding is a lookup) and causal attention's two products, whose
    query at position i meets i + 1 keys, (seq_len + 1) / 2 on average."""
    d, v, n = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    h, dh = m["num_attention_heads"], m["head_dim"]
    matmul = n * layer_matmul_params(m) + v * d
    attn_fwd = n * 2 * 2 * h * dh * (seq_len + 1) / 2
    return 6.0 * matmul + 3.0 * attn_fwd


def _normal(key, shape, dtype):
    # the barrier keeps jit from folding the scale into the draw's
    # constants, so a jitted draw gives the bits of an eager one
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape))
    return (0.02 * z).astype(dtype)


def _layer_init(key, c, dtype):
    k_attn, k_mlp, _, _, _, _ = jax.random.split(key, 6)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k1, k2, k3 = jax.random.split(k_mlp, 3)
    d, h, kvh, dh, ff = (c["hidden_size"], c["num_attention_heads"],
                         c["num_key_value_heads"], c["head_dim"],
                         c["intermediate_size"])
    return {
        "pre_norm": {"scale": jnp.ones((d,), dtype)},
        "attn": {"wq": _normal(kq, (d, h, dh), dtype),
                 "wk": _normal(kk, (d, kvh, dh), dtype),
                 "wv": _normal(kv, (d, kvh, dh), dtype),
                 "wo": _normal(ko, (h, dh, d), dtype)},
        "mlp_norm": {"scale": jnp.ones((d,), dtype)},
        "mlp": {"w_gate": _normal(k1, (d, ff), dtype),
                "w_up": _normal(k2, (d, ff), dtype),
                "w_down": _normal(k3, (ff, d), dtype)},
    }


def seed_key(seed: int) -> jax.Array:
    """The root key of `seed`, made outside any jit: a key passed to a
    jitted `init` as an argument lets every seed share one compiled
    program, where a seed closed over would compile one per seed."""
    return jax.random.PRNGKey(seed)


def init(key: jax.Array, c: dict, dtype=jnp.float32) -> dict:
    """Weights for `c` (the configuration's `model` sizes) from the root
    key of the seed (`seed_key`)."""
    k_embed, _, k_blocks, _, k_unembed, _ = jax.random.split(key, 6)
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    (k_layers,) = jax.random.split(k_blocks, 1)
    layer_keys = jnp.stack(list(jax.random.split(k_layers, n)))
    return {
        "embed": {"table": _normal(k_embed, (v, d), dtype)},
        "blocks": [jax.vmap(lambda k: _layer_init(k, c, dtype))(layer_keys)],
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "unembed": {"table": _normal(k_unembed, (v, d), dtype)},
    }


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE over the last axis; x: (b, t, heads, dh)."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _layer(p, x, c):
    eps = c["rms_norm_eps"]
    h = _rmsnorm(x, p["pre_norm"]["scale"], eps)
    a = p["attn"]
    q = _rope(jnp.einsum("btd,dhk->bthk", h, a["wq"]), c["rope_theta"])
    k = _rope(jnp.einsum("btd,dhk->bthk", h, a["wk"]), c["rope_theta"])
    v = jnp.einsum("btd,dhk->bthk", h, a["wv"])
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    t = x.shape[1]
    s = jnp.einsum("bthk,bshk->bhts", q, k).astype(jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhts,bshk->bthk", probs, v)
    x = x + jnp.einsum("bthk,hkd->btd", o, a["wo"])
    h = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    gate = jnp.einsum("btd,df->btf", h, m["w_gate"])
    up = jnp.einsum("btd,df->btf", h, m["w_up"])
    return x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up, m["w_down"])


def loss_sum(params, tokens, labels, c):
    """(Σ next-token cross entropy over valid labels, valid count) for one
    block of rows; labels < 0 are masked."""
    x = params["embed"]["table"][tokens]
    layer = jax.checkpoint(lambda p, x: _layer(p, x, c))

    def body(x, p):
        return layer(p, x), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = jnp.einsum("btd,vd->btv", x,
                        params["unembed"]["table"]).astype(jnp.float32)
    mask = labels >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - gold) * mask
    return jnp.sum(nll), jnp.sum(mask).astype(jnp.float32)


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
