"""DeepSeek-V2 Multi-head Latent Attention (MLA), with YaRN RoPE scaling.

Training/prefill uses the expanded form; decode uses the *absorbed* form that
attends directly in the compressed latent space (the whole point of MLA: the
KV cache stores only `c_kv` (rank 512) plus the shared RoPE key, instead of
full per-head K/V).

RoPE rotates by halves.  DeepSeek's published code first de-interleaves
the RoPE columns of the query and the shared key; that is a fixed
permutation of the RoPE columns of `w_q` and `w_krope`, a layout of the
weights, not another function.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels import ops
from repro.models.common import normal_init, split_keys
from repro.models.norms import init_norm, apply_norm
from repro.models.embeddings import apply_rope, yarn_frequencies, yarn_mscale
from repro.models.attention import NEG_INF
from repro.models.config import MLAConfig
from repro.distributed.sharding import maybe_shard


def init_mla(key, d_model: int, num_heads: int, m: MLAConfig, dtype):
    keys = split_keys(key, 8)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {}
    if m.q_lora_rank:
        p["w_dq"] = normal_init(keys[0], (d_model, m.q_lora_rank), dtype)
        p["q_norm"] = init_norm(keys[0], m.q_lora_rank, "rmsnorm", dtype)
        p["w_uq"] = normal_init(keys[1], (m.q_lora_rank, num_heads, qk_dim), dtype)
    else:
        p["w_q"] = normal_init(keys[1], (d_model, num_heads, qk_dim), dtype)
    p["w_dkv"] = normal_init(keys[2], (d_model, m.kv_lora_rank), dtype)
    p["kv_norm"] = init_norm(keys[3], m.kv_lora_rank, "rmsnorm", dtype)
    p["w_krope"] = normal_init(keys[4], (d_model, m.qk_rope_head_dim), dtype)
    p["w_uk"] = normal_init(keys[5], (m.kv_lora_rank, num_heads, m.qk_nope_head_dim), dtype)
    p["w_uv"] = normal_init(keys[6], (m.kv_lora_rank, num_heads, m.v_head_dim), dtype)
    p["w_o"] = normal_init(keys[7], (num_heads, m.v_head_dim, d_model), dtype)
    return p


def _rope(x, positions, m: MLAConfig, theta: float):
    """RoPE over the last axis of x (..., seq, heads, rope width), with
    YaRN's frequencies and cos/sin factor where the config scales."""
    if m.rope_factor <= 1:
        return apply_rope(x, positions, theta)
    freqs = yarn_frequencies(x.shape[-1], theta, m.rope_factor,
                             m.original_max_position, m.beta_fast,
                             m.beta_slow)
    scale = (yarn_mscale(m.rope_factor, m.mscale)
             / yarn_mscale(m.rope_factor, m.mscale_all_dim))
    return apply_rope(x, positions, theta, freqs=freqs, scale=scale)


def softmax_scale(m: MLAConfig) -> float:
    """1/sqrt(query/key width), times YaRN's mscale² where the config
    scales (DeepSeek-V2: (0.1·mscale_all_dim·ln factor + 1)²)."""
    scale = 1.0 / math.sqrt(m.qk_head_dim)
    if m.rope_factor > 1 and m.mscale_all_dim:
        scale *= yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2
    return scale


def _queries(params, x, positions, m: MLAConfig, theta: float):
    if "w_dq" in params:
        cq = jnp.einsum("btd,dr->btr", x, params["w_dq"].astype(x.dtype))
        cq = apply_norm(params["q_norm"], cq, "rmsnorm")
        q = jnp.einsum("btr,rhk->bthk", cq, params["w_uq"].astype(x.dtype))
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["w_q"].astype(x.dtype))
    q = maybe_shard(q, "batch", "seq", "heads", None)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = _rope(q[..., m.qk_nope_head_dim:], positions, m, theta)
    return q_nope, q_rope


def _latents(params, x, positions, m: MLAConfig, theta: float):
    c_kv = jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(x.dtype))
    c_kv = apply_norm(params["kv_norm"], c_kv, "rmsnorm")
    k_rope = jnp.einsum("btd,dr->btr", x, params["w_krope"].astype(x.dtype))
    # shared rope key: single head
    k_rope = _rope(k_rope[:, :, None, :], positions, m, theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_full(params, x, positions, m: MLAConfig, theta: float,
             causal: bool = True):
    """Expanded-form MLA over a full sequence (training / prefill): keys
    are [k_nope ‖ the shared RoPE key, broadcast to every head], and the
    core (`ops.attention_core`: the flash kernels on TPU) runs at query/key
    width qk_nope + qk_rope and value width v_head_dim."""
    q_nope, q_rope = _queries(params, x, positions, m, theta)
    c_kv, k_rope = _latents(params, x, positions, m, theta)
    k_nope = jnp.einsum("btr,rhn->bthn", c_kv, params["w_uk"].astype(x.dtype))
    v = jnp.einsum("btr,rhv->bthv", c_kv, params["w_uv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              (*k_nope.shape[:3], m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    with jax.named_scope(spans.ATTENTION_CORE):
        out = ops.attention_core(q, k, v, causal=causal, window=0,
                                 softcap=0.0, scale=softmax_scale(m))
    out = jnp.einsum("bthv,hvd->btd", out, params["w_o"].astype(x.dtype))
    return maybe_shard(out, "batch", "seq", "embed")


def init_mla_cache(batch: int, cache_len: int, m: MLAConfig, dtype):
    return {
        "c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype),
    }


def mla_decode(params, x, cache, pos, m: MLAConfig, theta: float,
               ring: bool = False):
    """Absorbed-form single-token decode against the latent cache.  `pos` is
    a scalar int32, or a (b,) int32 vector of per-row positions (continuous-
    batching serving — each row writes and masks its own timeline)."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _queries(params, x, positions, m, theta)   # (b,1,h,*)
    c_new, kr_new = _latents(params, x, positions, m, theta)    # (b,1,r)
    cache_len = cache["c_kv"].shape[1]
    slot = pos % cache_len if ring else pos
    if per_row:
        rows = jnp.arange(b)
        c_kv = cache["c_kv"].at[rows, slot].set(
            c_new[:, 0].astype(cache["c_kv"].dtype))
        k_rope = cache["k_rope"].at[rows, slot].set(
            kr_new[:, 0].astype(cache["k_rope"].dtype))
    else:
        c_kv = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, slot, 0))
        k_rope = jax.lax.dynamic_update_slice(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, slot, 0))
    # absorb W_uk into the query: attend in latent space
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, params["w_uk"].astype(x.dtype))
    logits = jnp.einsum("bthr,bsr->bhts", q_lat, c_kv.astype(x.dtype))
    logits += jnp.einsum("bthr,bsr->bhts", q_rope, k_rope.astype(x.dtype))
    logits = logits.astype(jnp.float32) * softmax_scale(m)
    kpos = jnp.arange(cache_len)
    ppos = pos[:, None] if per_row else pos
    if ring:
        valid = (kpos <= ppos) | (ppos >= cache_len)
    else:
        valid = kpos <= ppos
    logits = jnp.where(valid[:, None, None, :] if per_row
                       else valid[None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhts,bsr->bthr", probs, c_kv.astype(x.dtype))
    out = jnp.einsum("bthr,rhv->bthv", out_lat, params["w_uv"].astype(x.dtype))
    out = jnp.einsum("bthv,hvd->btd", out, params["w_o"].astype(x.dtype))
    return out, {"c_kv": c_kv, "k_rope": k_rope}
