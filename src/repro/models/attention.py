"""GQA attention with RoPE, sliding windows, logit soft-capping and KV caches.

Three entry points:
  * `attend_full`   — training / prefill over a whole sequence (causal or not)
  * `attend_decode` — single-token decode against a KV cache
  * caches: `init_cache` (full-length) and ring-buffer sliding caches for the
    `long_500k` serving mode.

`attend_full`'s score-softmax-output core goes through
`repro.kernels.ops.attention_core`: the Pallas flash kernels
(`repro.kernels.flash_attention`, forward and backward) on TPU, and the jnp
path here (`sdpa_self`) on CPU and for shapes the kernels do not take.  The
tests check the kernels against this path; the benchmark's plain reference
is `benchmarks/chip/chipbench/reference/`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels import ops
from repro.models.common import normal_init, split_keys
from repro.models.embeddings import apply_rope
from repro.distributed.sharding import maybe_shard

NEG_INF = -2.0e38


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int, head_dim: int, dtype):
    kq, kk, kv, ko = split_keys(key, 4)
    return {
        "wq": normal_init(kq, (d_model, num_heads, head_dim), dtype),
        "wk": normal_init(kk, (d_model, num_kv_heads, head_dim), dtype),
        "wv": normal_init(kv, (d_model, num_kv_heads, head_dim), dtype),
        "wo": normal_init(ko, (num_heads, head_dim, d_model), dtype),
    }


def _project_qkv(params, x, positions, rope_theta, qk_norm: bool):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    q = maybe_shard(q, "batch", "seq", "heads", None)
    k = maybe_shard(k, "batch", "seq", "kv_heads", None)
    v = maybe_shard(v, "batch", "seq", "kv_heads", None)
    if qk_norm:
        q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-6)
        k = k / (jnp.linalg.norm(k, axis=-1, keepdims=True) + 1e-6)
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, softcap: float, scale: float | None = None):
    """q: (b,t,h,dk) k: (b,s,kv,dk) v: (b,s,kv,dv); GQA via head grouping.
    mask: (b,t,s) or (t,s).  Scores scaled by `scale`, 1/sqrt(dk) by
    default."""
    b, t, h, dk = q.shape
    kv = k.shape[2]
    # GQA via *kv-head expansion* (kv tensors are small) instead of grouping
    # q heads: the (b,t,kv,g,d) reshape breaks the `heads` sharding axis and
    # forced the SPMD partitioner into full-remat copies (see §Perf-1).
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        k = maybe_shard(k, "batch", None, "heads", None)
        v = maybe_shard(v, "batch", None, "heads", None)
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(dk) if scale is None else logits * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    if mask is not None:
        # mask is (t,s) or (b,t,s) or (b,1,s); logits are (b,h,t,s)
        if mask.ndim == 2:
            mask = mask[None, None]
        elif mask.ndim == 3:
            mask = mask[:, None]
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return out


def _sdpa_grouped(q, k, v, mask, softcap: float):
    """Grouped-query attention for DECODE: q is reshaped to (b,t,kv,g,d) so
    the KV cache is read once, never expanded.  (Training uses `_sdpa`'s
    kv-expansion — see §Perf-1/§Perf-3: expansion is right when kv << t·d
    activations, wrong when the cache dominates, i.e. decode.)"""
    b, t, h, dk = q.shape
    kv = k.shape[2]
    g = h // kv
    q = q.reshape(b, t, kv, g, dk)
    logits = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(dk)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, None]
        elif mask.ndim == 3:
            mask = mask[:, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, h, dk)


def causal_mask(t: int, s: int, offset: int = 0, window: int = 0):
    """(t, s) boolean mask. q position i (global i+offset) sees kv j<=i+offset;
    with window>0 also j > i+offset-window."""
    qpos = jnp.arange(t)[:, None] + offset
    kpos = jnp.arange(s)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


Q_CHUNK = 512
CHUNK_THRESHOLD = 2048  # use q-chunked (flash-style) attention for t >= this


def _sdpa_chunked(q, k, v, softcap, causal, window, q_chunk=Q_CHUNK,
                  scale=None):
    """Memory-bounded attention: lax.scan over query chunks so the logits
    buffer is O(q_chunk · s) instead of O(t · s): the CPU path, and the TPU
    path for shapes the Pallas flash kernels do not take."""
    b, t, h, dk = q.shape
    s = k.shape[1]
    assert t % q_chunk == 0, (t, q_chunk)
    nc = t // q_chunk
    qc = jnp.moveaxis(q.reshape(b, nc, q_chunk, h, dk), 1, 0)

    def body(carry, xs):
        qi, ci = xs
        mask = causal_mask(q_chunk, s, offset=ci * q_chunk, window=window) \
            if (causal or window > 0) else None
        return carry, _sdpa(qi, k, v, mask, softcap, scale)

    body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nc) * 1))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def sdpa_self(q, k, v, *, causal, window, softcap, scale=None):
    """The jnp attention core of `attend_full` and `mla_full`: q-chunked
    from CHUNK_THRESHOLD positions on, else one score matrix.  v's width
    may differ from q's and k's; `scale` is 1/sqrt(q's width) by default."""
    t = q.shape[1]
    if t >= CHUNK_THRESHOLD and t % Q_CHUNK == 0:
        return _sdpa_chunked(q, k, v, softcap, causal, window, scale=scale)
    mask = causal_mask(t, t, 0, window) if causal else None
    return _sdpa(q, k, v, mask, softcap, scale)


def attend_full(params, x, positions, *, rope_theta, softcap=0.0, window=0,
                causal=True, qk_norm=False):
    """Self-attention over a full sequence (training / prefill)."""
    q, k, v = _project_qkv(params, x, positions, rope_theta, qk_norm)
    with jax.named_scope(spans.ATTENTION_CORE):
        out = ops.attention_core(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(x.dtype))
    return maybe_shard(out, "batch", "seq", "embed")


def cross_attend(params, x, kv_source, *, softcap=0.0):
    """Encoder-decoder cross attention; kv_source either hidden states
    (b,s,d) or a precomputed {"k","v"} cache."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    if isinstance(kv_source, dict):
        k, v = kv_source["k"].astype(x.dtype), kv_source["v"].astype(x.dtype)
    else:
        k = jnp.einsum("bsd,dhk->bshk", kv_source, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", kv_source, params["wv"].astype(x.dtype))
    out = _sdpa(q, k, v, None, softcap)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(x.dtype),
                     preferred_element_type=x.dtype)
    return out


def precompute_cross_kv(params, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    return {"k": k, "v": v}


# ------------------------------------------------------------- KV cache ----

def init_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int, dtype):
    """Full-length cache (decode_32k) or ring buffer (long_500k windowed mode —
    pass cache_len=window)."""
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
    }


def attend_decode(params, x, cache, pos, *, rope_theta, softcap=0.0,
                  ring: bool = False, qk_norm=False):
    """Single-token decode. x: (b,1,d); pos: scalar int32 global position,
    or a (b,) int32 vector of PER-ROW positions (continuous-batching serving:
    each cache row advances on its own timeline, writes its own slot, and
    masks its own prefix).  Returns (out, new_cache).  `ring=True` treats the
    cache as a circular sliding-window buffer of length cache_len."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, positions, rope_theta, qk_norm)
    cache_len = cache["k"].shape[1]
    slot = pos % cache_len if ring else pos
    if per_row:
        rows = jnp.arange(b)
        k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    else:
        k = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    kpos = jnp.arange(cache_len)
    ppos = pos[:, None] if per_row else pos   # broadcasts over (b, cache_len)
    if ring:
        # valid slots: all once pos>=cache_len-1, else slots <= pos
        valid = kpos <= jnp.maximum(ppos, cache_len - 1)
        valid &= (kpos <= ppos) | (ppos >= cache_len)
    else:
        valid = kpos <= ppos
    mask = valid[:, None, :] if per_row else valid[None, None, :]
    out = _sdpa_grouped(q, k.astype(x.dtype), v.astype(x.dtype), mask, softcap)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(x.dtype),
                     preferred_element_type=x.dtype)
    return out, {"k": k, "v": v}
