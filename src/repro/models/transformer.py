"""Decoder-only / encoder-decoder transformer stack.

Structure: `num_repeats` repeats of `cfg.block_pattern` are scanned with
`lax.scan` (per-pattern-position parameters stacked along a leading repeat
axis) so trace size is O(pattern), not O(layers) — essential for the 60-layer
dry-runs.  MoE models may keep the first `moe.first_dense` layers as
unscanned dense "prefix" blocks (DeepSeek-V2 keeps layer 0 dense).

Cross-entropy is computed in sequence chunks (`cfg.xent_chunk`) so the
(batch, seq, vocab) logits tensor is never materialized — with 256k vocabs the
full tensor would dominate HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import spans
from repro.models import blocks as blk
from repro.models.common import split_keys
from repro.models.config import ModelConfig
from repro.models.moe import MoEStats
from repro.models.embeddings import (
    init_embedding, embed_tokens, unembed, sinusoidal_positions, sinusoidal_at)
from repro.models.norms import init_norm, apply_norm
from repro.models.attention import init_attention, cross_attend, precompute_cross_kv
from repro.distributed.sharding import maybe_shard


# ------------------------------------------------------------------ init ----

def _layer_plan(cfg: ModelConfig):
    """(prefix_kinds, prefix_moe_flags, pattern_kinds, pattern_moe_flags, repeats)"""
    prefix_kinds = cfg.prefix_pattern
    pattern = cfg.block_pattern
    repeats = cfg.num_repeats
    prefix_moe = tuple(False for _ in prefix_kinds)   # prefix layers stay dense
    pattern_moe = tuple(cfg.moe is not None for _ in pattern)
    return prefix_kinds, prefix_moe, pattern, pattern_moe, repeats


def _init_one_block(key, cfg, kind, moe_layer):
    p = blk.init_block(key, cfg, kind, moe_layer)
    if cfg.encoder is not None:  # decoder cross-attention sub-layer
        kc, kn = split_keys(key, 2)
        p["cross_norm"] = init_norm(kn, cfg.d_model, cfg.norm_kind, cfg.p_dtype)
        p["cross_attn"] = init_attention(
            kc, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.p_dtype)
    return p


def init_params(key, cfg: ModelConfig):
    prefix_kinds, prefix_moe, pattern, pattern_moe, repeats = _layer_plan(cfg)
    k_embed, k_prefix, k_blocks, k_norm, k_unembed, k_enc = split_keys(key, 6)
    params = {"embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model, cfg.p_dtype)}

    if prefix_kinds:
        keys = split_keys(k_prefix, len(prefix_kinds))
        params["prefix_blocks"] = [
            _init_one_block(k, cfg, kind, m)
            for k, kind, m in zip(keys, prefix_kinds, prefix_moe)
        ]

    stacked = []
    pos_keys = split_keys(k_blocks, len(pattern))
    for pk, kind, moe_layer in zip(pos_keys, pattern, pattern_moe):
        rep_keys = jnp.stack(split_keys(pk, repeats))
        stacked.append(jax.vmap(
            lambda kk: _init_one_block(kk, cfg, kind, moe_layer))(rep_keys))
    params["blocks"] = stacked

    params["final_norm"] = init_norm(k_norm, cfg.d_model, cfg.norm_kind, cfg.p_dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(k_unembed, cfg.vocab_size, cfg.d_model, cfg.p_dtype)

    if cfg.encoder is not None:
        enc_keys = split_keys(k_enc, cfg.encoder.num_layers + 1)
        params["encoder"] = {
            "blocks": [blk.init_block(k, cfg, "attn", False) for k in enc_keys[:-1]],
            "final_norm": init_norm(enc_keys[-1], cfg.d_model, cfg.norm_kind, cfg.p_dtype),
        }
    return params


# --------------------------------------------------------------- encoder ----

def encode(params, frames, cfg: ModelConfig):
    """Whisper-style encoder over stub frame embeddings (b, nf, d)."""
    nf = frames.shape[1]
    x = frames + sinusoidal_positions(nf, cfg.d_model, frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(nf, dtype=jnp.int32), frames.shape[:2])
    for p in params["encoder"]["blocks"]:
        x, _, _ = blk.block_full(p, x, positions, cfg, "attn", False, causal=False)
    x = apply_norm(params["encoder"]["final_norm"], x, cfg.norm_kind)
    return x


# ----------------------------------------------------------------- stack ----

def _apply_cross(p, x, enc, cfg):
    if enc is not None and "cross_attn" in p:
        h = apply_norm(p["cross_norm"], x, cfg.norm_kind)
        x = x + cross_attend(p["cross_attn"], h, enc)
    return x


def run_stack(params, x, positions, cfg: ModelConfig, enc=None, collect_cache=False):
    """Run prefix + scanned blocks. Returns (hidden, MoEStats over the
    layers, caches|None)."""
    prefix_kinds, prefix_moe, pattern, pattern_moe, repeats = _layer_plan(cfg)
    aux_total = MoEStats.zeros()
    prefix_caches = []
    for p, kind, moe_layer in zip(params.get("prefix_blocks", []), prefix_kinds, prefix_moe):
        x, aux, cache = blk.block_full(p, x, positions, cfg, kind, moe_layer,
                                       collect_cache=collect_cache)
        x = _apply_cross(p, x, enc, cfg)
        aux_total = aux_total.merge(aux)
        prefix_caches.append(cache)

    def body(carry, layer_params):
        x, aux_total = carry
        caches = []
        for p, kind, moe_layer in zip(layer_params, pattern, pattern_moe):
            x, aux, cache = blk.block_full(p, x, positions, cfg, kind, moe_layer,
                                           collect_cache=collect_cache)
            x = _apply_cross(p, x, enc, cfg)
            aux_total = aux_total.merge(aux)
            caches.append(cache)
        out = tuple(caches) if collect_cache else None
        return (x, aux_total), out

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    elif cfg.remat == "tp_boundary":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names("tp_out"))

    if cfg.scan_layers:
        (x, aux_total), scanned_caches = jax.lax.scan(
            body, (x, aux_total), tuple(params["blocks"]))
    else:
        outs = []
        for r in range(repeats):
            layer_params = tuple(jax.tree.map(lambda a: a[r], blk_p)
                                 for blk_p in params["blocks"])
            (x, aux_total), out = body((x, aux_total), layer_params)
            outs.append(out)
        scanned_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                          if collect_cache else None)
    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    caches = None
    if collect_cache:
        caches = {"prefix": prefix_caches, "scanned": scanned_caches}
    return x, aux_total, caches


# ------------------------------------------------------------------ loss ----

def _logits(params, hidden, cfg: ModelConfig):
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    src = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(src, hidden, tied_table=tied)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def _xent(logits, labels):
    """Cross entropy with label -1 == masked. Returns (sum_loss, count)."""
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll), jnp.sum(mask)


@spans.scoped(spans.HEAD)
def token_loss(params, hidden, labels, cfg: ModelConfig):
    """Chunked softmax cross-entropy over the sequence axis."""
    chunk = cfg.xent_chunk
    t = hidden.shape[1]
    if chunk <= 0 or t <= chunk or t % chunk != 0:
        logits = _logits(params, hidden, cfg)
        s, c = _xent(logits, labels)
        return s / jnp.maximum(c, 1)

    nch = t // chunk
    h = hidden.reshape(hidden.shape[0], nch, chunk, -1).swapaxes(0, 1)
    l = labels.reshape(labels.shape[0], nch, chunk).swapaxes(0, 1)

    def body(carry, xs):
        s, c = carry
        hc, lc = xs
        logits = _logits(params, hc, cfg)
        ds, dc = _xent(logits, lc)
        return (s + ds, c + dc), None

    body = jax.checkpoint(body)
    (s, c), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2, (h, l))
    return s / jnp.maximum(c, 1)


# ------------------------------------------------------------- model API ----

def _assemble_inputs(batch, params, cfg: ModelConfig):
    """Embed tokens and any stub-frontend embeddings. Returns (x, positions,
    label_offset, enc)."""
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg.scale_embed, cfg.d_model)
    x = x.astype(cfg.act_dtype)
    enc = None
    offset = 0
    if cfg.frontend.kind == "vision_stub":
        patches = batch["patch_embeds"].astype(cfg.act_dtype)   # (b, np, d)
        x = jnp.concatenate([patches, x], axis=1)
        offset = patches.shape[1]
    elif cfg.frontend.kind == "audio_stub":
        enc = encode(params, batch["frames"].astype(cfg.act_dtype), cfg)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if cfg.pos_embed == "sinusoidal":
        x = x + sinusoidal_positions(t, cfg.d_model, x.dtype)[None]
    x = maybe_shard(x, "batch", "seq", "embed")
    return x, positions, offset, enc


def forward(params, batch, cfg: ModelConfig):
    """Full forward -> (hidden, aux, offset, enc)."""
    x, positions, offset, enc = _assemble_inputs(batch, params, cfg)
    hidden, aux, _ = run_stack(params, x, positions, cfg, enc=enc)
    return hidden, aux, offset, enc


def loss_fn(params, batch, cfg: ModelConfig):
    """Mean next-token cross entropy (+ MoE aux). labels use -1 as mask.
    A model with expert layers also reports the dispatch counters
    (`spans.COUNTERS`) of the call."""
    hidden, stats, offset, _ = forward(params, batch, cfg)
    labels = batch["labels"]
    if offset:  # VLM: prefix patch positions carry no labels
        pad = jnp.full((labels.shape[0], offset), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    loss = token_loss(params, hidden, labels, cfg)
    metrics = {"xent": loss, "aux": stats.aux}
    if cfg.moe is not None:
        metrics[spans.EXPERT_ROWS] = stats.rows
        metrics[spans.EXPERT_BUFFER_ROWS] = stats.buffer_rows
        metrics[spans.EXPERT_LOAD_MAX] = stats.load_max
    return loss + stats.aux, metrics


def prefill(params, batch, cfg: ModelConfig):
    """Prefill for serving: returns (last_token_logits, caches, enc_cross_kv)."""
    x, positions, offset, enc = _assemble_inputs(batch, params, cfg)
    hidden, _, caches = run_stack(params, x, positions, cfg, enc=enc, collect_cache=True)
    logits = _logits(params, hidden[:, -1:, :], cfg)
    return logits[:, 0], caches


# ------------------------------------------------------------- decoding ----

def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int, ring: bool = False):
    """Fresh decode cache pytree.  ring=True (long_500k serving mode) bounds
    full-attention caches to cfg.long_context_window."""
    prefix_kinds, _, pattern, _, repeats = _layer_plan(cfg)
    dtype = cfg.act_dtype

    def one(kind):
        length = cache_len
        if ring and kind in ("attn", "mla"):
            length = min(cache_len, cfg.long_context_window)
        return blk.init_block_cache(cfg, kind, batch, length, dtype)

    cache = {
        "prefix": [one(k) for k in prefix_kinds],
        "scanned": [
            jax.tree.map(lambda x: jnp.broadcast_to(x, (repeats,) + x.shape), one(kind))
            for kind in pattern
        ],
    }
    if cfg.encoder is not None:
        # cross-attention K/V per decoder layer (prefix + scanned)
        nf = cfg.encoder.num_frames
        kv = lambda: {
            "k": jnp.zeros((batch, nf, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, nf, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
        cache["cross_prefix"] = [kv() for _ in prefix_kinds]
        cache["cross_scanned"] = [
            jax.tree.map(lambda x: jnp.broadcast_to(x, (repeats,) + x.shape), kv())
            for _ in pattern
        ]
    return cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, ring: bool = False):
    """One decode step. tokens: (b,) int32; pos: scalar int32 global position
    or a (b,) int32 vector of per-row positions (continuous-batching serving).
    Returns (logits (b, vocab), new_cache)."""
    prefix_kinds, prefix_moe, pattern, pattern_moe, repeats = _layer_plan(cfg)
    x = embed_tokens(params["embed"], tokens[:, None], cfg.scale_embed, cfg.d_model)
    x = x.astype(cfg.act_dtype)
    if cfg.pos_embed == "sinusoidal":
        emb = sinusoidal_at(jnp.asarray(pos), cfg.d_model, x.dtype)
        x = x + (emb[:, None, :] if emb.ndim == 2 else emb[None, None, :])

    new_prefix = []
    for i, (p, kind, moe_layer) in enumerate(
            zip(params.get("prefix_blocks", []), prefix_kinds, prefix_moe)):
        x, _, c = blk.block_decode(p, x, cache["prefix"][i], pos, cfg, kind,
                                   moe_layer, ring=ring)
        if "cross_prefix" in cache and "cross_attn" in p:
            h = apply_norm(p["cross_norm"], x, cfg.norm_kind)
            x = x + cross_attend(p["cross_attn"], h, cache["cross_prefix"][i])
        new_prefix.append(c)

    def body(x, xs):
        layer_params, layer_caches, cross_caches = xs
        new_caches = []
        for j, (p, kind, moe_layer) in enumerate(zip(layer_params, pattern, pattern_moe)):
            x, _, c = blk.block_decode(p, x, layer_caches[j], pos, cfg, kind,
                                       moe_layer, ring=ring)
            if cross_caches is not None and "cross_attn" in p:
                h = apply_norm(p["cross_norm"], x, cfg.norm_kind)
                x = x + cross_attend(p["cross_attn"], h, cross_caches[j])
            new_caches.append(c)
        return x, tuple(new_caches)

    has_cross = "cross_scanned" in cache
    if cfg.scan_layers:
        if has_cross:
            xs = (tuple(params["blocks"]), tuple(cache["scanned"]),
                  tuple(cache["cross_scanned"]))
            x, new_scanned = jax.lax.scan(body, x, xs)
        else:
            x, new_scanned = jax.lax.scan(
                lambda xx, ys: body(xx, (ys[0], ys[1], None)),
                x, (tuple(params["blocks"]), tuple(cache["scanned"])))
    else:
        outs = []
        for r in range(repeats):
            lp = tuple(jax.tree.map(lambda a: a[r], bp) for bp in params["blocks"])
            lc = tuple(jax.tree.map(lambda a: a[r], bc) for bc in cache["scanned"])
            cc = (tuple(jax.tree.map(lambda a: a[r], xc)
                        for xc in cache["cross_scanned"]) if has_cross else None)
            x, out = body(x, (lp, lc, cc))
            outs.append(out)
        new_scanned = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    logits = _logits(params, x, cfg)[:, 0]
    new_cache = dict(cache)
    new_cache["prefix"] = new_prefix
    new_cache["scanned"] = list(new_scanned)
    return logits, new_cache
