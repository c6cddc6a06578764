"""Decoder block: dispatch over layer kinds (attn/local/mla/rglru/ssd),
pre/post norms, dense-MLP or MoE feed-forward, residuals.

Every block exposes:
  * block_full(params, x, positions, cfg, kind, moe_layer, collect_cache)
        -> (x, MoEStats, cache | None)        # training / prefill
  * block_decode(params, x, cache, pos, cfg, kind, moe_layer, ring)
        -> (x, MoEStats, new_cache)           # single-token serving
(`MoEStats` of an expert layer — balance loss and dispatch counters — or
zeros.)
  * init_block / init_block_cache
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

from repro import spans
from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import rglru as rglru_lib
from repro.models import ssd as ssd_lib
from repro.models.config import ModelConfig, ATTN, LOCAL_ATTN, MLA_ATTN, RGLRU, SSD
from repro.models.mlp import init_mlp, apply_mlp
from repro.models.moe import MoEStats, init_moe, moe_apply
from repro.models.norms import init_norm, apply_norm
from repro.models.common import split_keys
from repro.distributed.sharding import maybe_shard


def _has_mlp(cfg: ModelConfig, kind: str) -> bool:
    return cfg.mlp_kind != "none" and kind != SSD


def init_block(key, cfg: ModelConfig, kind: str, moe_layer: bool):
    k_attn, k_mlp, k_n1, k_n2, k_n3, k_n4 = split_keys(key, 6)
    d, dtype = cfg.d_model, cfg.p_dtype
    p = {"pre_norm": init_norm(k_n1, d, cfg.norm_kind, dtype)}
    if kind in (ATTN, LOCAL_ATTN):
        p["attn"] = attn_lib.init_attention(
            k_attn, d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, dtype)
    elif kind == MLA_ATTN:
        p["attn"] = mla_lib.init_mla(k_attn, d, cfg.num_heads, cfg.mla, dtype)
    elif kind == RGLRU:
        p["rec"] = rglru_lib.init_rglru(k_attn, d, cfg.rglru, dtype)
    elif kind == SSD:
        p["ssd"] = ssd_lib.init_ssd(k_attn, d, cfg.ssm, dtype)
    else:
        raise ValueError(kind)
    if cfg.post_attn_norm:
        p["post_norm"] = init_norm(k_n2, d, cfg.norm_kind, dtype)
    if _has_mlp(cfg, kind):
        p["mlp_norm"] = init_norm(k_n3, d, cfg.norm_kind, dtype)
        if moe_layer:
            p["mlp"] = init_moe(k_mlp, d, cfg.moe, cfg.mlp_kind, dtype)
        else:
            p["mlp"] = init_mlp(k_mlp, d, cfg.d_ff, cfg.mlp_kind, dtype)
        if cfg.post_attn_norm:
            p["post_mlp_norm"] = init_norm(k_n4, d, cfg.norm_kind, dtype)
    return p


def _mixer_full(params, x, positions, cfg: ModelConfig, kind: str,
                collect_cache: bool, causal: bool = True):
    """Sequence mixer (attention or recurrence) over a full sequence."""
    cache = None
    rope_theta = cfg.rope_theta if cfg.pos_embed == "rope" else 0.0
    if kind in (ATTN, LOCAL_ATTN):
        window = cfg.sliding_window if kind == LOCAL_ATTN else 0
        with jax.named_scope(spans.ATTENTION):
            out = attn_lib.attend_full(
                params["attn"], x, positions, rope_theta=rope_theta,
                softcap=cfg.attn_logit_softcap, window=window, causal=causal,
                qk_norm=cfg.qk_norm)
        if collect_cache:
            q, k, v = attn_lib._project_qkv(
                params["attn"], x, positions, rope_theta, cfg.qk_norm)
            cache = {"k": k, "v": v}
    elif kind == MLA_ATTN:
        with jax.named_scope(spans.ATTENTION):
            out = mla_lib.mla_full(params["attn"], x, positions, cfg.mla,
                                   cfg.rope_theta, causal=causal)
        if collect_cache:
            c_kv, k_rope = mla_lib._latents(params["attn"], x, positions,
                                            cfg.mla, cfg.rope_theta)
            cache = {"c_kv": c_kv, "k_rope": k_rope}
    elif kind == RGLRU:
        out = rglru_lib.rglru_block(params["rec"], x, cfg.rglru)
        # (prefill state collection for RG-LRU is handled by the decode path)
    elif kind == SSD:
        out = ssd_lib.ssd_block(params["ssd"], x, cfg.ssm)
    else:
        raise ValueError(kind)
    return out, cache


def block_full(params, x, positions, cfg: ModelConfig, kind: str,
               moe_layer: bool, collect_cache: bool = False, causal: bool = True):
    stats = MoEStats.zeros()
    h = apply_norm(params["pre_norm"], x, cfg.norm_kind)
    mixed, cache = _mixer_full(params, h, positions, cfg, kind, collect_cache, causal)
    if cfg.post_attn_norm:
        mixed = apply_norm(params["post_norm"], mixed, cfg.norm_kind)
    # TP boundary: `mixed` is the post-all-reduce output of the row-parallel
    # projection.  Under remat="tp_boundary" these named tensors are saved so
    # the backward recompute never re-runs the forward all-reduces (§Perf-1.3).
    mixed = checkpoint_name(mixed, "tp_out")
    mixed = maybe_shard(mixed, "batch", "act_seq", "embed")
    x = x + mixed
    if _has_mlp(cfg, kind):
        h = apply_norm(params["mlp_norm"], x, cfg.norm_kind)
        if moe_layer:
            h, stats = moe_apply(params["mlp"], h, cfg.moe)
        else:
            h = apply_mlp(params["mlp"], h, cfg.mlp_kind)
        if cfg.post_attn_norm:
            h = apply_norm(params["post_mlp_norm"], h, cfg.norm_kind)
        h = checkpoint_name(h, "tp_out")
        h = maybe_shard(h, "batch", "act_seq", "embed")
        x = x + h
    return x, stats, cache


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int, dtype):
    if kind in (ATTN, LOCAL_ATTN):
        length = min(cache_len, cfg.sliding_window) if kind == LOCAL_ATTN else cache_len
        return attn_lib.init_cache(batch, length, cfg.num_kv_heads, cfg.head_dim, dtype)
    if kind == MLA_ATTN:
        return mla_lib.init_mla_cache(batch, cache_len, cfg.mla, dtype)
    if kind == RGLRU:
        return rglru_lib.init_rglru_state(batch, cfg.d_model, cfg.rglru, dtype)
    if kind == SSD:
        return ssd_lib.init_ssd_state(batch, cfg.d_model, cfg.ssm, dtype)
    raise ValueError(kind)


def block_decode(params, x, cache, pos, cfg: ModelConfig, kind: str,
                 moe_layer: bool, ring: bool = False):
    stats = MoEStats.zeros()
    h = apply_norm(params["pre_norm"], x, cfg.norm_kind)
    if kind in (ATTN, LOCAL_ATTN):
        # local-attn caches are rings by construction (length == window)
        is_ring = ring or kind == LOCAL_ATTN
        rope_theta = cfg.rope_theta if cfg.pos_embed == "rope" else 0.0
        mixed, new_cache = attn_lib.attend_decode(
            params["attn"], h, cache, pos, rope_theta=rope_theta,
            softcap=cfg.attn_logit_softcap, ring=is_ring, qk_norm=cfg.qk_norm)
    elif kind == MLA_ATTN:
        mixed, new_cache = mla_lib.mla_decode(params["attn"], h, cache, pos,
                                              cfg.mla, cfg.rope_theta,
                                              ring=ring)
    elif kind == RGLRU:
        mixed, new_cache = rglru_lib.rglru_decode(params["rec"], h, cache, cfg.rglru)
    elif kind == SSD:
        mixed, new_cache = ssd_lib.ssd_decode(params["ssd"], h, cache, cfg.ssm)
    else:
        raise ValueError(kind)
    if cfg.post_attn_norm:
        mixed = apply_norm(params["post_norm"], mixed, cfg.norm_kind)
    x = x + mixed
    if _has_mlp(cfg, kind):
        h = apply_norm(params["mlp_norm"], x, cfg.norm_kind)
        if moe_layer:
            h, stats = moe_apply(params["mlp"], h, cfg.moe)
        else:
            h = apply_mlp(params["mlp"], h, cfg.mlp_kind)
        if cfg.post_attn_norm:
            h = apply_norm(params["post_mlp_norm"], h, cfg.norm_kind)
        x = x + h
    return x, stats, new_cache
