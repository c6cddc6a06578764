"""Pallas TPU kernel: blockwise flash attention (causal / sliding-window /
logit-softcap) — the TPU-native replacement for the jnp chunked attention in
`repro.models.attention`.

Grid: (batch·heads, q_blocks, kv_blocks), sequential minor-to-major on TPU, so
the kv_block axis is innermost and the online-softmax state (running max m,
denominator l, accumulator acc) lives in VMEM scratch across kv iterations:

    @ kv_block == 0:        init m = -inf, l = 0, acc = 0
    each kv_block:          s = q·kᵀ (softcap / mask) ; online-softmax update
    @ kv_block == last:     out = acc / l

Causality/window skip whole blocks via `pl.when` (no wasted MXU work on fully
masked blocks — this is the structural win over the jnp scan, which computes
every (q,kv) pair).  GQA: the kv index map divides the head index, so kv
blocks are read once per q-head group without materializing repeats.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, softcap, causal, window, block_q, block_kv, seq_len):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * block_q
    kv_start = kb * block_kv

    # block-level relevance: any (i,j) with j <= i and j > i - window?
    run = True
    if causal:
        run = jnp.logical_and(True, kv_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, kv_start + block_kv - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (block_q, d)
        k = k_ref[0].astype(jnp.float32)              # (block_kv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == nkv - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_kv: int = 256, interpret: bool | None = None):
    """q: (b, t, h, d); k/v: (b, s, kv_heads, d) with h % kv_heads == 0.
    Returns (b, t, h, d).  Softmax scale is 1/sqrt(d)."""
    b, t, h, d = q.shape
    s = k.shape[1]
    kvh = k.shape[2]
    assert h % kvh == 0
    group = h // kvh
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    assert t % block_q == 0
    pad_s = -(-s // block_kv) * block_kv
    if pad_s != s:
        k = jnp.pad(k, ((0, 0), (0, pad_s - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s - s), (0, 0), (0, 0)))

    # (b*h, t, d) layout; kv stays (b*kvh, s, d) and the index map folds GQA
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, pad_s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, pad_s, d)

    grid = (b * h, t // block_q, pad_s // block_kv)

    def q_map(bh, qb, kb):
        return (bh, qb, 0)

    def kv_map(bh, qb, kb):
        return ((bh // h) * kvh + (bh % h) // group, kb, 0)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), softcap=softcap, causal=causal,
        window=window, block_q=block_q, block_kv=block_kv, seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
