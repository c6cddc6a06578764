"""Pallas TPU kernels: blockwise flash attention (causal / sliding-window /
logit-softcap) with its backward — the TPU path of
`repro.kernels.ops.attention_core`.

Layout: q as (batch·heads, t, d), k as (batch·kv_heads, s, d) and v as
(batch·kv_heads, s, d_v); the value width may differ from the query/key
width (MLA: 192 and 128).  GQA folds into the index maps, so kv blocks are
read once per q-head group without materialising repeats.

Forward, grid (batch·heads, q_blocks, kv_blocks), kv innermost.  The
online-softmax state (running max m, denominator l, accumulator acc) lives
in VMEM scratch across kv iterations; the last one writes the output and
the per-row logsumexp `lse = m + log l` (f32), the backward's residual.

Backward, from `lse` and `D = rowsum(dO·O)`, P recomputed blockwise:
  * dK/dV kernel, grid (batch·kv_heads, kv_blocks, group·q_blocks): for
    one kv block it runs over every q block of every q head of its group,
    so dK and dV come out summed over the group.  It works on transposed
    scores (kv rows, q columns), so `lse` and `D` enter as rows.
  * dQ kernel, grid (batch·heads, q_blocks, kv_blocks).
Softcap's derivative is 1 - (s/cap)² of the capped score s.

Causality and window skip whole blocks (`pl.when`), and the index maps
clamp a skipped step's block to one the grid fetches anyway, so a skipped
step does no DMA (as `jax.experimental.pallas.ops.tpu.flash_attention`
does).  Only blocks that cross the diagonal, the window's edge or the
padded tail build a mask.

Precision: compiled, f32 operands enter the MXU as bf16 with f32
accumulation — one bf16 pass, what XLA's default precision does for the
jnp path's f32 einsums on TPU; m, l, exp, the accumulators and `lse` stay
f32.  In interpret mode everything stays f32, like XLA's dot on CPU.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, resolve_interpret

NEG_INF = -2.0e38
# every grid: two independent axes, then the one the scratch accumulates over
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """One call's static configuration (hashable: the custom_vjp's
    non-differentiable argument)."""
    causal: bool
    window: int
    softcap: float
    scale: float
    block_q: int
    block_kv: int
    nq: int
    nkv: int
    seq_len: int          # keys before padding
    heads: int
    kv_heads: int
    mxu_dtype: object     # operand dtype of the kernels' matmuls
    dtypes: tuple         # of q, k and v as the caller passed them
    interpret: bool

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads


# ------------------------------------------------------------ block ranges --

def _kv_range(qb, c: _Cfg):
    """First and last kv block that q block `qb` attends to."""
    lo, hi = 0, c.nkv - 1
    q_start = qb * c.block_q
    if c.causal:
        hi = jnp.minimum(hi, (q_start + c.block_q - 1) // c.block_kv)
    if c.window > 0:
        lo = jnp.maximum(q_start - c.window + 1, 0) // c.block_kv
    return lo, hi


def _q_range(kb, c: _Cfg):
    """First and last q block that attends to kv block `kb`."""
    lo, hi = 0, c.nq - 1
    kv_start = kb * c.block_kv
    if c.causal:
        lo = kv_start // c.block_q
    if c.window > 0:
        hi = jnp.minimum(hi, (kv_start + c.block_kv - 2 + c.window)
                         // c.block_q)
    return lo, hi


def _needs_mask(qb, kb, c: _Cfg):
    """Whether block (qb, kb) holds a masked pair: it crosses the diagonal,
    the window's edge or the padded keys."""
    q_start, kv_start = qb * c.block_q, kb * c.block_kv
    q_end, kv_end = q_start + c.block_q, kv_start + c.block_kv
    need = kv_end > c.seq_len
    if c.causal:
        need = jnp.logical_or(need, kv_end - 1 > q_start)
    if c.window > 0:
        need = jnp.logical_or(need, kv_start <= q_end - 1 - c.window)
    return need


def _run_blocks(body, qb, kb, lo, hi, idx, c: _Cfg):
    """Run `body(masked)` where lo <= idx <= hi: masked where the block
    needs a mask, plain elsewhere."""
    run = jnp.logical_and(idx >= lo, idx <= hi)
    need = _needs_mask(qb, kb, c)

    @pl.when(jnp.logical_and(run, need))
    def _masked():
        body(True)

    @pl.when(jnp.logical_and(run, jnp.logical_not(need)))
    def _plain():
        body(False)


def _scores(a, b, q_start, kv_start, c: _Cfg, masked: bool, q_rows: bool):
    """Scaled, capped and masked scores a·bᵀ (f32) and the capped tanh (or
    None).  q_rows: `a` is the q block (rows are queries); else the kv
    block (rows are keys, the transposed scores)."""
    s = _dot_t(a, b) * c.scale
    cap = None
    if c.softcap > 0:
        cap = jnp.tanh(s / c.softcap)
        s = c.softcap * cap
    if masked:
        qax, kax = (0, 1) if q_rows else (1, 0)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, qax)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, kax)
        mask = kpos < c.seq_len
        if c.causal:
            mask &= kpos <= qpos
        if c.window > 0:
            mask &= kpos > qpos - c.window
        s = jnp.where(mask, s, NEG_INF)
    return s, cap


def _row(col):
    """A (n, 1) column as a (1, n) row (an aligned 2-D transpose)."""
    return jnp.broadcast_to(col, (col.shape[0], LANE)).T[:1]


def _col(row):
    """A (1, n) row as a (n, 1) column."""
    return jnp.broadcast_to(row, (LANE, row.shape[1])).T[:, :1]


def _dot(a, b):
    """a·b, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a·bᵀ, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# index maps of the (batch·heads, q_blocks, kv_blocks) grids
def _q_map(i, qb, kb):
    return (i, qb, 0)


def _row_map(i, qb, kb):
    return (i, 0, qb)


def _kv_map(c: _Cfg):
    """The q head's kv head, and the kv block clamped to the range the q
    block attends to, so a skipped step refetches nothing."""
    def index(i, qb, kb):
        lo, hi = _kv_range(qb, c)
        return ((i // c.heads) * c.kv_heads + (i % c.heads) // c.group,
                jnp.clip(kb, lo, hi), 0)
    return index


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, c: _Cfg):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s, _ = _scores(q_ref[0], k_ref[0], qb * c.block_q, kb * c.block_kv,
                       c, masked, q_rows=True)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[...] = corr * acc_scr[...] + _dot(p.astype(v.dtype), v)
        m_scr[...] = m_new

    lo, hi = _kv_range(qb, c)
    _run_blocks(body, qb, kb, lo, hi, kb, c)

    @pl.when(kb == c.nkv - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = _row(m_scr[...] + jnp.log(l))


def _operands(qf, kf, vf, c: _Cfg):
    return tuple(x.astype(c.mxu_dtype) for x in (qf, kf, vf))


def _forward(q, k, v, c: _Cfg):
    """(o, lse) of (batch·heads, t, d) q against (batch·kv_heads, s, d) k
    and (batch·kv_heads, s, d_v) v, all in the MXU dtype; o is
    (batch·heads, t, d_v) in q's dtype as the caller passed it, lse is
    (batch·heads, 1, t) f32."""
    bh, t, d = q.shape
    d_v = v.shape[-1]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c),
        name="flash_attention_fwd",
        grid=(bh, c.nq, c.nkv),
        in_specs=[
            pl.BlockSpec((1, c.block_q, d), _q_map),
            pl.BlockSpec((1, c.block_kv, d), _kv_map(c)),
            pl.BlockSpec((1, c.block_kv, d_v), _kv_map(c)),
        ],
        out_specs=[
            pl.BlockSpec((1, c.block_q, d_v), _q_map),
            pl.BlockSpec((1, 1, c.block_q), _row_map),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d_v), c.dtypes[0]),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((c.block_q, 1), jnp.float32),
            pltpu.VMEM((c.block_q, 1), jnp.float32),
            pltpu.VMEM((c.block_q, d_v), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=c.interpret,
    )(q, k, v)


# --------------------------------------------------------------- backward --

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, c: _Cfg):
    kb, j = pl.program_id(1), pl.program_id(2)
    qb = j % c.nq

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0], do_ref[0]
        st, cap = _scores(k_ref[0], q, qb * c.block_q, kb * c.block_kv, c,
                          masked, q_rows=False)
        pt = jnp.exp(st - lse_ref[0])                    # (kv, q)
        dv_scr[...] += _dot(pt.astype(do.dtype), do)
        dpt = _dot_t(v_ref[0], do)
        dst = pt * (dpt - di_ref[0])
        if cap is not None:
            dst = dst * (1.0 - cap * cap)
        dk_scr[...] += _dot(dst.astype(q.dtype), q)

    lo, hi = _q_range(kb, c)
    _run_blocks(body, qb, kb, lo, hi, qb, c)

    @pl.when(j == c.group * c.nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * c.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               lse_scr, di_scr, *, c: _Cfg):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _col(lse_ref[0])
        di_scr[...] = _col(di_ref[0])

    def body(masked):
        k = k_ref[0]
        s, cap = _scores(q_ref[0], k, qb * c.block_q, kb * c.block_kv, c,
                         masked, q_rows=True)
        p = jnp.exp(s - lse_scr[...])                     # (q, kv)
        dp = _dot_t(do_ref[0], v_ref[0])
        ds = p * (dp - di_scr[...])
        if cap is not None:
            ds = ds * (1.0 - cap * cap)
        dq_scr[...] += _dot(ds.astype(k.dtype), k)

    lo, hi = _kv_range(qb, c)
    _run_blocks(body, qb, kb, lo, hi, kb, c)

    @pl.when(kb == c.nkv - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * c.scale).astype(dq_ref.dtype)


def _backward(c: _Cfg, res, do):
    q, k, v, o, lse = res
    bh, t, d = q.shape
    bkvh, s, _ = k.shape
    d_v = v.shape[-1]
    h, kvh, g = c.heads, c.kv_heads, c.group
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = di.reshape(bh, 1, t)
    dom = do.astype(c.mxu_dtype)

    # dK/dV: kv block outer, (group member, q block) inner
    def qh(i, j):
        return (i // kvh) * h + (i % kvh) * g + j // c.nq

    def qb_dkv(kb, j):
        lo, hi = _q_range(kb, c)
        return jnp.clip(j % c.nq, lo, hi)

    def q_dkv(i, kb, j):
        return (qh(i, j), qb_dkv(kb, j), 0)

    def row_dkv(i, kb, j):
        return (qh(i, j), 0, qb_dkv(kb, j))

    def kv_dkv(i, kb, j):
        return (i, kb, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, c=c),
        name="flash_attention_dkv",
        grid=(bkvh, c.nkv, g * c.nq),
        in_specs=[
            pl.BlockSpec((1, c.block_q, d), q_dkv),
            pl.BlockSpec((1, c.block_kv, d), kv_dkv),
            pl.BlockSpec((1, c.block_kv, d_v), kv_dkv),
            pl.BlockSpec((1, c.block_q, d_v), q_dkv),
            pl.BlockSpec((1, 1, c.block_q), row_dkv),
            pl.BlockSpec((1, 1, c.block_q), row_dkv),
        ],
        out_specs=[pl.BlockSpec((1, c.block_kv, d), kv_dkv),
                   pl.BlockSpec((1, c.block_kv, d_v), kv_dkv)],
        out_shape=[jax.ShapeDtypeStruct((bkvh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bkvh, s, d_v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((c.block_kv, d), jnp.float32),
                        pltpu.VMEM((c.block_kv, d_v), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=c.interpret,
    )(q, k, v, dom, lse, di)

    # dQ: q block outer, kv block inner
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, c=c),
        name="flash_attention_dq",
        grid=(bh, c.nq, c.nkv),
        in_specs=[
            pl.BlockSpec((1, c.block_q, d), _q_map),
            pl.BlockSpec((1, c.block_kv, d), _kv_map(c)),
            pl.BlockSpec((1, c.block_kv, d_v), _kv_map(c)),
            pl.BlockSpec((1, c.block_q, d_v), _q_map),
            pl.BlockSpec((1, 1, c.block_q), _row_map),
            pl.BlockSpec((1, 1, c.block_q), _row_map),
        ],
        out_specs=pl.BlockSpec((1, c.block_q, d), _q_map),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c.block_q, d), jnp.float32),
                        pltpu.VMEM((c.block_q, 1), jnp.float32),
                        pltpu.VMEM((c.block_q, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=c.interpret,
    )(q, k, v, dom, lse, di)
    return dq, dk, dv


# ------------------------------------------------------------- custom_vjp --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(qf, kf, vf, c: _Cfg):
    return _forward(*_operands(qf, kf, vf, c), c)[0]


def _attention_fwd(qf, kf, vf, c: _Cfg):
    q, k, v = _operands(qf, kf, vf, c)
    o, lse = _forward(q, k, v, c)
    return o, (q, k, v, o, lse)


def _attention_bwd(c: _Cfg, res, do):
    grads = _backward(c, res, do)
    return tuple(g.astype(dt) for g, dt in zip(grads, c.dtypes))


_attention.defvjp(_attention_fwd, _attention_bwd)


def _flat(q, k, v, causal, window, softcap, block_q, block_kv, interpret,
          scale=None):
    """q/k/v in the kernels' layout, padded keys, and their config."""
    b, t, h, d = q.shape
    d_v = v.shape[-1]
    s, kvh = k.shape[1], k.shape[2]
    if h % kvh:
        raise ValueError(f"{h} query heads over {kvh} kv heads")
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    if t % block_q:
        raise ValueError(f"t={t} is not a multiple of block_q={block_q}")
    pad_s = -(-s // block_kv) * block_kv
    if pad_s != s:
        k = jnp.pad(k, ((0, 0), (0, pad_s - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s - s), (0, 0), (0, 0)))
    ip = resolve_interpret(interpret)
    mxu = (jnp.float32 if ip else
           jnp.bfloat16 if q.dtype == jnp.float32 else q.dtype)
    c = _Cfg(causal=causal, window=window, softcap=softcap,
             scale=1.0 / math.sqrt(d) if scale is None else scale,
             block_q=block_q, block_kv=block_kv,
             nq=t // block_q, nkv=pad_s // block_kv, seq_len=s, heads=h,
             kv_heads=kvh, mxu_dtype=mxu,
             dtypes=(q.dtype, k.dtype, v.dtype), interpret=ip)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, pad_s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, pad_s, d_v)
    return qf, kf, vf, c


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    block_q: int = 1024, block_kv: int = 1024,
                    interpret: bool | None = None):
    """q: (b, t, h, d); k: (b, s, kv_heads, d); v: (b, s, kv_heads, d_v)
    with h % kv_heads == 0.  Returns (b, t, h, d_v), differentiable in q,
    k and v.  Softmax scale `scale`, 1/sqrt(d) by default."""
    b, t, h, d = q.shape
    qf, kf, vf, c = _flat(q, k, v, causal, window, softcap, block_q,
                          block_kv, interpret, scale)
    out = _attention(qf, kf, vf, c)
    return out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)

