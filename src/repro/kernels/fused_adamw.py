"""Pallas TPU kernel: fused AdamW update (Algorithm 1's optimizer block).

One pass over (p, g, m, v) tiles in VMEM producing (p', m', v') — instead of
the ~10 separate elementwise HLO ops (each an HBM round-trip) XLA emits for
the unfused update.  Scalar step state (lr, the bias corrections c1/c2, and
the global-norm clip scale, all of which change every step) arrives as a
(1, 4) f32 operand broadcast to every grid step; the static hyperparameters
are closure constants.

Two entry points:

* `fused_adamw`       — the original per-tensor update (p', m', v').
* `fused_adamw_stats` — the flat-buffer path (DESIGN §9): same update over
  one dtype-homogeneous buffer, consuming a traced `clip_scale` and emitting
  **Σg² of the raw gradient as a kernel byproduct** (one lane-aligned
  (8, 128) f32 partial tile per block), so the ACCUM-NORM statistic and
  the `grad_norm` metric cost zero extra passes over gradient-sized data.

Both stream 1-D blocks of the flattened operands: no padded or reshaped
copy of a model-sized buffer is made around the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import (flat_grid, flat_spec, mask_tail, partial_shape,
                           partial_spec, resolve_interpret, tile_partial,
                           tiles)

DEFAULT_BLOCK_ROWS = 256     # 256×128-element blocks of the flat operands


def _update(g, p_ref, m_ref, v_ref, scalars_ref, *, beta1, beta2, eps,
            weight_decay):
    lr = scalars_ref[0, 0]
    c1 = scalars_ref[0, 1]
    c2 = scalars_ref[0, 2]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    mhat = m / c1
    vhat = v / c2
    p = p_ref[...].astype(jnp.float32)
    p = (1.0 - lr * weight_decay) * p - lr * mhat / (jnp.sqrt(vhat) + eps)
    return p, m, v


def _kernel(scalars_ref, p_ref, g_ref, m_ref, v_ref,
            p_out, m_out, v_out, *, beta1, beta2, eps, weight_decay):
    g = g_ref[...].astype(jnp.float32)
    p, m, v = _update(g, p_ref, m_ref, v_ref, scalars_ref, beta1=beta1,
                      beta2=beta2, eps=eps, weight_decay=weight_decay)
    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


def _stats_kernel(scalars_ref, p_ref, g_ref, m_ref, v_ref,
                  p_out, m_out, v_out, gsq_out, *, beta1, beta2, eps,
                  weight_decay, n, block):
    g_raw = g_ref[...].astype(jnp.float32)
    # byproduct: pre-clip Σg² (the ragged end of the last block masked out)
    gsq_out[...] = tile_partial(mask_tail(tiles(g_raw * g_raw), n, block))
    g = g_raw * scalars_ref[0, 3]                  # global-norm clip scale
    p, m, v = _update(g, p_ref, m_ref, v_ref, scalars_ref, beta1=beta1,
                      beta2=beta2, eps=eps, weight_decay=weight_decay)
    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


def _scalars(lr, c1, c2, clip_scale=1.0):
    return jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32),
                      jnp.asarray(clip_scale, jnp.float32)]).reshape(1, 4)


def _flat_operands(p, g, m, v, block_rows):
    """(p, g, m, v) as 1-D operands of one grid, plus (block, blocks)."""
    pf, block, blocks = flat_grid(p.reshape(-1), block_rows)
    gf, _, _ = flat_grid(g.reshape(-1), block_rows)
    mf, _, _ = flat_grid(m.reshape(-1).astype(jnp.float32), block_rows)
    vf, _, _ = flat_grid(v.reshape(-1).astype(jnp.float32), block_rows)
    return (pf, gf, mf, vf), block, blocks


def _call(kernel, scalars, operands, block, blocks, p_dtype, extra_out, ip):
    pf = operands[0]
    spec = flat_spec(block)
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0))] + [spec] * 4,
        out_specs=[spec, spec, spec] + [partial_spec()] * extra_out,
        out_shape=[
            jax.ShapeDtypeStruct(pf.shape, p_dtype),
            jax.ShapeDtypeStruct(pf.shape, jnp.float32),
            jax.ShapeDtypeStruct(pf.shape, jnp.float32),
        ] + [partial_shape(blocks)] * extra_out,
        interpret=ip,
    )(scalars, *operands)


def fused_adamw(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, c1, c2,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None):
    """AdamW update on one tensor; returns (p', m', v') with p's shape/dtype."""
    ip = resolve_interpret(interpret)
    shape, n = p.shape, p.size
    operands, block, blocks = _flat_operands(p, g, m, v, block_rows)
    kernel = functools.partial(_kernel, beta1=beta1, beta2=beta2, eps=eps,
                               weight_decay=weight_decay)
    p2, m2, v2 = _call(kernel, _scalars(lr, c1, c2), operands, block,
                       blocks, p.dtype, 0, ip)
    unpad = lambda a: a[:n].reshape(shape)
    return unpad(p2), unpad(m2), unpad(v2)


def fused_adamw_stats(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay,
                      c1, c2, clip_scale=1.0,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool | None = None):
    """Flat-buffer AdamW: one launch over one dtype-homogeneous buffer.

    `clip_scale` (traced f32) is folded into the gradient inside the kernel;
    returns (p', m', v', Σg²) where Σg² is of the RAW (pre-clip) gradient."""
    ip = resolve_interpret(interpret)
    shape, n = p.shape, p.size
    operands, block, blocks = _flat_operands(p, g, m, v, block_rows)
    kernel = functools.partial(_stats_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay,
                               n=operands[0].shape[0], block=block)
    p2, m2, v2, gsq = _call(kernel, _scalars(lr, c1, c2, clip_scale),
                            operands, block, blocks, p.dtype, 1, ip)
    unpad = lambda a: a[:n].reshape(shape)
    return unpad(p2), unpad(m2), unpad(v2), jnp.sum(gsq)
