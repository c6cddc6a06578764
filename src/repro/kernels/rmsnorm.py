"""Pallas TPU kernel: row-wise RMSNorm.

Tiles rows of the (tokens, d_model) activation through VMEM; each program
normalizes a (block_rows, d) tile in one pass (f32 accumulation, cast back).
d_model must be lane-aligned (all assigned configs are multiples of 128; the
wrapper pads the row dim only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_ROWS = 128


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x / jnp.sqrt(var + eps) * s_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool | None = None):
    """x: (..., d); scale: (d,). Returns same shape/dtype as x."""
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    blocks = max(1, -(-rows // block_rows))
    padded = blocks * block_rows
    if padded != rows:
        xf = jnp.pad(xf, ((0, padded - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(xf, scale.reshape(1, d))
    return out[:rows].reshape(shape)
