"""Pallas TPU kernel: fused norm-test statistics — Σ(x−y)² AND Σy² in ONE
read of the two operands (DESIGN §9).

The DDP-/FSDP-Norm statistic needs both ‖g_j − g‖² (per-worker squared
deviation) and ‖g‖² (the denominator of eq. 5's test) every step.  Computed
separately (`sqdiff_norm` + a `tree_sqnorm`) that is two full HBM passes
over the mean gradient; here each 32k-element block of x and y is
streamed through VMEM once and BOTH partial sums are accumulated in f32 —
one read of each operand, no extra passes, no intermediate writes.

Grid: 1-D over blocks of the flattened operands; each program writes one
lane-aligned (8, 128) f32 partial tile per statistic; the wrapper sums the
partials (trivially small).  The ragged end of the last block is masked out
of both sums.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import (flat_grid, flat_spec, mask_tail, partial_shape,
                           partial_spec, resolve_interpret, tile_partial,
                           tiles)

DEFAULT_BLOCK_ROWS = 256     # 256×128 f32 block = 128 KiB/operand in VMEM


def _kernel(x_ref, y_ref, diff_ref, ysq_ref, *, n, block):
    x = tiles(x_ref[...])
    y = tiles(y_ref[...])
    d = x - y
    diff_ref[...] = tile_partial(mask_tail(d * d, n, block))
    ysq_ref[...] = tile_partial(mask_tail(y * y, n, block))


def fused_stats(x, y, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None):
    """(Σ(x−y)², Σy²) over equal-shape tensors, f32, one read of each."""
    assert x.shape == y.shape, (x.shape, y.shape)
    ip = resolve_interpret(interpret)
    xf, block, blocks = flat_grid(x.reshape(-1), block_rows)
    yf, _, _ = flat_grid(y.reshape(-1), block_rows)
    diff, ysq = pl.pallas_call(
        functools.partial(_kernel, n=xf.shape[0], block=block),
        grid=(blocks,),
        in_specs=[flat_spec(block), flat_spec(block)],
        out_specs=[partial_spec(), partial_spec()],
        out_shape=[partial_shape(blocks), partial_shape(blocks)],
        interpret=ip,
    )(xf, yf)
    return jnp.sum(diff), jnp.sum(ysq)
