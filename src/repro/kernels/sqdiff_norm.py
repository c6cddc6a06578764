"""Pallas TPU kernel: fused Σ(x−y)² reduction — the norm-test hot spot.

The paper's DDP-/FSDP-Norm evaluates ‖g_j − g‖² over the whole gradient every
step.  Naively that materializes the difference tensor (one extra gradient-
sized HBM round-trip).  This kernel streams x and y through VMEM in
32k-element blocks and accumulates the squared difference in f32 without
writing the intermediate — one read of each operand, no extra writes.

Grid: 1-D over blocks of the flattened operands; each program writes one
lane-aligned (8, 128) f32 partial tile; the wrapper sums the partials (a
trivially small reduction).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import (flat_grid, flat_spec, mask_tail, partial_shape,
                           partial_spec, resolve_interpret, tile_partial,
                           tiles)

DEFAULT_BLOCK_ROWS = 256     # 256×128 f32 block = 128 KiB/operand in VMEM


def _kernel(x_ref, y_ref, o_ref, *, n, block):
    d = tiles(x_ref[...]) - tiles(y_ref[...])
    o_ref[...] = tile_partial(mask_tail(d * d, n, block))


def sqdiff_norm(x, y, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None):
    """Σ(x−y)² over arbitrarily-shaped equal-shape tensors, f32 result."""
    assert x.shape == y.shape, (x.shape, y.shape)
    ip = resolve_interpret(interpret)
    xf, block, blocks = flat_grid(x.reshape(-1), block_rows)
    yf, _, _ = flat_grid(y.reshape(-1), block_rows)
    partials = pl.pallas_call(
        functools.partial(_kernel, n=xf.shape[0], block=block),
        grid=(blocks,),
        in_specs=[flat_spec(block), flat_spec(block)],
        out_specs=partial_spec(),
        out_shape=partial_shape(blocks),
        interpret=ip,
    )(xf, yf)
    return jnp.sum(partials)
