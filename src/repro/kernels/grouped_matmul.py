"""Grouped matrix products of a dropless expert layer on TPU: the Pallas
megablox kernels (`gmm` forward and for the input's gradient, `tgmm` for
the weights' gradient), the TPU path of `repro.kernels.ops.grouped_matmul`.

Rows of `x` are sorted by group; group i's `group_sizes[i]` rows meet
`w[i]`.  The kernels visit only the row tiles the groups cover, so the work
follows the actual group sizes, not the buffer's rows.  Rows past the last
group are left unwritten (the caller masks them; see `models/moe.py`).

Precision: f32 operands enter the MXU as bf16 with f32 accumulation — one
bf16 pass, what XLA's default precision does for an f32 einsum on TPU; the
gradients' operands likewise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

# row tiles, the largest that divides the (128-padded) rows; the
# contraction and column tile (the widths need not divide it: the kernels
# mask the last partial tile)
ROW_TILES = (512, 256, 128)
WIDTH_TILE = 512


def _mxu(x):
    return x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, group_sizes, tiling, interpret):
    return gmm(_mxu(x), _mxu(w), group_sizes, jnp.float32, tiling,
               interpret=interpret).astype(x.dtype)


def _gmm_fwd(x, w, group_sizes, tiling, interpret):
    xm, wm = _mxu(x), _mxu(w)
    out = gmm(xm, wm, group_sizes, jnp.float32, tiling, interpret=interpret)
    return out.astype(x.dtype), (xm, wm, group_sizes)


def _gmm_bwd(tiling, interpret, res, cot):
    # x, w and the output share one dtype (`grouped_matmul`)
    xm, wm, group_sizes = res
    gm = _mxu(cot)
    dx = gmm(gm, wm, group_sizes, jnp.float32, tiling, transpose_rhs=True,
             interpret=interpret)
    dw = tgmm(xm.swapaxes(0, 1), gm, group_sizes, jnp.float32, tiling,
              num_actual_groups=wm.shape[0], interpret=interpret)
    return dx.astype(cot.dtype), dw.astype(cot.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """x: (m, k) rows sorted by group; w: (groups, k, n); group_sizes:
    (groups,) int32.  Returns (m, n) in x's dtype: row r of group i is
    x[r] @ w[i]; rows past sum(group_sizes) are undefined."""
    m = x.shape[0]
    pad = -m % ROW_TILES[-1]
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tm = next(t for t in ROW_TILES if (m + pad) % t == 0)
    out = _gmm(x, w.astype(x.dtype), group_sizes.astype(jnp.int32),
               (tm, WIDTH_TILE, WIDTH_TILE), interpret)
    return out[:m] if pad else out
