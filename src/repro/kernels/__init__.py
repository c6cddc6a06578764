# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Shared Pallas plumbing lives here so every kernel module resolves the
# execution mode the same way instead of hard-coding `interpret=True`:
#
# * `resolve_interpret(flag)` — explicit flag wins; else the
#   `REPRO_PALLAS_INTERPRET` env var (0/1); else auto-detect once per
#   process (compiled on TPU, interpreted everywhere else).
# * `flat_grid(flat, block_rows)` / `flat_spec(block)` / `tiles(v)` /
#   `mask_tail(x, n, block)` — the 1-D grid of the flat reduction/update
#   kernels.  Operands stay 1-D: on TPU a `(rows, LANE)` view of a 1-D
#   array is a relayout copy, which for model-sized flat buffers costs
#   gigabytes of HBM.  The last block may run past the operand's end; the
#   reductions mask it and its out-of-range writes are dropped.
# * `tile_partial(x)` / `partial_spec()` / `partial_shape(blocks)` — the
#   per-grid-step partial sums of the reduction kernels, one lane-aligned
#   (SUBLANE, LANE) f32 tile per block (Mosaic only accepts blocks whose
#   last two dims divide (8, 128)); the wrapper sums the partials.

from __future__ import annotations

import functools
import os

LANE = 128
SUBLANE = 8


@functools.lru_cache(maxsize=None)
def _backend_is_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(flag: bool | None = None) -> bool:
    """Pallas execution mode: explicit flag > env override > backend.

    `REPRO_PALLAS_INTERPRET=1` forces interpret mode everywhere (debugging);
    `=0` forces compiled Pallas even off-TPU (will fail on backends without
    Mosaic — use only on TPU-like targets).  Unset: compiled on TPU,
    interpreted elsewhere (this container is CPU-only; interpret mode is the
    correctness path, validated against ref.py).
    """
    if flag is not None:
        return bool(flag)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env:
        return env not in ("0", "false", "no")
    return not _backend_is_tpu()


TILE = SUBLANE * LANE        # elements of one (SUBLANE, LANE) f32 tile


def flat_grid(flat, block_rows: int):
    """The 1-D grid over a 1-D operand: returns (flat, block, blocks).

    `flat` comes back as it is — no padding copy — unless it is shorter
    than one tile, when it is zero-padded to one (at most 4 KiB; TPU lays
    out shorter 1-D arrays in smaller tiles than a kernel block).  `block`
    is `block_rows * LANE` elements, or the operand rounded up to whole
    tiles if that is less; the last of `blocks` may run past the end."""
    import jax.numpy as jnp
    n = flat.shape[0]
    if n < TILE:
        flat = jnp.pad(flat, (0, TILE - n))
        n = TILE
    block = min(block_rows * LANE, -(-n // TILE) * TILE)
    return flat, block, -(-n // block)


def flat_spec(block: int):
    """BlockSpec of one grid step's `block` elements of a 1-D operand."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((block,), lambda i: (i,))


def tiles(v):
    """A 1-D block value as f32 (block // LANE, LANE) tiles."""
    import jax.numpy as jnp
    return v.astype(jnp.float32).reshape(-1, LANE)


def mask_tail(x, n: int, block: int):
    """Zero the entries of the (rows, LANE) block value `x` that lie past
    element `n` of the operand (only the last block of a ragged grid has
    any; what the read there returns is undefined)."""
    if n % block == 0:
        return x
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    index = pl.program_id(0) * block + row * LANE + lane
    return jnp.where(index < n, x, 0.0)


def tile_partial(x):
    """Fold a (block_rows, LANE) f32 tile into one (SUBLANE, LANE) partial
    by elementwise adds across sublane groups (no cross-lane reduction in
    the kernel; block_rows must be a multiple of SUBLANE)."""
    return x.reshape(-1, SUBLANE, LANE).sum(axis=0)


def partial_spec():
    """BlockSpec of one grid step's (SUBLANE, LANE) partial tile."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0))


def partial_shape(blocks: int):
    """Output shape holding `blocks` stacked partial tiles."""
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((blocks * SUBLANE, LANE), jnp.float32)
