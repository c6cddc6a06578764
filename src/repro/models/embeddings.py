"""Token embeddings, RoPE (with DeepSeek-V2's YaRN scaling), and sinusoidal
positions."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import normal_init
from repro.distributed.sharding import maybe_shard


def init_embedding(key, vocab: int, d: int, dtype):
    return {"table": normal_init(key, (vocab, d), dtype)}


def embed_tokens(params, tokens, scale: bool, d_model: int):
    table = maybe_shard(params["table"], "vocab", "embed")
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(jnp.sqrt(d_model), x.dtype)
    return x


def unembed(params, x, tied_table=None):
    """Project hidden states to vocab logits (tied or untied)."""
    table = tied_table if tied_table is not None else params["table"]
    table = maybe_shard(table, "vocab", "embed")
    return jnp.einsum("...d,vd->...v", x, table.astype(x.dtype))


# ---------------------------------------------------------------- RoPE ----

def rope_frequencies(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                       # (head_dim//2,)


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         original_max: int) -> float:
    """The dimension whose wavelength turns `rotations` times over the
    original context."""
    return (dim * math.log(original_max / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float, beta_slow: float):
    """YaRN inverse frequencies as DeepSeek-V2's modelling code defines
    them: extrapolated (`theta^(-2i/dim)`) below the correction dimension
    of `beta_fast` rotations, interpolated (divided by `factor`) above that
    of `beta_slow`, a linear ramp between."""
    extra = rope_frequencies(dim, theta)
    low = max(math.floor(_yarn_correction_dim(
        beta_fast, dim, theta, original_max)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        beta_slow, dim, theta, original_max)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, theta: float, freqs=None, scale: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    `freqs` (head_dim/2,) replaces the plain frequencies (YaRN), and `scale`
    multiplies cos and sin (YaRN's mscale ratio)."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)            # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                   # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_at(pos, d: int, dtype):
    """Sinusoidal embedding row(s) at (possibly traced) position `pos`:
    scalar -> (d,); a (b,) vector of per-row positions -> (b, d)."""
    log_timescale = jnp.log(10000.0) / (d // 2 - 1)
    inv_timescales = jnp.exp(-log_timescale * jnp.arange(d // 2, dtype=jnp.float32))
    if hasattr(pos, "astype"):
        p = pos.astype(jnp.float32)
        scaled = (p[:, None] if p.ndim == 1 else p) * inv_timescales
    else:
        scaled = float(pos) * inv_timescales
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1).astype(dtype)


def sinusoidal_positions(num_pos: int, d: int, dtype):
    """Whisper-style fixed sinusoidal embeddings, shape (num_pos, d)."""
    log_timescale = jnp.log(10000.0) / (d // 2 - 1)
    inv_timescales = jnp.exp(-log_timescale * jnp.arange(d // 2, dtype=jnp.float32))
    scaled = jnp.arange(num_pos, dtype=jnp.float32)[:, None] * inv_timescales[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1).astype(dtype)
