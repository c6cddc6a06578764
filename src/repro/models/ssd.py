"""Mamba-2 SSD (state-space duality) block [arXiv:2405.21060].

Training/prefill uses the chunked dual form: within a chunk the recurrence is
materialized as masked matmuls (MXU-dense); across chunks a short
`lax.scan` carries the (heads, head_dim, state) SSM state — O(T/Q) sequential
steps instead of O(T).  Decode is the exact single-step recurrence.

Layout notes (TPU adaptation): heads are sharded over the `model` mesh axis;
chunk size defaults to 128 so intra-chunk matmuls are MXU-aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import normal_init, zeros_init, split_keys
from repro.models.config import SSMConfig
from repro.distributed.sharding import maybe_shard


def init_ssd(key, d_model: int, s: SSMConfig, dtype):
    di = s.d_inner(d_model)
    nh = s.num_heads(d_model)
    conv_ch = di + 2 * s.state_dim          # conv over [x, B, C]
    keys = split_keys(key, 5)
    return {
        # fused input projection -> [z, x, B, C, dt]
        "w_in": normal_init(keys[0], (d_model, 2 * di + 2 * s.state_dim + nh), dtype),
        "conv_w": normal_init(keys[1], (s.conv_width, conv_ch), dtype),
        "conv_b": zeros_init(keys[1], (conv_ch,), dtype),
        # a host constant: jitted and eager initialisation agree bit for bit
        "a_log": jnp.asarray(np.log(np.linspace(1.0, 16.0, nh)), jnp.float32),
        "dt_bias": zeros_init(keys[2], (nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), dtype),
        "w_out": normal_init(keys[3], (di, d_model), dtype),
    }


def _split_proj(params, x, s: SSMConfig, d_model: int):
    di = s.d_inner(d_model)
    nh = s.num_heads(d_model)
    proj = jnp.einsum("btd,dp->btp", x, params["w_in"].astype(x.dtype))
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * s.state_dim], axis=-1)
    return z, xbc, dt, di, nh


def _causal_conv(x, w, b):
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    return jax.nn.silu(out + b[None, None, :])


def _gated_out(params, y, z, x_dtype):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y / jnp.sqrt(var + 1e-6) * params["norm_scale"].astype(jnp.float32)
    return jnp.einsum("btf,fd->btd", y.astype(x_dtype), params["w_out"].astype(x_dtype))


def ssd_block(params, x, s: SSMConfig, initial_state=None, return_state=False):
    """Chunked SSD over a full sequence. x: (b,t,d)."""
    b, t, d_model = x.shape
    z, xbc, dt_raw, di, nh = _split_proj(params, x, s, d_model)
    xbc = _causal_conv(xbc, params["conv_w"].astype(x.dtype), params["conv_b"].astype(x.dtype))
    xs, B, C = jnp.split(xbc, [di, di + s.state_dim], axis=-1)
    p = s.head_dim
    xs = xs.reshape(b, t, nh, p)
    xs = maybe_shard(xs, "batch", "seq", "ssm_heads", None)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :])  # (b,t,nh)
    a = -jnp.exp(params["a_log"])                                   # (nh,)
    dA = dt * a[None, None, :]                                      # log decay per step

    q = s.chunk_size
    assert t % q == 0, f"seq {t} must be divisible by chunk {q}"
    nc = t // q
    # reshape into chunks
    xs_c = xs.reshape(b, nc, q, nh, p).astype(jnp.float32)
    B_c = B.reshape(b, nc, q, s.state_dim).astype(jnp.float32)
    C_c = C.reshape(b, nc, q, s.state_dim).astype(jnp.float32)
    dt_c = dt.reshape(b, nc, q, nh)
    dA_c = dA.reshape(b, nc, q, nh)

    cum = jnp.cumsum(dA_c, axis=2)                                  # (b,nc,q,nh)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]             # (b,nc,q_i,q_j,nh)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    # mask BEFORE exp: non-causal entries have positive log-decay -> exp
    # overflows -> 0*inf = NaN in the backward pass
    seg = jnp.where(causal, seg, -1e30)
    decay = jnp.exp(seg)

    # intra-chunk: y[i] = sum_j<=i (C_i . B_j) decay(i,j) dt_j x_j
    cb = jnp.einsum("bcin,bcjn->bcij", C_c, B_c)                    # (b,nc,q,q)
    m = cb[:, :, :, :, None] * decay                                # (b,nc,q,q,nh)
    y_intra = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", m, dt_c, xs_c)

    # chunk state contributions: S_c = sum_j exp(cum[-1]-cum[j]) dt_j B_j x_j^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)                 # (b,nc,q,nh)
    sc = jnp.einsum("bcjh,bcjh,bcjn,bcjhp->bchnp", decay_to_end, dt_c, B_c, xs_c)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                         # (b,nc,nh)

    # scan over chunks carrying state (b, nh, n, p)
    if initial_state is None:
        s0 = jnp.zeros((b, nh, s.state_dim, p), jnp.float32)
    else:
        s0 = initial_state.astype(jnp.float32)

    def step(state, inp):
        sc_c, cdec = inp                                            # (b,nh,n,p), (b,nh)
        new = state * cdec[:, :, None, None] + sc_c
        return new, state                                           # emit state *before* chunk

    sc_t = jnp.moveaxis(sc, 1, 0)                                   # (nc,b,nh,n,p)
    cdec_t = jnp.moveaxis(chunk_decay, 1, 0)                        # (nc,b,nh)
    final_state, prev_states = jax.lax.scan(step, s0, (sc_t, cdec_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)                   # (b,nc,nh,n,p)

    # inter-chunk: y[i] += C_i . (decay_from_start(i) * S_prev)
    decay_from_start = jnp.exp(cum)                                 # (b,nc,q,nh)
    y_inter = jnp.einsum("bcin,bchnp,bcih->bcihp", C_c, prev_states, decay_from_start)

    y = (y_intra + y_inter).reshape(b, t, nh, p)
    y = y + params["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, t, di)
    out = _gated_out(params, y, z, x.dtype)
    out = maybe_shard(out, "batch", "seq", "embed")
    if return_state:
        return out, final_state
    return out


def init_ssd_state(batch: int, d_model: int, s: SSMConfig, dtype):
    nh = s.num_heads(d_model)
    di = s.d_inner(d_model)
    return {
        "ssm": jnp.zeros((batch, nh, s.state_dim, s.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, di + 2 * s.state_dim), dtype),
    }


def ssd_decode(params, x, state, s: SSMConfig):
    """Exact single-step recurrence. x: (b,1,d)."""
    b, _, d_model = x.shape
    z, xbc, dt_raw, di, nh = _split_proj(params, x, s, d_model)
    conv_in = jnp.concatenate([state["conv"], xbc], axis=1)
    wconv = params["conv_w"].astype(x.dtype)
    xbc_t = jax.nn.silu(jnp.einsum("bkc,kc->bc", conv_in, wconv)
                        + params["conv_b"].astype(x.dtype))
    xs, B, C = jnp.split(xbc_t, [di, di + s.state_dim], axis=-1)
    p = s.head_dim
    xs = xs.reshape(b, nh, p).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"][None, :])  # (b,nh)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dt * a[None, :])                                # (b,nh)
    Bf = B.astype(jnp.float32)
    new_state = state["ssm"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhnp", dt, Bf, xs)
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), new_state)
    y = y + params["d_skip"][None, :, None] * xs
    y = y.reshape(b, 1, di)
    out = _gated_out(params, y, z, x.dtype)
    return out, {"ssm": new_state, "conv": conv_in[:, 1:, :]}
