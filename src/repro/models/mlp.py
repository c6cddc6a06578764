"""Dense MLP variants: SwiGLU / GeGLU / GELU / squared-ReLU (Nemotron-4)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import spans
from repro.models.common import normal_init, split_keys
from repro.distributed.sharding import maybe_shard


def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype):
    k1, k2, k3 = split_keys(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": normal_init(k1, (d_model, d_ff), dtype),
            "w_up": normal_init(k2, (d_model, d_ff), dtype),
            "w_down": normal_init(k3, (d_ff, d_model), dtype),
        }
    elif kind in ("gelu", "relu2"):
        return {
            "w_up": normal_init(k1, (d_model, d_ff), dtype),
            "w_down": normal_init(k2, (d_ff, d_model), dtype),
        }
    raise ValueError(kind)


@spans.scoped(spans.MLP)
def apply_mlp(params, x, kind: str):
    if kind in ("swiglu", "geglu"):
        gate = jnp.einsum("btd,df->btf", x, params["w_gate"].astype(x.dtype))
        up = jnp.einsum("btd,df->btf", x, params["w_up"].astype(x.dtype))
        gate = maybe_shard(gate, "batch", "seq", "ffn")
        act = jax.nn.silu(gate) if kind == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jnp.einsum("btd,df->btf", x, params["w_up"].astype(x.dtype))
        h = maybe_shard(h, "batch", "seq", "ffn")
        if kind == "gelu":
            h = jax.nn.gelu(h)
        elif kind == "relu2":
            h = jnp.square(jax.nn.relu(h))
        else:
            raise ValueError(kind)
    out = jnp.einsum("btf,fd->btd", h, params["w_down"].astype(x.dtype))
    return maybe_shard(out, "batch", "seq", "embed")
