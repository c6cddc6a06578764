"""jit'd public wrappers for the Pallas kernels.

`interpret` resolves through `repro.kernels.resolve_interpret`: explicit
flag > `REPRO_PALLAS_INTERPRET` env override > backend auto-detect (compiled
on TPU, interpreted elsewhere — this container is CPU-only; the kernels
target TPU and are validated against ref.py in interpret mode).

The `*_flat` entry points at the bottom are the DESIGN §9 hot-path dispatch:
compiled Pallas on TPU, the fused-jnp reference otherwise (interpret-mode
Pallas is a correctness tool, far too slow for the per-step tail).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import _backend_is_tpu, resolve_interpret as _default_resolve
from repro.kernels import ref
from repro.kernels.sqdiff_norm import sqdiff_norm as _sqdiff_norm
from repro.kernels.fused_adamw import (
    fused_adamw as _fused_adamw, fused_adamw_stats as _fused_adamw_stats)
from repro.kernels.fused_stats import fused_stats as _fused_stats
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.launch.mesh import data_axes


def _default_interpret() -> bool:
    return _default_resolve(None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqdiff_norm(x, y, interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    return _sqdiff_norm(x, y, interpret=ip)


def sqdiff_norm_tree(tree_a, tree_b, interpret: bool | None = None):
    """Fused Σ‖a−b‖² over a whole gradient pytree (norm-test statistic)."""
    ip = _default_interpret() if interpret is None else interpret
    total = jnp.zeros((), jnp.float32)
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        total += _sqdiff_norm(a, b, interpret=ip)
    return total


@functools.partial(jax.jit, static_argnames=(
    "beta1", "beta2", "eps", "weight_decay", "interpret"))
def fused_adamw(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, c1, c2,
                interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    return _fused_adamw(p, g, m, v, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                        weight_decay=weight_decay, c1=c1, c2=c2, interpret=ip)


def fused_adamw_tree(params, grads, m, v, *, lr, beta1, beta2, eps,
                     weight_decay, c1, c2, interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = jax.tree.leaves(grads)
    leaves_m = jax.tree.leaves(m)
    leaves_v = jax.tree.leaves(v)
    new_p, new_m, new_v = [], [], []
    for p_, g_, m_, v_ in zip(leaves_p, leaves_g, leaves_m, leaves_v):
        a, b, c = _fused_adamw(p_, g_, m_, v_, lr=lr, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay, c1=c1,
                               c2=c2, interpret=ip)
        new_p.append(a); new_m.append(b); new_v.append(c)
    unf = treedef.unflatten
    return unf(new_p), unf(new_m), unf(new_v)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_stats(x, y, interpret: bool | None = None):
    """(Σ(x−y)², Σy²) in one read of each operand (norm-test statistics)."""
    ip = _default_interpret() if interpret is None else interpret
    return _fused_stats(x, y, interpret=ip)


@functools.partial(jax.jit, static_argnames=(
    "beta1", "beta2", "eps", "weight_decay", "interpret"))
def fused_adamw_stats(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay,
                      c1, c2, clip_scale=1.0, interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    return _fused_adamw_stats(p, g, m, v, lr=lr, beta1=beta1, beta2=beta2,
                              eps=eps, weight_decay=weight_decay, c1=c1,
                              c2=c2, clip_scale=clip_scale, interpret=ip)


# ------------------------------------------------ flat hot-path dispatch ----
# Traced inside the train steps (no jit here — the callers are jitted).
# Compiled Pallas on TPU; fused-jnp reference elsewhere.  NOT governed by
# REPRO_PALLAS_INTERPRET: interpret-mode Pallas is for validating kernels,
# not for running the per-step tail.

def on_local_shards(kernel, bufs, scalars=(), *, n_bufs_out: int,
                    n_sums: int):
    """Run `kernel(*bufs, *scalars) -> (out_bufs, partial_sums)` on every
    device's local shard of 1-D flat buffers.

    GSPMD cannot partition a Mosaic call, so where the context mesh still
    has Auto axes the call is wrapped in a `shard_map` manual over them.
    The buffers carry the DESIGN §9 data-axis sharding (`P(data axes)` of
    the axes still auto; replicated when the data axes are already manual,
    as inside the FSDP-Norm step), so each device updates its own bucket
    shard and the per-shard partial sums are `psum`'d over those axes.
    With no context mesh the kernel runs as is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = () if mesh.empty else tuple(mesh.auto_axes)
    if not auto:
        return kernel(*bufs, *scalars)
    daxes = tuple(a for a in data_axes(mesh) if a in auto)
    spec = P(daxes) if daxes else P()

    def body(*args):
        out_bufs, sums = kernel(*args)
        if daxes:
            sums = tuple(jax.lax.psum(s, daxes) for s in sums)
        return tuple(out_bufs), tuple(sums)

    scalars = tuple(jnp.asarray(s, jnp.float32) for s in scalars)
    return jax.shard_map(
        body, in_specs=(spec,) * len(bufs) + (P(),) * len(scalars),
        out_specs=((spec,) * n_bufs_out, (P(),) * n_sums),
        axis_names=set(auto), check_vma=False)(*bufs, *scalars)


def stats_flat(x, y):
    """Backend-dispatched single-pass (Σ(x−y)², Σy²) over flat buffers.

    The Pallas grid is sized from the operand actually passed in — inside a
    shard_map manual region that is the worker's LOCAL bucket shard, so a
    J-way-sharded bucket costs 1/J of the launch grid per worker (zero
    shard-padding contributes nothing to either sum)."""
    if _backend_is_tpu():
        _, sums = on_local_shards(
            lambda a, b: ((), _fused_stats(a, b, interpret=False)), (x, y),
            n_bufs_out=0, n_sums=2)
        return sums
    return ref.fused_stats_ref(x, y)


def adamw_flat(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, c1, c2,
               clip_scale=1.0):
    """Backend-dispatched flat-buffer AdamW; returns (p', m', v', Σg²_raw).

    Like `stats_flat`, the grid covers whatever buffer arrives: under the
    sharded-bucket FSDP-Norm step each worker updates only its 1/J bucket
    shard, so per-worker update flops and moment traffic drop by J."""
    if _backend_is_tpu():
        def kernel(p, g, m, v, lr, c1, c2, clip_scale):
            *bufs, gsq = _fused_adamw_stats(
                p, g, m, v, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                weight_decay=weight_decay, c1=c1, c2=c2,
                clip_scale=clip_scale, interpret=False)
            return bufs, (gsq,)
        (p2, m2, v2), (gsq,) = on_local_shards(
            kernel, (p, g, m, v), (lr, c1, c2, clip_scale),
            n_bufs_out=3, n_sums=1)
        return p2, m2, v2, gsq
    return ref.adamw_stats_ref(p, g, m, v, lr=lr, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay, c1=c1,
                               c2=c2, clip_scale=clip_scale)


def flat_dispatch_info() -> dict:
    """Which implementation the DESIGN §9 flat hot-path tail dispatches to
    on this process's backend.  Recorded in the `repro.analysis` report's
    `checked` section: a clean static-analysis run thereby documents WHICH
    backend's step graphs it certified (the compiled-Pallas TPU tail and
    the fused-jnp CPU tail lower different equations)."""
    return {
        "backend": jax.default_backend(),
        "flat_tail": "pallas-compiled" if _backend_is_tpu() else
                     "jnp-reference",
        "pallas_interpret_default": bool(_default_interpret()),
    }


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    return _rmsnorm(x, scale, eps=eps, interpret=ip)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=256, block_kv=256, interpret: bool | None = None):
    ip = _default_interpret() if interpret is None else interpret
    return _flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, block_q=block_q,
                            block_kv=block_kv, interpret=ip)
