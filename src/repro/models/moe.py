"""Mixture-of-Experts layer: a router over all `num_experts`, top-k gates,
dropless dispatch to the experts this chip holds, grouped matrix products,
shared experts, and DeepSeek-V2's sequence-level balance loss.

Expert parallelism: the layer holds routed experts [0, `MoEConfig.held`)
(the chip's share; all of them by default) and computes their part of each
token's result.  The router still scores every expert and picks each
token's top k of all of them; pairs that go to experts held elsewhere are
computed by nobody here, and what those experts would add is left out (on
one chip the layer runs without its exchange).  Where the mesh has a
`model` axis, the held stacks stay split over it (`moe_apply`).

Dispatch is dropless.  The dispatch group is every token of the call (a
device's local tokens of one microbatch).  Its token-expert pairs that go
to held experts are sorted by expert, one group per held expert, and the
three products of the SwiGLU experts run as grouped matmuls
(`kernels.ops.grouped_matmul`) over the sorted rows, whose work follows
the actual group sizes.  The row buffer holds the worst case dropless
allows, n·min(k, held) pairs, cut into chunks whose size follows the shapes
(`buffer_chunks`: a little above the held pairs of even routing).  The
first chunk always runs, each later one only where the call's held pairs
reach it (`lax.cond`), so the gather, the casts, the gating and the
combine work on about the pairs held, and a call whose routing piles onto
the held experts still runs them all; a layer whose shapes give one chunk
(all experts held, decode) is the single buffer, with no `cond`.  Rows
past the held pairs are masked on the way in and out.  The combine adds
each pair's gate-weighted output back to its token.  Shared experts are
dense and counted once.

The layer returns, besides the output, the balance loss and three counters
of the dispatch (`MoEStats`): the pairs computed on held experts, the
largest held expert's pairs over the mean, and the rows of the buffer
chunks that carried them.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import spans
from repro.distributed.sharding import DEFAULT_RULES, current_rules
from repro.kernels import ops
from repro.kernels.grouped_matmul import ROW_TILES
from repro.launch.mesh import data_axes
from repro.models.common import normal_init, split_keys
from repro.models.config import MoEConfig


class MoEStats(NamedTuple):
    """What one expert layer reports besides its output (f32 scalars)."""
    aux: jax.Array          # balance loss, times its coefficient
    rows: jax.Array         # token-expert pairs computed on held experts
    load_max: jax.Array     # largest held expert's pairs over the mean
    buffer_rows: jax.Array  # rows of the dispatch buffers that held them

    @staticmethod
    def zeros() -> "MoEStats":
        z = jnp.zeros((), jnp.float32)
        return MoEStats(z, z, z, z)

    def merge(self, other: "MoEStats") -> "MoEStats":
        """Over layers: losses, pairs and buffer rows add, the load ratio
        is the max."""
        return MoEStats(self.aux + other.aux, self.rows + other.rows,
                        jnp.maximum(self.load_max, other.load_max),
                        self.buffer_rows + other.buffer_rows)


def init_moe(key, d_model: int, m: MoEConfig, mlp_kind: str, dtype):
    k_r, k_g, k_u, k_d, k_s = split_keys(key, 5)
    h = m.held
    p = {
        "router": normal_init(k_r, (d_model, m.num_experts), dtype),
        "w_gate": normal_init(k_g, (h, d_model, m.d_expert), dtype),
        "w_up": normal_init(k_u, (h, d_model, m.d_expert), dtype),
        "w_down": normal_init(k_d, (h, m.d_expert, d_model), dtype),
    }
    if m.num_shared_experts:
        width = m.num_shared_experts * m.shared_d_expert
        ks1, ks2, ks3 = split_keys(k_s, 3)
        p["shared"] = {
            "w_gate": normal_init(ks1, (d_model, width), dtype),
            "w_up": normal_init(ks2, (d_model, width), dtype),
            "w_down": normal_init(ks3, (width, d_model), dtype),
        }
    return p


def route(params, x, m: MoEConfig):
    """Router probabilities over all experts (f32, at `highest` precision,
    as DeepSeek's gate computes in fp32) and each token's top-k experts
    with their gates.  x: (b, t, d) -> probs (b, t, E), experts and gates
    (b, t, k)."""
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, m.top_k)
    if m.normalize_gates:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return probs, experts, gates * m.routed_scaling_factor


def balance_loss(probs, experts, m: MoEConfig):
    """DeepSeek-V2's sequence-level balance loss, times α: per sequence,
    (E/k)·Σ_e (count_e/T)·mean_t p_{t,e}, averaged over sequences."""
    b, t, e = probs.shape
    counts = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.float32),
                     axis=(1, 2))                                  # (b, E)
    per_seq = (e / m.top_k) * jnp.sum(counts / t * jnp.mean(probs, axis=1),
                                      axis=-1)
    return m.router_aux_coef * jnp.mean(per_seq)


def buffer_chunks(n: int, k: int, held: int, num_experts: int
                  ) -> tuple[int, int]:
    """How the dispatch buffer is cut: (rows of a chunk, chunks).  The
    buffer holds the worst case dropless allows, W = n·min(k, held) pairs.
    A chunk is W halved while the half stays a multiple of 128 and holds at
    least max(1.25·F, the grouped matmul's largest row tile), F =
    n·k·held/num_experts being the held pairs under even routing.  One
    chunk where the layer holds all its experts (F ≥ W/2) or the call is
    too small to split."""
    worst = n * min(k, held)
    rows, least = worst, max(1.25 * n * k * held / num_experts, ROW_TILES[0])
    while rows % 256 == 0 and rows // 2 >= least:
        rows //= 2
    return rows, worst // rows


def _chunk_sizes(group_sizes, s: int, c: int):
    """Each held expert's pairs among sorted pairs [s, s + c)."""
    ends = jnp.cumsum(group_sizes)
    return (jnp.clip(ends - s, 0, c)
            - jnp.clip(ends - group_sizes - s, 0, c))


def _chunk(s: int, c: int, k: int, y, x, gates, w, order, sizes, total):
    """y plus the gate-weighted expert outputs of sorted pairs [s, s + c),
    each added to its token.  x, y: (n, d); gates, order: (n·k,); w: the
    gate, up and down stacks; sizes: the held experts' pairs among them."""
    order = order[s:s + c]
    valid = (jnp.arange(c) < total - s)[:, None]
    token = order // k
    dt = x.dtype
    w_gate, w_up, w_down = (a.astype(dt) for a in w)
    # rows past the held pairs are undefined in every product's output:
    # select, never multiply, on the way in and out, so neither they nor
    # their cotangents reach a token or a gate
    rows = jnp.where(valid, x[token], 0)
    with jax.named_scope(spans.EXPERT_MATMUL):
        gate = ops.grouped_matmul(rows, w_gate, sizes)
        up = ops.grouped_matmul(rows, w_up, sizes)
        out = ops.grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
    pair_gate = gates[order].astype(dt)
    out = jnp.where(valid, out, 0) * pair_gate[:, None]
    return y.at[token].add(out)


def _chunked_fwd(c, count, k, x, gates, w, order, group_sizes, total):
    # the first chunk always runs, each later one only where held pairs
    # reach it
    y = jnp.zeros(x.shape, x.dtype)
    for j in range(count):
        run = functools.partial(_chunk, j * c, c, k, x=x, gates=gates, w=w,
                                order=order, total=total,
                                sizes=_chunk_sizes(group_sizes, j * c, c))
        y = run(y) if j == 0 else \
            jax.lax.cond(total > j * c, run, lambda y: y, y)
    return y, (x, gates, w, order, group_sizes, total)


def _chunked_bwd(c, count, k, res, ct):
    # each chunk that ran is recomputed and transposed here, and its
    # gradients added up in place; the forward keeps only the operands
    x, gates, w, order, group_sizes, total = res

    def grads(j):
        sizes = _chunk_sizes(group_sizes, j * c, c)
        _, vjp = jax.vjp(
            lambda x, gates, w: _chunk(j * c, c, k, jnp.zeros_like(ct), x,
                                       gates, w, order, sizes, total),
            x, gates, w)
        return vjp(ct)

    acc = grads(0)
    for j in range(1, count):
        acc = jax.lax.cond(
            total > j * c,
            lambda a, j=j: jax.tree.map(jnp.add, a, grads(j)),
            lambda a: a, acc)
    return (*acc, None, None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _chunked(c, count, k, x, gates, w, order, group_sizes, total):
    """The held pairs' gate-weighted expert outputs added to their tokens,
    through the first ceil(total / c) of `count` chunks of c sorted
    pairs."""
    return _chunked_fwd(c, count, k, x, gates, w, order, group_sizes,
                        total)[0]


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _experts(params, x, experts, gates, m: MoEConfig, first):
    """This device's held experts' gate-weighted part of each token's
    result, its pairs on them, its largest expert's pairs and the rows of
    the buffer chunks that carried them.  The held block is experts
    [first, first + held), held being the stacks' leading size.  x: (n, d);
    experts, gates: (n, k)."""
    n, d = x.shape
    k, held = m.top_k, params["w_up"].shape[0]
    rel = experts.reshape(-1) - first
    # pairs of held experts first, sorted by expert; the rest (key `held`)
    # after them, and one_hot gives them a zero row
    key = jnp.where((rel >= 0) & (rel < held), rel, held)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    total = jnp.sum(group_sizes)
    c, count = buffer_chunks(n, k, held, m.num_experts)
    args = (x, gates.reshape(-1),
            (params["w_gate"], params["w_up"], params["w_down"]), order,
            group_sizes, total)
    if count == 1:
        y = _chunk(0, c, k, jnp.zeros(x.shape, x.dtype), *args)
    else:
        y = _chunked(c, count, k, *args)
    buffered = c * jnp.clip((total + c - 1) // c, 1, count)
    return y, total.astype(jnp.float32), \
        jnp.max(group_sizes).astype(jnp.float32), \
        buffered.astype(jnp.float32)


def _shared(params, x):
    sh = params["shared"]
    dt = x.dtype
    hs = jax.nn.silu(jnp.einsum("btd,df->btf", x, sh["w_gate"].astype(dt)))
    hs = hs * jnp.einsum("btd,df->btf", x, sh["w_up"].astype(dt))
    return jnp.einsum("btf,fd->btd", hs, sh["w_down"].astype(dt))


def _layer(params, x, m: MoEConfig, daxes: tuple, eaxis):
    b, t, d = x.shape
    probs, experts, gates = route(params, x, m)
    aux = balance_loss(probs, experts, m)
    held = params["w_up"].shape[0]
    first = jax.lax.axis_index(eaxis) * held if eaxis else 0
    y, rows, most, buffered = _experts(params, x.reshape(b * t, d),
                             experts.reshape(b * t, -1),
                             gates.reshape(b * t, -1), m, first)
    y = y.reshape(b, t, d)
    sh = params.get("shared")
    split = (sh is not None and
             sh["w_up"].shape[1] < m.num_shared_experts * m.shared_d_expert)
    if split:
        y = y + _shared(params, x)
    if eaxis:
        # each device holds a block of the experts (and of the shared
        # width where it divides): their parts of every token's result add
        y = jax.lax.psum(y, eaxis)
        rows, buffered = jax.lax.psum((rows, buffered), eaxis)
        most = jax.lax.pmax(most, eaxis)
    if sh is not None and not split:
        y = y + _shared(params, x)
    load_max = jnp.where(rows > 0, most * m.held / jnp.maximum(rows, 1.0),
                         0.0)
    if daxes:
        aux = jax.lax.pmean(aux, daxes)
        rows, buffered = jax.lax.psum((rows, buffered), daxes)
        load_max = jax.lax.pmax(load_max, daxes)
    return y, MoEStats(aux, rows, load_max, buffered)


def _expert_axis(mesh, auto, params):
    """The mesh axis the expert stacks are split over (the `experts`
    sharding rule, as `distributed.params` lays the weights out), or None
    where it is not an Auto axis of more than one device or does not
    divide the held experts."""
    axes = (current_rules() or DEFAULT_RULES).rules.get("experts")
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    if len(axes) != 1 or axes[0] not in auto:
        return None
    size = mesh.shape[axes[0]]
    if size < 2 or params["w_up"].shape[0] % size:
        return None
    return axes[0]


@spans.scoped(spans.MOE)
def moe_apply(params, x, m: MoEConfig):
    """x: (b, t, d) -> (out, MoEStats); training, prefill and decode alike.

    A Mosaic call lowers only where every mesh axis is manual, so where the
    context mesh has Auto axes the layer runs in a `shard_map` manual over
    them.  Each device dispatches its own tokens (the batch split over the
    data axes; a batch they do not divide, as in the decode of fewer
    sequences than data shards, is whole on every device, as it is outside
    the layer).  The expert stacks stay split over the `experts` axis
    (`model`): each device computes its block of the held experts, and of
    the shared experts' width where that divides, for its tokens, and the
    parts are summed over the axis.  The counters are reduced over both."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = () if mesh.empty else tuple(mesh.auto_axes)
    if not auto:
        return _layer(params, x, m, (), None)
    daxes = tuple(a for a in data_axes(mesh) if a in auto)
    if x.shape[0] % math.prod(mesh.shape[a] for a in daxes):
        daxes = ()
    eaxis = _expert_axis(mesh, auto, params)
    xspec = P(daxes or None, None, None)
    pspec = jax.tree.map(lambda _: P(), params)
    if eaxis:
        pspec.update(w_gate=P(eaxis), w_up=P(eaxis), w_down=P(eaxis))
        sh = params.get("shared")
        if sh is not None and sh["w_up"].shape[1] % mesh.shape[eaxis] == 0:
            pspec["shared"] = {"w_gate": P(None, eaxis),
                               "w_up": P(None, eaxis),
                               "w_down": P(eaxis, None)}
    return jax.shard_map(
        lambda p, xx: _layer(p, xx, m, daxes, eaxis), in_specs=(pspec, xspec),
        out_specs=(xspec, MoEStats(P(), P(), P(), P())),
        axis_names=set(auto), check_vma=False)(params, x)
