"""Distributed train steps.

Two builders (DESIGN §2/§7):

* `make_fsdp_norm_step` — the paper's DDP-/FSDP-Norm in its JAX-native form:
  `shard_map` manual over the data axes (each manual instance is one of the
  paper's J workers), GSPMD auto over the `model` axis (parameter sharding =
  the FSDP/TP part).  The per-worker minibatch gradient g_j exists explicitly
  before the `pmean`, exactly like the pre-all-reduce gradient in PyTorch
  DDP/FSDP, and the eq.(5) statistic is computed from it.

* `make_accum_norm_step` — beyond-paper ACCUM-NORM under pure GSPMD with
  full-mesh FSDP parameter sharding: the variance statistic comes from the M
  gradient-accumulation microbatch gradients, so no manual axes are needed
  and parameters/moments shard over all 256/512 chips.

Both take a stacked-microbatch batch {tokens/labels: (M, B_global, seq)} and
perform: accumulate grads over M -> statistic -> AdamW -> metrics.

Two residency switches (both default 'tree'):

* `stats_impl={tree,flat}` — how the statistics+AdamW tail runs: leaf-by-leaf
  pytree walk, or the DESIGN §9 bucketed flat buffers with fused single-pass
  kernels.
* `params_impl={tree,flat}` — the residency format of the PARAMETERS
  (DESIGN §10): 'flat' makes the bucketed buffers the live format — the
  step unflattens them once, accumulates leaf cotangents with the tree
  path's exact arithmetic, and transposes the result through the explicit
  pad-slice adjoint (`layout.pack_cotangents`, the linear transpose of
  `unflatten`) so gradients are *born flat* and the steady-state step
  graph carries ZERO pack eqns (asserted by the DESIGN §13 jaxpr counter,
  `repro.analysis.count_layout_ops`, with stats_impl='flat'; the tree
  oracle stays available for the differential equivalence suite).  `unflatten_for_grad` is the custom-vjp form of the
  same adjoint, used where a single `jax.grad` spans the whole update
  (local-SGD) and by the adjoint microbenchmarks/property tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.core.norm_test import (
    worker_variance_stats, worker_variance_stats_buffers,
    worker_variance_stats_flat, paper_faithful_worker_variance,
    accum_variance_stats, tree_sqnorm)
from repro.optim.adamw import (
    AdamWConfig, init_adamw, init_adamw_flat, adamw_update,
    adamw_update_buffers, clip_scale_from_norm)
from repro.distributed.flatbuf import FlatLayout
from repro.distributed.params import param_pspecs, opt_pspecs
from repro.distributed.sharding import (
    DEFAULT_RULES, MULTIPOD_RULES, manual_data_rules, use_sharding_rules,
    with_sequence_parallel, flat_buffer_specs, gather_flat_buffers,
    shard_flat_buffers)
from repro.launch.mesh import data_axes, num_workers


def _manual_axes(mesh) -> tuple[str, ...]:
    """Manual axes of the hybrid steps' shard_map: the data axes, plus every
    axis of size 1.  A size-1 axis partitions nothing, so making it manual
    changes no layout — and a Mosaic (Pallas TPU) call lowers only where
    every mesh axis is manual, which a nested shard_map cannot provide."""
    daxes = data_axes(mesh)
    return tuple(a for a in mesh.axis_names
                 if a in daxes or mesh.shape[a] == 1)


def _tree_zeros_f32(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _rules_for(mesh):
    return MULTIPOD_RULES if "pod" in mesh.axis_names else DEFAULT_RULES


def _batch_pspec(batch_tree, daxes):
    """(M, B, ...) leaves: shard the global-batch dim over the data axes."""
    return jax.tree.map(lambda x: P(None, daxes) if x.ndim >= 2 else P(), batch_tree)


def _check_stats_impl(stats_impl: str, variance_impl: str = "scalar"):
    if stats_impl not in ("tree", "flat"):
        raise ValueError(f"stats_impl must be 'tree' or 'flat', got {stats_impl!r}")
    if stats_impl == "flat" and variance_impl == "paper":
        raise ValueError("variance_impl='paper' (full-vector all-reduce "
                         "baseline) has no flat-buffer path; use stats_impl='tree'")


def _check_params_impl(params_impl: str, variance_impl: str = "scalar"):
    if params_impl not in ("tree", "flat"):
        raise ValueError(
            f"params_impl must be 'tree' or 'flat', got {params_impl!r}")
    if params_impl == "flat" and variance_impl == "paper":
        raise ValueError("variance_impl='paper' walks tree-resident gradient "
                         "leaves; use params_impl='tree'")


def _opt_like_for(stats_impl: str, params_like, shard_divisor: int = 1,
                  layout=None):
    """Abstract optimizer state: pytree moments ('tree') or the DESIGN §9
    flat bucketed buffers ('flat', padded to `shard_divisor`-divisible
    buckets so they shard evenly over the data axes)."""
    if stats_impl == "flat":
        return jax.eval_shape(
            functools.partial(init_adamw_flat, shard_divisor=shard_divisor,
                              layout=layout),
            params_like)
    return jax.eval_shape(init_adamw, params_like)


def _worker_index(mesh, daxes):
    """This manual instance's flattened data-worker index j ∈ [0, J), first
    data axis major — the same order `P(daxes)` lays bucket shards out in."""
    idx = jnp.zeros((), jnp.int32)
    for a in daxes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _shard_bucket(b, idx, J):
    """Worker `idx`'s 1/J slice of one J-divisible bucket buffer (J is a
    trace-time constant: the J=1 slice is the identity, not a copy)."""
    if J == 1:
        return b
    n = b.shape[0] // J
    return jax.lax.dynamic_slice_in_dim(b, idx * n, n)


def _sharded_buffer_update(pb_local, gb, opt_state, opt_cfg, lr,
                           grad_sqnorm, mesh, daxes):
    """Core of the FSDP-style sharded flat AdamW inside the shard_map manual
    region (DESIGN §9/§10 sharded flat buckets).

    The moment buffers arrive as this worker's 1/J bucket shard (in_specs
    `P(daxes)`), and `pb_local` is the worker's 1/J slice of the packed
    parameter buffers; the mean-gradient buffers are replicated inside the
    manual region, so each worker slices out its own gradient shard and
    runs the fused update on 1/J of the data (per-worker moment memory AND
    update flops drop by J).  Bucket sizes are J-divisible by construction
    (`FlatLayout.from_tree(shard_divisor=J)`), so the slices are exact.
    `grad_sqnorm` is the globally-reduced Σ‖g‖² from the fused statistics —
    the clip scale needs the GLOBAL norm, which a per-shard kernel
    byproduct could not provide.

    Returns the worker's updated param SHARDS: the flat-resident step emits
    them directly (out_specs `P(daxes)`, the next step's `gather_flat_buffers`
    reassembles them); the tree-resident wrapper below all-gathers here."""
    J = num_workers(mesh)
    idx = _worker_index(mesh, daxes) if J > 1 else jnp.zeros((), jnp.int32)
    gb_local = [_shard_bucket(b, idx, J) for b in gb]
    new_pl, new_mb, new_vb, count, gnorm, _ = adamw_update_buffers(
        list(pb_local), gb_local, list(opt_state["m"]), list(opt_state["v"]),
        opt_cfg, lr, opt_state["count"], grad_sqnorm=grad_sqnorm)
    new_opt = {"m": tuple(new_mb), "v": tuple(new_vb), "count": count}
    return new_pl, new_opt, gnorm


def _flat_sharded_update(layout, params, gb, opt_state, opt_cfg, lr,
                         grad_sqnorm, mesh, daxes):
    """Tree-resident wrapper over `_sharded_buffer_update`: pack the params
    once against the shared layout, slice this worker's shard, update, and
    all-gather only the updated parameter shards back to the replicated
    pytree layout (DESIGN §9 dataflow for stats_impl='flat')."""
    J = num_workers(mesh)
    idx = _worker_index(mesh, daxes)
    pb_local = [_shard_bucket(b, idx, J) for b in layout.flatten(params)]
    new_pl, new_opt, gnorm = _sharded_buffer_update(
        pb_local, gb, opt_state, opt_cfg, lr, grad_sqnorm, mesh, daxes)
    new_pb = (new_pl if J == 1 else
              [jax.lax.all_gather(p, daxes, tiled=True) for p in new_pl])
    return layout.unflatten(new_pb), new_opt, gnorm


def _accumulate(loss_fn, params, batch, track_micro_sqnorm: bool):
    """lax.scan over the M stacked microbatches; returns (mean grads g,
    mean loss, mean aux, Σ_m ‖ĝ^m‖² if tracked, effective microbatch count).

    `loss_fn(params, microbatch) -> (loss, metrics)`; `params` is whatever
    the loss differentiates — the model pytree, or a tuple of flat-resident
    buffers (DESIGN §10), in which case the gradients accumulate as f32
    buffers: everything here is residency-agnostic tree arithmetic.

    Microbatch contributions are weighted by their VALID-TOKEN count
    (labels >= 0), normalized by the total.  With the full, equal-sized
    microbatches of an unpadded batch this is exactly the old uniform mean;
    under the bucketed engine's padding (DESIGN §8) it makes padded slots —
    whole microbatches of `labels = -1` slots or a padded tail inside one —
    contribute nothing, so padded and unpadded batches produce identical
    loss and gradients."""

    def body(carry, mb):
        acc_g, acc_loss, acc_aux, acc_sq, acc_w, acc_m = carry
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        w = jnp.sum(mb["labels"] >= 0).astype(jnp.float32)
        acc_g = jax.tree.map(lambda a, b: a + w * b.astype(jnp.float32), acc_g, g)
        if track_micro_sqnorm:
            # fully-padded microbatches carry no gradient draw: skip them in
            # the Σ_m ‖ĝ^m‖² used by the accumulation-variance estimator
            acc_sq = acc_sq + jnp.where(w > 0, tree_sqnorm(g), 0.0)
        return (acc_g, acc_loss + w * loss, acc_aux + w * metrics["aux"],
                acc_sq, acc_w + w, acc_m + (w > 0)), None

    init = (_tree_zeros_f32(params), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    (acc_g, acc_loss, acc_aux, acc_sq, acc_w, acc_m), _ = \
        jax.lax.scan(body, init, batch)
    denom = jnp.maximum(acc_w, 1.0)
    g = jax.tree.map(lambda x: x / denom, acc_g)
    return g, acc_loss / denom, acc_aux / denom, acc_sq, acc_m, acc_w


def _accumulate_buffers(loss_fn, layout, pb, batch,
                        track_micro_sqnorm: bool):
    """Flat-resident gradient accumulation (DESIGN §10): unflatten the
    param buffers ONCE per step, let the microbatch scan accumulate
    per-micro leaf cotangents with the EXACT arithmetic of the tree path
    (`_accumulate` on the tree view — XLA fuses the leaf adds into the
    backward; per-micro Σ‖ĝ^m‖² comes for free when tracked), and
    transpose the accumulated cotangent through the explicit pad-slice
    adjoint (`layout.pack_cotangents`) exactly once: one gradient-size
    concat per step, not M.

    Two rejected alternatives, for the record: differentiating the loss
    through unflatten per MICROBATCH accumulates in buffer space — an
    extra gradient-size concat+add every scan iteration, measured ~15% of
    CPU step time at M=4; differentiating the whole scan in one
    `jax.grad` folds the 1/W normalization into each microbatch cotangent,
    drifting ~5e-5 from the tree oracle over 5 AdamW steps.  The adjoint
    is LINEAR, so transposing the accumulated cotangent here is bit-exact
    to accumulating per-micro transposed buffers — and applying it via
    `pack_cotangents` (not a dtype-strict `jax.vjp`) keeps the f32
    accumulators intact for low-precision params, matching the tree path
    and the flat-stats pack of f32 gradients exactly.

    Returns `_accumulate`'s tuple with g as born-flat f32 buffers."""
    tree = layout.unflatten(list(pb))
    g_tree, loss, aux, sq, m_eff, w = _accumulate(loss_fn, tree, batch,
                                                  track_micro_sqnorm)
    gb = layout.pack_cotangents(g_tree)
    return gb, loss, aux, sq, m_eff, w


# --------------------------------------------------------- FSDP-Norm ----

def make_fsdp_norm_step(model, opt_cfg: AdamWConfig, mesh, *,
                        variance_impl: str = "scalar",
                        stats_impl: str = "tree",
                        params_impl: str = "tree",
                        sequence_parallel: bool = False,
                        params_like=None, jit: bool = True):
    """variance_impl: 'scalar' (pre-reduced 8-byte collective, DESIGN §7.1)
    or 'paper' (eq. 5 literal: all-reduce the full (g_j-g)² vector).

    stats_impl: 'tree' (leaf-by-leaf reference path) or 'flat' (DESIGN §9:
    bucketed flat buffers, single-pass fused statistics, one AdamW launch
    per bucket; optimizer state from `init_adamw_flat(shard_divisor=J)` —
    the moment buffers are SHARDED over the data axes, and the mean
    gradient is packed exactly once per step).

    params_impl: 'tree' (params are the model pytree, replicated across the
    data axes) or 'flat' (DESIGN §10: params REST as their `P(daxes)` 1/J
    bucket shard; the step all-gathers the shards into full buffers, the
    accumulated gradient transposes through the explicit pad-slice adjoint
    so it is born flat, and only the worker's updated param shard leaves
    the step — with stats_impl='flat' the steady-state step performs ZERO
    packs).

    The shared per-step-signature `FlatLayout` is exposed as
    `wrap.flat_layout` (None on the pure tree path) so callers — the
    training loop, the bucketed engine, checkpointing — reuse ONE layout
    across every ladder rung instead of rebuilding per trace."""
    _check_stats_impl(stats_impl, variance_impl)
    _check_params_impl(params_impl, variance_impl)
    daxes = data_axes(mesh)
    J = num_workers(mesh)
    base = _rules_for(mesh)
    if sequence_parallel:
        base = with_sequence_parallel(base)
    manual = _manual_axes(mesh)
    rules = manual_data_rules(base, manual)

    if params_like is None:
        params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # ONE layout per step signature, shared by the statistics and the AdamW
    # tail (packs happen against it exactly once per tree per step) and by
    # every bucket the engine compiles
    layout = (FlatLayout.from_tree(params_like, shard_divisor=J)
              if (stats_impl == "flat" or params_impl == "flat") else None)

    def inner(params, opt_state, batch, lr):
        with use_sharding_rules(rules, mesh):
            if params_impl == "flat":
                # params arrive as this worker's 1/J bucket shard; gather to
                # full buffers and differentiate the whole accumulation
                # straight through unflatten — g_j is born flat, one
                # adjoint pack for the whole step (J is static: no gather
                # ops on a 1-worker mesh)
                pb_full = (tuple(params) if J == 1 else
                           tuple(gather_flat_buffers(params, daxes)))
                g_j, loss, aux, _, _, w_j = _accumulate_buffers(
                    model.loss, layout, pb_full, batch, False)
            else:
                g_j, loss, aux, _, _, w_j = _accumulate(
                    model.loss, params, batch, False)
            # valid-token-weighted mean over workers: equals plain pmean on
            # unpadded batches; exact under the engine's padding even when
            # the padded tail lands unevenly across workers (DESIGN §8)
            w_sum = jnp.maximum(jax.lax.psum(w_j, daxes), 1.0)
            g = jax.tree.map(
                lambda x: jax.lax.psum(x * w_j, daxes) / w_sum, g_j)
            if params_impl == "flat":
                if stats_impl == "flat":
                    # born-flat single-pass pair: no pack anywhere
                    var_l1, gsq = worker_variance_stats_buffers(g_j, g, daxes)
                else:
                    # tree oracle over the unflattened gradient views
                    var_l1, gsq = worker_variance_stats(
                        layout.unflatten(list(g_j)), layout.unflatten(list(g)),
                        daxes)
            elif stats_impl == "flat":
                # single-pass fused pair; the packed mean-gradient buffers
                # come back and feed the update directly — g is packed ONCE
                var_l1, gsq, gb = worker_variance_stats_flat(
                    g_j, g, daxes, layout=layout)
            elif variance_impl == "paper":
                var_l1, gsq = paper_faithful_worker_variance(g_j, g, daxes)
            else:
                var_l1, gsq = worker_variance_stats(g_j, g, daxes)
            loss = jax.lax.psum(loss * w_j, daxes) / w_sum
            aux = jax.lax.psum(aux * w_j, daxes) / w_sum
            if params_impl == "flat":
                if stats_impl == "flat":
                    # the input params ARE the worker's param shard; the
                    # updated shards leave the step directly (the next
                    # step's gather reassembles them)
                    new_pl, new_opt, gnorm = _sharded_buffer_update(
                        list(params), list(g), opt_state, opt_cfg, lr, gsq,
                        mesh, daxes)
                    new_params = tuple(new_pl)
                else:
                    # tree-oracle tail on the unflattened views, then one
                    # pack + slice back to the resident shard
                    new_tree, new_opt, gnorm = adamw_update(
                        layout.unflatten(list(pb_full)),
                        layout.unflatten(list(g)), opt_state, opt_cfg, lr)
                    idx = _worker_index(mesh, daxes)
                    new_params = tuple(
                        _shard_bucket(b, idx, J)
                        for b in layout.flatten(new_tree))
            elif stats_impl == "flat":
                # per-bucket fused AdamW on this worker's 1/J bucket shard;
                # the ‖g‖² from the statistics doubles as the clip norm
                new_params, new_opt, gnorm = _flat_sharded_update(
                    layout, params, gb, opt_state, opt_cfg, lr, gsq,
                    mesh, daxes)
            else:
                new_params, new_opt, gnorm = adamw_update(
                    params, g, opt_state, opt_cfg, lr)
        metrics = {"loss": loss, "aux": aux, "var_l1": var_l1,
                   "grad_sqnorm": gsq, "grad_norm": gnorm,
                   "clip_scale": clip_scale_from_norm(gnorm, opt_cfg.grad_clip)}
        return new_params, new_opt, metrics

    p_tree_specs = param_pspecs(params_like, mesh, fsdp=False)
    # bucketed 1-D param buffers REST as their P(daxes) 1/J shard
    p_specs = (flat_buffer_specs(layout.num_buffers, daxes)
               if params_impl == "flat" else p_tree_specs)
    opt_like = _opt_like_for(stats_impl, params_like, shard_divisor=J,
                             layout=layout)
    if stats_impl == "flat":
        # bucketed 1-D buffers: moments sharded over the data axes (the
        # per-worker ~J× optimizer-memory saving), step count replicated
        bspecs = flat_buffer_specs(layout.num_buffers, daxes)
        o_specs = {"m": bspecs, "v": bspecs, "count": P()}
    else:
        o_specs = {"m": p_tree_specs, "v": p_tree_specs, "count": P()}

    def batch_specs(batch_like):
        return _batch_pspec(batch_like, daxes)

    # inside the manual region, sharded flat buffers (moments, and the param
    # buffers on the flat-resident path) enter/leave as the worker's local
    # shard; everything else stays replicated
    o_sm_specs = (o_specs if stats_impl == "flat"
                  else jax.tree.map(lambda _: P(), opt_like))
    p_sm_specs = (p_specs if params_impl == "flat"
                  else jax.tree.map(lambda _: P(), params_like))

    def wrap(batch_like):
        sm = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(p_sm_specs,
                      o_sm_specs,
                      batch_specs(batch_like), P()),
            out_specs=(p_sm_specs,
                       o_sm_specs,
                       {"loss": P(), "aux": P(), "var_l1": P(),
                        "grad_sqnorm": P(), "grad_norm": P(),
                        "clip_scale": P()}),
            axis_names=set(manual), check_vma=False)
        if not jit:
            return sm
        return jax.jit(
            sm,
            in_shardings=(
                jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda s: NamedSharding(mesh, s),
                             batch_specs(batch_like),
                             is_leaf=lambda s: isinstance(s, P)),
                None),
            out_shardings=(
                jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                None),
            donate_argnums=(0, 1))

    wrap.flat_layout = layout
    return wrap, p_specs, o_specs


# -------------------------------------------------------- ACCUM-NORM ----

def make_accum_norm_step(model, opt_cfg: AdamWConfig, mesh, *,
                         stats_impl: str = "tree",
                         params_impl: str = "tree",
                         params_like=None, jit: bool = True):
    """Beyond-paper: pure-GSPMD step with full-mesh FSDP params; variance from
    accumulation microbatches (requires M >= 2 for a signal).

    stats_impl='flat' (DESIGN §9): the AdamW tail runs over bucketed flat
    buffers and its Σ‖g‖² kernel byproduct feeds the variance statistic and
    the grad_norm metric — zero extra gradient-sized passes, and the mean
    gradient is packed exactly once per step.  Flat moment buffers carry
    data-axis `PartitionSpec`s (J-divisible buckets), so the flat path
    composes with full-mesh FSDP instead of replicating optimizer state.

    params_impl='flat' (DESIGN §10): the param buffers themselves are the
    residency format (jit in/out shardings `P(daxes)` per bucket, GSPMD
    partitions the tail); the accumulated gradient transposes through the
    explicit pad-slice adjoint, so it is born flat — with stats_impl='flat'
    the step performs ZERO packs.  The shared layout is exposed as
    `wrap.flat_layout`."""
    _check_stats_impl(stats_impl)
    _check_params_impl(params_impl)
    daxes = data_axes(mesh)
    rules = _rules_for(mesh)
    J = num_workers(mesh)

    if params_like is None:
        params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = (FlatLayout.from_tree(params_like, shard_divisor=J)
              if (stats_impl == "flat" or params_impl == "flat") else None)

    def step(params, opt_state, batch, lr):
        with use_sharding_rules(rules, mesh):
            # constrain the batch over data axes (GSPMD)
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, P(None, daxes)) if x.ndim >= 2 else x, batch)
            if params_impl == "flat":
                # no sharding constraint on the param buffers: they arrive
                # as committed jit inputs already carrying the P(daxes)
                # in_shardings
                pb = tuple(params)
                g, loss, aux, sq_sum, m_eff, _ = _accumulate_buffers(
                    model.loss, layout, pb, batch, True)
                gb = shard_flat_buffers(list(g), daxes)
                if stats_impl == "flat":
                    # born-flat buffers straight into the fused tail: the
                    # Σg² byproduct feeds the variance statistic — no packs
                    new_pb, new_mb, new_vb, count, gnorm, gsq = \
                        adamw_update_buffers(
                            list(pb), gb, list(opt_state["m"]),
                            list(opt_state["v"]), opt_cfg, lr,
                            opt_state["count"])
                    new_params = tuple(new_pb)
                    new_opt = {"m": tuple(new_mb), "v": tuple(new_vb),
                               "count": count}
                    var_l1, gsq = accum_variance_stats(sq_sum, None, m_eff, J,
                                                       gsq=gsq)
                else:
                    # tree-oracle tail over the unflattened views, then one
                    # pack back to the resident buffers
                    g_tree = layout.unflatten(gb)
                    var_l1, gsq = accum_variance_stats(sq_sum, g_tree,
                                                       m_eff, J)
                    new_tree, new_opt, gnorm = adamw_update(
                        layout.unflatten(list(pb)), g_tree, opt_state,
                        opt_cfg, lr)
                    new_params = tuple(shard_flat_buffers(
                        layout.flatten(new_tree), daxes))
            else:
                g, loss, aux, sq_sum, m_eff, _ = _accumulate(
                    model.loss, params, batch, True)
                if stats_impl == "flat":
                    # pack g and params ONCE against the shared layout, keep
                    # the buffers on the data axes, and run the pack-free tail
                    gb = shard_flat_buffers(layout.flatten(g), daxes)
                    pb = shard_flat_buffers(layout.flatten(params), daxes)
                    new_pb, new_mb, new_vb, count, gnorm, gsq = \
                        adamw_update_buffers(
                            pb, gb, list(opt_state["m"]),
                            list(opt_state["v"]),
                            opt_cfg, lr, opt_state["count"])
                    new_params = layout.unflatten(new_pb)
                    new_opt = {"m": tuple(new_mb), "v": tuple(new_vb),
                               "count": count}
                    var_l1, gsq = accum_variance_stats(sq_sum, g, m_eff, J,
                                                       gsq=gsq)
                else:
                    var_l1, gsq = accum_variance_stats(sq_sum, g, m_eff, J)
                    new_params, new_opt, gnorm = adamw_update(
                        params, g, opt_state, opt_cfg, lr)
        metrics = {"loss": loss, "aux": aux, "var_l1": var_l1,
                   "grad_sqnorm": gsq, "grad_norm": gnorm,
                   "clip_scale": clip_scale_from_norm(gnorm, opt_cfg.grad_clip)}
        return new_params, new_opt, metrics

    if params_impl == "flat":
        p_specs = flat_buffer_specs(layout.num_buffers, daxes)
    else:
        p_specs = param_pspecs(params_like, mesh, fsdp=True)
    if stats_impl == "flat":
        bspecs = flat_buffer_specs(layout.num_buffers, daxes)
        o_specs = {"m": bspecs, "v": bspecs, "count": P()}
    else:
        tree_specs = param_pspecs(params_like, mesh, fsdp=True)
        o_specs = {"m": tree_specs, "v": tree_specs, "count": P()}

    def wrap(batch_like):
        if not jit:
            return step
        return jax.jit(
            step,
            in_shardings=(
                jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda x: NamedSharding(mesh, P(None, daxes))
                             if x.ndim >= 2 else NamedSharding(mesh, P()),
                             batch_like),
                None),
            # pin outputs to the declared layout: GSPMD propagation would
            # otherwise pick its own param/moment shardings, and feeding
            # step t's output into step t+1 would conflict with in_shardings
            out_shardings=(
                jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                             is_leaf=lambda s: isinstance(s, P)),
                None),
            donate_argnums=(0, 1))

    wrap.flat_layout = layout
    return wrap, p_specs, o_specs
