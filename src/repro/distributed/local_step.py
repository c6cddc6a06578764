"""Communication-efficient local-update training with adaptive batch sizes —
the paper's companion scheme (Lau, Li, Xu, Liu, Kolar, arXiv:2406.13936,
cited in the paper's introduction as the local-gradient-method extension).

Each data-parallel worker takes H local AdamW steps on its own replica
between synchronizations; at sync, parameters and moments are averaged
(one all-reduce per H steps instead of per step), and the adaptive batch
statistic is computed from the *divergence of worker updates*:

    Δ_j = w_j^{(H)} − w^{(0)},   Δ = (1/J) Σ_j Δ_j
    var_l1 = (1/J) Σ_j ‖Δ_j − Δ‖²,  stat vs ‖Δ‖²

which plays the role eq. (5)'s per-worker gradient variance plays in
DDP-Norm: high inter-worker divergence ⇒ the local batches are too noisy ⇒
Algorithm 1 grows them.  Same controller, same rounding.

`params_impl='flat'` (DESIGN §10) keeps the replica flat-RESIDENT through
the whole round: every local step differentiates
`layout.unflatten_for_grad`, so local gradients are born flat, the fused
buffer AdamW updates the buffers in place, and the update-divergence
statistic is a plain buffer subtraction — the round performs ZERO packs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.core.norm_test import (
    tree_sqdiff, tree_sqnorm, worker_variance_stats_buffers,
    worker_variance_stats_flat)
from repro.distributed.flatbuf import FlatLayout
from repro.optim.adamw import (
    AdamWConfig, init_adamw, init_adamw_flat, adamw_update,
    adamw_update_buffers)
from repro.distributed.params import param_pspecs
from repro.distributed.sharding import (
    flat_buffer_specs, manual_data_rules, use_sharding_rules)
from repro.distributed.train_step import (
    _rules_for, _batch_pspec, _manual_axes, _check_params_impl)
from repro.launch.mesh import data_axes


def make_local_sgd_step(model, opt_cfg: AdamWConfig, mesh, *,
                        stats_impl: str = "tree",
                        params_impl: str = "tree",
                        params_like=None, jit: bool = True):
    """Returns wrap(batch_like) -> jitted round function:
        round(params, opt_state, batch, lr) -> (params', opt', metrics)
    where batch leaves are (H, B_global, ...) — H local steps per sync.

    stats_impl='flat' computes the update-divergence statistic (‖Δ_j − Δ‖²
    and ‖Δ‖²) via the single-pass fused kernel over bucketed flat buffers
    (DESIGN §9) instead of the leaf-by-leaf sqdiff + sqnorm double pass.

    params_impl='flat' makes the replica flat-resident for the whole round
    (DESIGN §10): local gradients are born flat, the buffer AdamW runs per
    bucket, Δ_j/Δ are buffer subtractions, and sync averages buffers —
    zero packs per round.  Requires a flat optimizer state
    (`init_adamw_flat`); the shared layout is exposed as
    `wrap.flat_layout`."""
    if stats_impl not in ("tree", "flat"):
        raise ValueError(f"stats_impl must be 'tree' or 'flat', got {stats_impl!r}")
    _check_params_impl(params_impl)
    if params_impl == "flat" and stats_impl == "tree":
        # unlike the train-step builders there is no tree-ORACLE tail over
        # flat params here: the flat round always runs the buffer AdamW, so
        # accepting this combo would silently give flat semantics under a
        # tree label (and a tree opt state would mismatch the flat o_specs)
        raise ValueError("local-SGD has no tree-oracle tail over flat "
                         "params; use stats_impl='flat' with "
                         "params_impl='flat'")
    daxes = data_axes(mesh)
    manual = _manual_axes(mesh)
    rules = manual_data_rules(_rules_for(mesh), manual)

    if params_like is None:
        params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # one layout per step signature: the update-divergence trees (Δ_j, Δ)
    # are param-shaped, so they pack through the params layout (replicas are
    # per-worker whole copies here — no shard divisor)
    layout = (FlatLayout.from_tree(params_like)
              if (stats_impl == "flat" or params_impl == "flat") else None)

    def inner_tree(params, opt_state, batch, lr):
        with use_sharding_rules(rules, mesh):
            def local_step(carry, mb):
                p, o = carry
                (loss, _), g = jax.value_and_grad(
                    lambda q: model.loss(q, mb), has_aux=True)(p)
                p, o, _ = adamw_update(p, g, o, opt_cfg, lr)
                return (p, o), loss

            (p_j, o_j), losses = jax.lax.scan(local_step, (params, opt_state),
                                              batch)
            # inter-worker update divergence (the adaptive-batch statistic)
            delta_j = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p_j, params)
            delta = jax.tree.map(lambda x: jax.lax.pmean(x, daxes), delta_j)
            if stats_impl == "flat":
                # fused single-pass pair over bucketed flat buffers: pmean of
                # the local scalar + ‖Δ‖², one read of Δ_j and Δ (the shared
                # layout means each tree is packed exactly once)
                var_l1, dsq, _ = worker_variance_stats_flat(
                    delta_j, delta, daxes, layout=layout)
            else:
                var_l1 = jax.lax.pmean(tree_sqdiff(delta_j, delta), daxes)
                dsq = tree_sqnorm(delta)
            # synchronize: average replicas (params AND moments)
            p_avg = jax.tree.map(lambda x: jax.lax.pmean(x, daxes), p_j)
            o_avg = {
                "m": jax.tree.map(lambda x: jax.lax.pmean(x, daxes), o_j["m"]),
                "v": jax.tree.map(lambda x: jax.lax.pmean(x, daxes), o_j["v"]),
                "count": o_j["count"],
            }
            loss = jax.lax.pmean(jnp.mean(losses), daxes)
        metrics = {"loss": loss, "var_l1": var_l1, "grad_sqnorm": dsq,
                   "aux": jnp.zeros((), jnp.float32),
                   "grad_norm": jnp.sqrt(dsq)}
        return p_avg, o_avg, metrics

    def inner_flat(pb, opt_state, batch, lr):
        with use_sharding_rules(rules, mesh):
            def local_step(carry, mb):
                p, o = carry
                (loss, _), gb = jax.value_and_grad(
                    lambda q: model.loss(layout.unflatten_for_grad(q), mb),
                    has_aux=True)(p)
                new_p, new_m, new_v, count, _, _ = adamw_update_buffers(
                    list(p), list(gb), list(o["m"]), list(o["v"]),
                    opt_cfg, lr, o["count"])
                o = {"m": tuple(new_m), "v": tuple(new_v), "count": count}
                return (tuple(new_p), o), loss

            (p_j, o_j), losses = jax.lax.scan(local_step, (pb, opt_state),
                                              batch)
            # born-flat update divergence: plain buffer arithmetic, no pack
            # (the builder rejects tree stats over flat params, so the
            # fused buffer pair is the only statistics path here)
            delta_j = [a.astype(jnp.float32) - b.astype(jnp.float32)
                       for a, b in zip(p_j, pb)]
            delta = [jax.lax.pmean(x, daxes) for x in delta_j]
            var_l1, dsq = worker_variance_stats_buffers(delta_j, delta, daxes)
            p_avg = tuple(jax.lax.pmean(b, daxes) for b in p_j)
            o_avg = {
                "m": tuple(jax.lax.pmean(b, daxes) for b in o_j["m"]),
                "v": tuple(jax.lax.pmean(b, daxes) for b in o_j["v"]),
                "count": o_j["count"],
            }
            loss = jax.lax.pmean(jnp.mean(losses), daxes)
        metrics = {"loss": loss, "var_l1": var_l1, "grad_sqnorm": dsq,
                   "aux": jnp.zeros((), jnp.float32),
                   "grad_norm": jnp.sqrt(dsq)}
        return p_avg, o_avg, metrics

    if params_impl == "flat":
        inner = inner_flat
        # whole-replica buffers: replicated across workers like the tree
        # path (empty axes => flat_buffer_specs degrades to P() per bucket)
        bspecs = flat_buffer_specs(layout.num_buffers, ())
        p_specs = bspecs
        opt_like = jax.eval_shape(
            lambda p: init_adamw_flat(p, layout=layout), params_like)
        o_specs = {"m": bspecs, "v": bspecs, "count": P()}
    else:
        inner = inner_tree
        p_specs = param_pspecs(params_like, mesh, fsdp=False)
        opt_like = jax.eval_shape(init_adamw, params_like)
        o_specs = {"m": p_specs, "v": p_specs, "count": P()}

    # everything is replicated inside the manual region; the flat p_specs
    # are already all-P(), the tree specs must be stripped to P()
    p_sm_specs = (p_specs if params_impl == "flat"
                  else jax.tree.map(lambda _: P(), params_like))

    def wrap(batch_like):
        sm = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(p_sm_specs,
                      jax.tree.map(lambda _: P(), opt_like),
                      _batch_pspec(batch_like, daxes), P()),
            out_specs=(p_sm_specs,
                       jax.tree.map(lambda _: P(), opt_like),
                       {"loss": P(), "var_l1": P(), "grad_sqnorm": P(),
                        "aux": P(), "grad_norm": P()}),
            axis_names=set(manual), check_vma=False)
        if not jit:
            return sm
        ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                       is_leaf=lambda s: isinstance(s, P))
        return jax.jit(
            sm,
            in_shardings=(ns(p_specs), ns(o_specs),
                          ns(_batch_pspec(batch_like, daxes)), None),
            out_shardings=(ns(p_specs), ns(o_specs), None),
            donate_argnums=(0, 1))

    wrap.flat_layout = layout
    return wrap, p_specs, o_specs
