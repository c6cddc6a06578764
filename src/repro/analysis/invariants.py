"""The step-graph invariant catalog + trace-only matrix checker (DESIGN §13).

`run_invariant_checks()` builds every step variant the repo ships — the
stats×params residency matrix over FSDP-Norm, ACCUM-NORM, and local-SGD,
plus the serving slot-decode step — at smoke scale, TRACES each one (never
executes, never compiles to a loaded executable), and statically asserts:

* **layout op counts** (`EXPECTED_LAYOUT_COUNTS`): the exact number of
  pack / unflatten / adjoint-pack marker eqns in the step graph.  Frozen
  per residency combo; a drift in `pack` is the PR 3 double-pack class, a
  drift in `adjoint` means a gradient is being transposed more than once.
* **donation effectiveness**: every input the step declares donated is
  actually aliased to an output in the lowered HLO (`tf.aliasing_output`).
  A donation XLA silently drops doubles the step's parameter/optimizer
  memory — invisible until OOM at scale.
* **sharding agreement**: the traced pjit's input shardings equal the
  builder's declared (p_specs, o_specs), and flat bucket groups carry
  exactly `sharding.flat_buffer_specs` (data-sharded moments, DESIGN §9).
* **no host exits**: no callback / debug_print / infeed / interpreted
  Pallas eqn anywhere in the hot-path graph.
* **ladder hygiene**: every traced batch signature sits on its ladder, and
  an off-ladder batch is rejected by `BucketedEngine.get_step` with
  `LadderShapeError` BEFORE anything traces (`stats.compiles == 0`).

Run it via ``python -m repro.analysis`` (CI's static-analysis gate) or
call the functions directly from tests.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_check import (
    count_layout_ops, donation_effective, find_host_eqns, in_specs, trace)


@dataclass(frozen=True)
class LayoutCounts:
    """Frozen marker-eqn counts for one step graph (see the catalog)."""
    packs: int
    unflattens: int
    adjoints: int


# The invariant catalog: layout op counts per (step, stats_impl, params_impl)
# residency combo.  The `packs` column is the historical pack-count
# regression matrix (tests/test_flatbuf.py); `unflattens`/`adjoints` are the
# jaxpr-visible counts the removed Python-call proxy could never see.
EXPECTED_LAYOUT_COUNTS = {
    # FSDP-Norm, flat stats over tree params: packs g_j, mean g, and the
    # params (3) — the PR 3 regression packed g TWICE here (packs=4); one
    # unflatten returns the updated params to tree form.
    ("fsdp_norm", "flat", "tree"): LayoutCounts(3, 1, 0),
    # ACCUM-NORM, flat stats over tree params: packs mean g + params (2),
    # one unflatten back out.
    ("accum_norm", "flat", "tree"): LayoutCounts(2, 1, 0),
    # flat-RESIDENT params (DESIGN §10): ZERO host-level packs; exactly the
    # `unflatten_for_grad` custom-vjp pair — ONE unflatten (the primal view
    # the loss consumes; accumulation scans trace their body once, so M/H
    # never multiply it) and ONE adjoint pack (the gradient transposed into
    # buffers exactly once).
    ("fsdp_norm", "flat", "flat"): LayoutCounts(0, 1, 1),
    ("accum_norm", "flat", "flat"): LayoutCounts(0, 1, 1),
    # tree-oracle tail over flat-resident params: the custom-vjp pair, plus
    # oracle handoffs — ACCUM-NORM unflattens pb + accumulated g for the
    # tree AdamW (3 total with the primal); FSDP-Norm also unflattens
    # g_j + g for the tree variance oracle (5); the ONE pack is the updated
    # tree re-entering residency.
    ("fsdp_norm", "tree", "flat"): LayoutCounts(1, 5, 1),
    ("accum_norm", "tree", "flat"): LayoutCounts(1, 3, 1),
    # pure tree paths: the layout is never entered.
    ("fsdp_norm", "tree", "tree"): LayoutCounts(0, 0, 0),
    ("accum_norm", "tree", "tree"): LayoutCounts(0, 0, 0),
    # local-SGD rounds: flat stats pack the divergence trees Δ_j and Δ (2,
    # via worker_variance_stats_flat); the flat-resident round is buffer
    # arithmetic end-to-end — just the custom-vjp pair from the scanned
    # local step (traced once regardless of H).
    ("local_sgd", "tree", "tree"): LayoutCounts(0, 0, 0),
    ("local_sgd", "flat", "tree"): LayoutCounts(2, 0, 0),
    ("local_sgd", "flat", "flat"): LayoutCounts(0, 1, 1),
    # accumulation-free M=1 sub-steps (DESIGN §14): the train loop slices
    # one microbatch per optimizer step, so the engine sees (1, J·mb)
    # leading dims — same step builders, same custom-vjp pair (the scan
    # body is traced once regardless of M, so M=1 changes nothing the
    # layout budget can see; what this guards is that it STAYS that way,
    # since the accum-free regime was untraced before this entry).
    ("fsdp_norm_m1", "flat", "flat"): LayoutCounts(0, 1, 1),
    ("accum_norm_m1", "flat", "flat"): LayoutCounts(0, 1, 1),
    # serving decode: the KV cache is resident, nothing enters a layout.
    ("serve_decode", "-", "-"): LayoutCounts(0, 0, 0),
}


@dataclass
class StepVariant:
    """One traced-step check target (built by `build_variants`)."""
    name: str
    fn: object                  # the jitted step
    args: tuple                 # abstract operands (ShapeDtypeStructs)
    expected: LayoutCounts
    # expected PartitionSpec per flat input of the (params, opt/cache)
    # prefix, as the builder declared them
    spec_prefix: list
    # (group label, declared specs, required specs) triples for flat bucket
    # groups that must match sharding.flat_buffer_specs
    flat_groups: list
    # the builder's FlatLayout (None on tree paths) — layer 3 attributes
    # collectives to bucket groups by matching operand sizes against it
    layout: object = None
    # the mesh the step traces under (None: the smoke mesh); a step whose
    # shard_map names its own mesh must trace under that one
    mesh: object = None


# ------------------------------------------------------- variant builders ----

_SMOKE_CACHE = []


def _smoke_parts():
    """One smoke-scale (config, model, mesh) per process — every variant
    and every `check_variant` call shares it."""
    if not _SMOKE_CACHE:
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_host_mesh
        cfg = get_smoke_config("llama3.2-1b")
        _SMOKE_CACHE.append((cfg, build_model(cfg),
                             make_host_mesh(data=1, model=1)))
    return _SMOKE_CACHE[0]


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _spec_leaves(tree):
    return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P))


def build_variants(combos=None) -> list[StepVariant]:
    """Every step variant in the matrix, at smoke scale, fully abstract.

    `combos` optionally restricts to a subset of
    `EXPECTED_LAYOUT_COUNTS` keys (tests use this to keep one check
    fast)."""
    from jax import set_mesh
    from repro.core.schedule import BatchPlan, accum_free_plan
    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.distributed.local_step import make_local_sgd_step
    from repro.distributed.serve_step import make_slot_decode_step
    from repro.distributed.sharding import flat_buffer_specs
    from repro.distributed.train_step import (
        make_accum_norm_step, make_fsdp_norm_step)
    from repro.launch.mesh import data_axes
    from repro.optim.adamw import (
        AdamWConfig, init_adamw, init_adamw_flat)

    cfg, model, mesh = _smoke_parts()
    daxes = data_axes(mesh)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    plan = BatchPlan(global_batch=4, micro_batch=2, accum_steps=2, workers=1)
    batch = _abstract(jax.tree.map(jnp.asarray, make_batch(src, 0, plan, 16)))
    # the PR 9 accumulation-free regime: the SAME builders stepped at the
    # M=1 sub-plan (leading dims (1, J·mb)), exactly what the train loop
    # slices per optimizer step when `accum_free` engages
    sub_plan, _ = accum_free_plan(plan)
    batch_m1 = _abstract(jax.tree.map(jnp.asarray,
                                      make_batch(src, 0, sub_plan, 16)))
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    wanted = set(combos) if combos is not None else None
    makers = {"fsdp_norm": make_fsdp_norm_step,
              "accum_norm": make_accum_norm_step,
              "local_sgd": make_local_sgd_step}
    variants = []

    def add_train(step_impl, stats_impl, params_impl):
        key = (step_impl, stats_impl, params_impl)
        if wanted is not None and key not in wanted:
            return
        accum_free = step_impl.endswith("_m1")
        base_impl = step_impl[:-3] if accum_free else step_impl
        wrap, p_specs, o_specs = makers[base_impl](
            model, AdamWConfig(), mesh, stats_impl=stats_impl,
            params_impl=params_impl, params_like=params_like)
        layout = wrap.flat_layout
        # optimizer residency: the train steps key it on stats_impl (the
        # flat tail owns the moments), the local round on params_impl (the
        # tree round always runs the tree AdamW, even with flat stats)
        opt_flat = (params_impl if step_impl == "local_sgd"
                    else stats_impl) == "flat"
        opt = jax.eval_shape(
            (lambda p: init_adamw_flat(p, layout=layout))
            if opt_flat else init_adamw, params_like)
        p_in = (tuple(jax.ShapeDtypeStruct((n,), jnp.float32)
                      for n in layout.buffer_sizes)
                if params_impl == "flat" else params_like)
        if step_impl == "local_sgd":
            # local rounds take (H, B, ...) batches: reuse the (M, B) batch
            # as H=accum_steps local steps — same leading-dims contract
            b_in = batch
        else:
            b_in = batch_m1 if accum_free else batch
        with set_mesh(mesh):
            fn = wrap(b_in)
        flat_groups = []
        if layout is not None:
            # local-SGD replicas are whole per-worker copies (no data-axis
            # shard), the train steps shard buckets over the data axes
            axes = () if step_impl == "local_sgd" else daxes
            required = flat_buffer_specs(layout.num_buffers, axes)
            if opt_flat:
                flat_groups += [("opt.m", tuple(o_specs["m"]), required),
                                ("opt.v", tuple(o_specs["v"]), required)]
            if params_impl == "flat":
                flat_groups += [("params", tuple(p_specs), required)]
        variants.append(StepVariant(
            name="/".join(key), fn=fn,
            args=(p_in, opt, b_in, jax.ShapeDtypeStruct((), jnp.float32)),
            expected=EXPECTED_LAYOUT_COUNTS[key],
            spec_prefix=_spec_leaves((p_specs, o_specs)),
            flat_groups=flat_groups, layout=layout))

    for step_impl in ("fsdp_norm", "accum_norm"):
        for stats_impl in ("tree", "flat"):
            for params_impl in ("tree", "flat"):
                add_train(step_impl, stats_impl, params_impl)
    for step_impl in ("fsdp_norm_m1", "accum_norm_m1"):
        add_train(step_impl, "flat", "flat")
    for stats_impl, params_impl in (("tree", "tree"), ("flat", "tree"),
                                    ("flat", "flat")):
        add_train("local_sgd", stats_impl, params_impl)

    if wanted is None or ("serve_decode", "-", "-") in wanted:
        wrap, p_specs, cache_specs = make_slot_decode_step(
            model, mesh, max_slots=4, params_like=params_like)
        kv_like = jax.eval_shape(lambda: model.init_cache(4, 32))
        with set_mesh(mesh):
            fn = wrap(2, kv_like)
        tok = jax.ShapeDtypeStruct((2,), jnp.int32)
        variants.append(StepVariant(
            name="serve_decode/rung2", fn=fn,
            args=(params_like, kv_like, tok, tok),
            expected=EXPECTED_LAYOUT_COUNTS[("serve_decode", "-", "-")],
            spec_prefix=_spec_leaves((p_specs, cache_specs(kv_like))),
            flat_groups=[]))
    return variants


# --------------------------------------------------------------- checking ----

def check_variant(v: StepVariant) -> list[Finding]:
    """All invariant findings for one traced step variant (trace-only)."""
    from jax import set_mesh
    _, _, mesh = _smoke_parts()
    findings = []

    def bad(rule, msg):
        findings.append(Finding(rule=rule, layer="jaxpr", location=v.name,
                                message=msg))

    with set_mesh(mesh):
        traced = trace(v.fn, *v.args)
        got = count_layout_ops(traced)
        counts = LayoutCounts(packs=len(got["pack"]),
                              unflattens=len(got["unflatten"]),
                              adjoints=len(got["adjoint"]))
        if counts != v.expected:
            bad("pack-count",
                f"layout op counts {counts} != expected {v.expected} "
                f"(pack leaf counts: {got['pack']})")

        host = find_host_eqns(traced)
        if host:
            bad("host-callback",
                f"host-exiting eqns in the step graph: {sorted(set(host))}")

        specs = in_specs(traced)
        if specs is None:
            bad("sharding", "no pjit eqn in the traced step (jit missing?)")
        else:
            prefix = specs[:len(v.spec_prefix)]
            for i, (got_s, want_s) in enumerate(zip(prefix, v.spec_prefix)):
                if got_s != want_s:
                    bad("sharding",
                        f"input {i}: traced sharding {got_s} != declared "
                        f"{want_s}")
        for label, declared, required in v.flat_groups:
            if tuple(declared) != tuple(required):
                bad("sharding",
                    f"{label} bucket specs {declared} != "
                    f"flat_buffer_specs {required}")

        attrs, dead = donation_effective(v.fn, v.args)
        if dead:
            bad("donation",
                f"donated inputs {dead} were NOT aliased by XLA (of "
                f"{len(attrs)} args) — the donation silently does nothing "
                f"and the buffers are double-allocated")
    return findings


def check_ladder_rejection() -> list[Finding]:
    """An off-ladder batch must raise `LadderShapeError` from
    `BucketedEngine.get_step` BEFORE anything traces: zero fresh lowerings,
    zero cache entries (satellite: the silent-quantize fix)."""
    from repro.core.schedule import LadderShapeError, parse_ladder
    from repro.distributed.engine import BucketedEngine
    findings = []
    ladder = parse_ladder("2:1,2:2", workers=1)
    calls = []
    engine = BucketedEngine(lambda bl: calls.append(bl), ladder)
    off = {"tokens": jax.ShapeDtypeStruct((3, 2, 16), jnp.int32),
           "labels": jax.ShapeDtypeStruct((3, 2, 16), jnp.int32)}
    try:
        engine.get_step(off)
    except LadderShapeError:
        pass
    else:
        findings.append(Finding(
            rule="ladder-reject", layer="jaxpr", location="engine.get_step",
            message="off-ladder batch (M=3) was NOT rejected"))
    if calls or engine.stats.compiles:
        findings.append(Finding(
            rule="ladder-reject", layer="jaxpr", location="engine.get_step",
            message=f"off-ladder batch reached the build path "
                    f"({len(calls)} builds, {engine.stats.compiles} "
                    f"compiles) — rejection must cost zero fresh lowerings"))
    return findings


def run_invariant_checks(combos=None, variants=None) -> tuple[list[Finding], dict]:
    """The full trace-only matrix check.  Returns (findings, checked) where
    `checked` records coverage for the report.  Pass prebuilt `variants`
    to share one matrix build with the layer-3 checks (the CLI does)."""
    if variants is None:
        variants = build_variants(combos)
    findings = []
    for v in variants:
        findings.extend(check_variant(v))
    findings.extend(check_ladder_rejection())
    checked = {
        "variants": [v.name for v in variants],
        "invariants": ["pack-count", "donation", "sharding",
                       "host-callback", "ladder-reject"],
    }
    return findings, checked


__all__ = ["EXPECTED_LAYOUT_COUNTS", "LayoutCounts", "StepVariant",
           "build_variants", "check_ladder_rejection", "check_variant",
           "run_invariant_checks"]
