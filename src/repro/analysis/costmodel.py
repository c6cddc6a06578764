"""Layer 3a: the trace-only cost model (DESIGN §15).

For every step variant in the matrix (`invariants.build_variants`) this
module derives, from the traced jaxpr and the lowered (never compiled)
StableHLO:

* **collective volume** — op counts and payload bytes per collective kind
  (psum/all-gather/reduce-scatter/all-to-all/ppermute), with static scan
  trip counts multiplied in, and each site attributed to the flat bucket
  groups when its operands are bucket buffers (by `layout_marker_p`
  adjacency in its scope, or by bucket-shape match against the variant's
  `FlatLayout`).  Only *manually placed* collectives (shard_map regions)
  exist before compilation; GSPMD-inserted ones (ACCUM-NORM's
  `with_sharding_constraint` resharding) appear during SPMD partitioning
  and are invisible to a trace-only analysis — their budget entry is the
  honest zero, and the sharding-agreement check in layer 1 is what pins
  that path's layout.
* **analytic FLOPs** — 2·batch·M·N·K per `dot_general`, one flop per
  output element for elementwise compute, scan bodies × trip count, cond
  branches at their max.
* **a peak-memory watermark** — a liveness sweep over the step's pjit
  body where an input that XLA actually aliased to an output
  (`tf.aliasing_output` in the lowered text) makes that output free: a
  *dropped* donation therefore raises the watermark by exactly the
  double-allocated state it regresses, which is the class this metric
  gates.

All three are frozen in a committed machine-readable baseline
(`analysis_budget.json`).  `run_cost_checks` diffs a fresh measurement
against it — op counts exactly, byte/FLOP/peak metrics within the
per-metric tolerances the budget file itself declares — and emits
findings on any drift in EITHER direction (an improvement is a budget
update, not a free pass), plus staleness findings when the budget and the
traced matrix disagree about which variants exist.  Intentional changes
go through ``python -m repro.analysis --update-budget``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

BUDGET_SCHEMA = 1
BUDGET_FILENAME = "analysis_budget.json"

# drift allowed per derived metric before the gate fires; op counts are
# always exact.  These are the DEFAULTS stamped into a fresh budget — the
# committed file's own `tolerances` block is what the diff actually uses,
# so loosening for a JAX upgrade is a reviewed one-line change.
DEFAULT_TOLERANCES = {
    "collective_bytes": 0.0,   # payload bytes are pure static-shape math
    "flops": 0.01,
    "peak_bytes": 0.10,        # liveness order can shift across JAX minors
}


def _aval_bytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:       # tokens etc.
        return 0
    return int(size) * dtype.itemsize


def _is_var(v) -> bool:
    # jaxpr Vars participate in dataflow; Literals don't (and may not hash)
    return getattr(v, "count", None) is not None


def _unwrap(jaxpr):
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def collective_kind(prim_name: str) -> str | None:
    """Canonical collective kind of a primitive name, or None.  `psum2` and
    friends fold onto their base kind; pmax/pmin are all-reduces."""
    for prefix, kind in (("all_gather", "all_gather"),
                         ("reduce_scatter", "reduce_scatter"),
                         ("psum_scatter", "reduce_scatter"),
                         ("psum", "psum"),
                         ("pmax", "all_reduce"), ("pmin", "all_reduce"),
                         ("all_to_all", "all_to_all"),
                         ("ppermute", "ppermute")):
        if prim_name.startswith(prefix):
            return kind
    return None


def _eqn_subs(eqn):
    """(sub_jaxprs, trip_mult, is_cond) for one equation.  `scan` returns
    its body with the static trip count; `cond`/`switch` return every
    branch flagged so callers pick their policy (count one, diff all)."""
    p = eqn.params
    name = eqn.primitive.name
    if "branches" in p:
        return list(p["branches"]), 1, True
    if name == "scan":
        return [p["jaxpr"]], int(p.get("length", 1)), False
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = p.get(key)
        if sub is not None and (hasattr(sub, "eqns") or hasattr(sub, "jaxpr")):
            return [sub], 1, False
    subs = [s for v in p.values()
            for s in (v if isinstance(v, (list, tuple)) else (v,))
            if hasattr(s, "eqns") or hasattr(s, "jaxpr")]
    return subs, 1, False


def _axes_of(eqn) -> tuple:
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


# ------------------------------------------------- collective profiling ----

@dataclass(frozen=True)
class CollectiveSite:
    """One collective equation in a step graph (scan-multiplied)."""
    kind: str           # canonical kind (see `collective_kind`)
    primitive: str      # raw primitive name
    count: int          # executions per step (static trip counts folded in)
    bytes: int          # payload bytes per step (output avals × count)
    axes: tuple         # mesh axis names it reduces/gathers over
    flatbuf: bool       # attributed to a flat bucket group


# data-movement ops taint flows through when relating markers to the
# collectives that move the marked buffers
_TRANSPARENT = frozenset({
    "reshape", "convert_element_type", "slice", "dynamic_slice",
    "dynamic_update_slice", "transpose", "broadcast_in_dim", "squeeze",
    "expand_dims", "concatenate", "pad", "copy", "rev",
    "repro_layout_marker",
})


def _marker_adjacency(jx):
    """Per-scope var sets: `fwd` = reachable from a marker's outputs,
    `bwd` = reaching a marker's inputs, both through transparent
    data-movement ops only (eqns are in topological order)."""
    from repro.analysis.jaxpr_check import LAYOUT_MARKER
    fwd, bwd = set(), set()
    for eqn in jx.eqns:
        if eqn.primitive.name == LAYOUT_MARKER:
            fwd.update(eqn.outvars)
            bwd.update(v for v in eqn.invars if _is_var(v))
    for eqn in jx.eqns:
        if eqn.primitive.name in _TRANSPARENT and \
                any(v in fwd for v in eqn.invars if _is_var(v)):
            fwd.update(eqn.outvars)
    for eqn in reversed(jx.eqns):
        if eqn.primitive.name in _TRANSPARENT and \
                any(v in bwd for v in eqn.outvars):
            bwd.update(v for v in eqn.invars if _is_var(v))
    return fwd, bwd


def collective_sites(jaxpr, layout=None, _mult: int = 1) -> list[CollectiveSite]:
    """Every collective eqn in the (recursively entered) graph, with scan
    trip counts multiplied in and cond branches counted once (branch
    agreement is `divergence.py`'s check).  A site is flat-bucket
    attributed when it is marker-adjacent in its scope, or when its
    operands are 1-D buffers whose sizes match `layout`'s buckets (whole
    or per-shard) — bucket buffers enter a step as plain jit inputs, so
    shape-matching catches the gathers that run before any marker eqn."""
    jx = _unwrap(jaxpr)
    fwd, bwd = _marker_adjacency(jx)
    bucket_sizes = set()
    if layout is not None:
        for n in layout.buffer_sizes:
            bucket_sizes.add(int(n))
            div = getattr(layout, "shard_divisor", 1) or 1
            if div > 1 and n % div == 0:
                bucket_sizes.add(int(n) // div)
    sites: list[CollectiveSite] = []
    for eqn in jx.eqns:
        kind = collective_kind(eqn.primitive.name)
        if kind is not None:
            payload = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            adjacent = (any(v in fwd for v in eqn.invars if _is_var(v))
                        or any(v in bwd for v in eqn.outvars))
            shaped = bucket_sizes and any(
                len(getattr(v.aval, "shape", ())) == 1
                and v.aval.shape[0] in bucket_sizes for v in eqn.outvars)
            sites.append(CollectiveSite(
                kind=kind, primitive=eqn.primitive.name, count=_mult,
                bytes=payload * _mult, axes=_axes_of(eqn),
                flatbuf=bool(adjacent or shaped)))
        subs, mult, is_cond = _eqn_subs(eqn)
        if is_cond:
            if subs:
                sites.extend(collective_sites(subs[0], layout, _mult))
        else:
            for sub in subs:
                sites.extend(collective_sites(sub, layout, _mult * mult))
    return sites


def collective_profile(jaxpr, layout=None) -> dict:
    """Aggregate `collective_sites` into the budget's per-kind shape:
    {kind: {"count": n, "bytes": b}} plus the flat-bucket-attributed
    totals."""
    per_kind: dict = {}
    fb_count = fb_bytes = 0
    for s in collective_sites(jaxpr, layout):
        e = per_kind.setdefault(s.kind, {"count": 0, "bytes": 0})
        e["count"] += s.count
        e["bytes"] += s.bytes
        if s.flatbuf:
            fb_count += s.count
            fb_bytes += s.bytes
    return {"per_kind": dict(sorted(per_kind.items())),
            "flatbuf": {"count": fb_count, "bytes": fb_bytes}}


# --------------------------------------------------------- analytic FLOPs ----

# pure data movement: zero flops regardless of output size
_ZERO_FLOP = _TRANSPARENT | frozenset({
    "iota", "stop_gradient", "device_put", "gather", "scatter",
    "bitcast_convert_type", "select_n", "split",
})


def _dot_flops(eqn) -> int:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = 1
    for i in lb:
        batch *= lhs[i]
    k = 1
    for i in lc:
        k *= lhs[i]
    m = n = 1
    for i, d in enumerate(lhs):
        if i not in set(lc) | set(lb):
            m *= d
    for i, d in enumerate(rhs):
        if i not in set(rc) | set(rb):
            n *= d
    return 2 * batch * m * n * k


def flops_estimate(jaxpr) -> int:
    """Analytic FLOPs of one step: exact matmul math for `dot_general`,
    one flop per output element elsewhere, scan × static trip count, cond
    at the max over branches.  Deterministic by construction — this is a
    budget metric, not a profiler."""
    jx = _unwrap(jaxpr)
    total = 0
    for eqn in jx.eqns:
        subs, mult, is_cond = _eqn_subs(eqn)
        if subs:
            inner = [flops_estimate(s) for s in subs]
            total += max(inner) if is_cond else mult * sum(inner)
            continue
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name not in _ZERO_FLOP:
            total += sum(int(getattr(v.aval, "size", 0))
                         for v in eqn.outvars)
    return total


# ------------------------------------------------- peak-memory watermark ----

def _scope_peak(jx, zero_cost=frozenset()) -> int:
    """Liveness sweep over one scope: a var is resident from its defining
    eqn to its last use (scope outputs to the end); container eqns add
    their body's own peak on top of the parent's residency at that point.
    Vars in `zero_cost` (outputs covered by an accepted donation) are
    never charged — so a donation XLA dropped shows up as exactly the
    doubled state."""
    jx = _unwrap(jx)
    eqns = list(jx.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    for v in jx.outvars:
        if _is_var(v):
            last_use[v] = len(eqns)
    frees: dict = {}
    for v, i in last_use.items():
        frees.setdefault(i, []).append(v)
    cur = sum(_aval_bytes(v.aval)
              for v in list(jx.invars) + list(jx.constvars))
    peak = cur
    for i, eqn in enumerate(eqns):
        subs, _, _ = _eqn_subs(eqn)
        inner = max((_scope_peak(s) for s in subs), default=0)
        out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars
                    if v in last_use and v not in zero_cost)
        cur += out_b
        peak = max(peak, cur + inner)
        for v in frees.get(i, ()):
            if v not in zero_cost:
                cur -= _aval_bytes(v.aval)
    return peak


def peak_memory(traced, arg_attrs=None) -> int:
    """Peak-residency watermark of a traced jitted step (bytes).  Operates
    on the outermost pjit body; `arg_attrs` (from
    `jaxpr_check.main_arg_attrs` of the lowering) names the inputs XLA
    actually aliased — each is greedily matched to a same-shaped scope
    output, which then costs nothing (in-place update).  Without attrs the
    watermark is the no-donation upper bound."""
    from repro.analysis.jaxpr_check import top_pjit_params
    params = top_pjit_params(traced)
    if params is None:
        return _scope_peak(traced)
    inner = _unwrap(params["jaxpr"])
    zero_cost: set = set()
    if arg_attrs:
        outs = [v for v in inner.outvars if _is_var(v)]
        taken: set = set()
        for a in arg_attrs:
            if not a.aliased or a.index >= len(inner.invars):
                continue
            want = inner.invars[a.index].aval
            for v in outs:
                if v in taken or v in zero_cost:
                    continue
                if (getattr(v.aval, "shape", None) == want.shape
                        and getattr(v.aval, "dtype", None) == want.dtype):
                    zero_cost.add(v)
                    taken.add(v)
                    break
    return _scope_peak(inner, zero_cost=frozenset(zero_cost))


# -------------------------------------------------------- variant metrics ----

def variant_cost(v, mesh=None) -> dict:
    """All layer-3 metrics for one `StepVariant` (trace + lower, never
    compile), traced under `mesh` (default: the smoke mesh)."""
    import jax
    from repro.analysis.jaxpr_check import main_arg_attrs, trace
    if mesh is None:
        from repro.analysis.invariants import _smoke_parts
        _, _, mesh = _smoke_parts()
    with jax.set_mesh(mesh):
        traced = trace(v.fn, *v.args)
        lowered_text = v.fn.lower(*v.args).as_text()
    attrs = main_arg_attrs(lowered_text)
    layout = getattr(v, "layout", None)
    prof = collective_profile(traced, layout)
    return {
        "collectives": prof["per_kind"],
        "flatbuf": prof["flatbuf"],
        "flops": flops_estimate(traced),
        "peak_bytes": peak_memory(traced, attrs),
        "donated_aliased": sum(1 for a in attrs if a.aliased),
    }


def measure_variants(variants=None) -> dict:
    """{variant name: metrics} for the whole matrix (or a prebuilt
    subset), each traced under its own mesh (the smoke mesh unless the
    variant names one)."""
    from repro.analysis.invariants import _smoke_parts, build_variants
    if variants is None:
        variants = build_variants()
    _, _, smoke_mesh = _smoke_parts()
    return {v.name: variant_cost(v, v.mesh or smoke_mesh) for v in variants}


# ----------------------------------------------------------------- budget ----

def load_budget(path) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_budget(path, measured: dict) -> dict:
    """Freeze `measured` as the committed baseline (atomic replace).  The
    topology is recorded because collective structure is mesh-dependent:
    a budget measured at a different device count is stale, not wrong.
    The JAX version is recorded too: its lowering moves FLOPs and peak
    bytes slightly between releases."""
    import jax
    budget = {
        "schema": BUDGET_SCHEMA,
        "topology": {"device_count": jax.device_count(),
                     "backend": jax.default_backend(),
                     "jax": jax.__version__},
        "tolerances": dict(DEFAULT_TOLERANCES),
        "variants": {k: measured[k] for k in sorted(measured)},
    }
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(budget, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return budget


def _rel_drift(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1.0)


def budget_diff(measured: dict, budget: dict) -> list:
    """Findings for every way `measured` disagrees with `budget`:
    staleness (variant sets / topology out of sync), exact op-count
    drift, and relative metric drift beyond the budget's own
    tolerances.  Symmetric — regressions AND improvements both require
    an explicit `--update-budget`."""
    import jax
    from repro.analysis.findings import Finding

    def f(rule, loc, msg):
        return Finding(rule=rule, layer="cost", location=loc, message=msg)

    findings = []
    tol = {**DEFAULT_TOLERANCES, **(budget.get("tolerances") or {})}
    topo = budget.get("topology") or {}
    if topo.get("device_count") not in (None, jax.device_count()):
        findings.append(f(
            "budget-stale", BUDGET_FILENAME,
            f"budget was frozen at device_count="
            f"{topo.get('device_count')} but this run has "
            f"{jax.device_count()} — collective structure is "
            f"mesh-dependent; regenerate with --update-budget on the CI "
            f"topology"))
        return findings
    b_vars = budget.get("variants") or {}
    for name in sorted(set(measured) - set(b_vars)):
        findings.append(f(
            "budget-stale", name,
            "variant is in the traced matrix but missing from "
            f"{BUDGET_FILENAME}; run --update-budget"))
    for name in sorted(set(b_vars) - set(measured)):
        findings.append(f(
            "budget-stale", name,
            f"budget entry matches no variant in the traced matrix "
            f"(removed or renamed?); run --update-budget"))
    for name in sorted(set(measured) & set(b_vars)):
        got, want = measured[name], b_vars[name]
        gk, wk = got["collectives"], want.get("collectives", {})
        for kind in sorted(set(gk) | set(wk)):
            g = gk.get(kind, {"count": 0, "bytes": 0})
            w = wk.get(kind, {"count": 0, "bytes": 0})
            if g["count"] != w["count"]:
                findings.append(f(
                    "cost-collectives", name,
                    f"{kind} op count {g['count']} != budget "
                    f"{w['count']} — a collective was added or removed"))
            elif _rel_drift(g["bytes"], w["bytes"]) > tol["collective_bytes"]:
                findings.append(f(
                    "cost-collectives", name,
                    f"{kind} payload {g['bytes']}B drifted from budget "
                    f"{w['bytes']}B (tol {tol['collective_bytes']:.0%})"))
        gf, wf = got["flatbuf"], want.get("flatbuf", {"count": 0, "bytes": 0})
        if gf["count"] != wf["count"]:
            findings.append(f(
                "cost-collectives", name,
                f"flat-bucket-attributed collective count {gf['count']} "
                f"!= budget {wf['count']}"))
        if _rel_drift(got["flops"], want.get("flops", 0)) > tol["flops"]:
            findings.append(f(
                "cost-flops", name,
                f"analytic FLOPs {got['flops']:.4g} drifted from budget "
                f"{want.get('flops', 0):.4g} (tol {tol['flops']:.0%})"))
        if _rel_drift(got["peak_bytes"],
                      want.get("peak_bytes", 0)) > tol["peak_bytes"]:
            findings.append(f(
                "cost-peak-memory", name,
                f"peak-memory watermark {got['peak_bytes']}B drifted from "
                f"budget {want.get('peak_bytes', 0)}B (tol "
                f"{tol['peak_bytes']:.0%}) — check donation aliasing and "
                f"buffer lifetimes"))
        if got["donated_aliased"] < want.get("donated_aliased",
                                             got["donated_aliased"]):
            findings.append(f(
                "cost-peak-memory", name,
                f"{got['donated_aliased']} inputs aliased vs budget "
                f"{want['donated_aliased']} — a donation was dropped"))
    return findings


def run_cost_checks(budget_path, variants=None,
                    update: bool = False) -> tuple[list, dict]:
    """The layer-3a entry point: measure the matrix, then diff against
    (or, with `update`, rewrite) the committed budget.  Returns
    (findings, checked) where `checked["cost"]` carries the full
    per-variant metrics so the CI report always publishes comm bytes,
    FLOPs, and peak memory for every combo."""
    from repro.analysis.findings import Finding
    measured = measure_variants(variants)
    checked = {"budget": str(budget_path), "metrics": measured}
    if update:
        write_budget(budget_path, measured)
        checked["budget_updated"] = True
        return [], checked
    budget = load_budget(budget_path)
    if budget is None:
        return [Finding(
            rule="budget-stale", layer="cost", location=str(budget_path),
            message="no committed cost budget; run "
                    "`python -m repro.analysis --update-budget` and commit "
                    f"{BUDGET_FILENAME}")], checked
    return budget_diff(measured, budget), checked


__all__ = ["BUDGET_FILENAME", "CollectiveSite", "DEFAULT_TOLERANCES",
           "budget_diff", "collective_kind", "collective_profile",
           "collective_sites", "flops_estimate", "load_budget",
           "measure_variants", "peak_memory", "run_cost_checks",
           "variant_cost", "write_budget"]
