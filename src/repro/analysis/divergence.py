"""Layer 3b: the SPMD-divergence lint (DESIGN §15).

SPMD programs deadlock (or silently desync) when ranks disagree about
which collective comes next.  Rank-dependent *values* are what collectives
are for; rank-dependent collective ORDER is always a bug.  Before the
elastic-membership work makes step graphs a function of fleet state, this
module pins the two statically checkable halves of that contract:

* **emission-order determinism** — trace every step variant TWICE,
  independently, and require identical ordered collective signatures
  (kind, mesh axes, payload shape, scope path).  A builder that iterates
  an unordered container, or branches on host state (process index, pid,
  wall clock), emits different graphs on different ranks — and also on
  two traces within one process, which is what makes the hazard visible
  to a single-host CI run.
* **cond-branch agreement** — both branches of every traced `cond` /
  `switch` must contain the same collective sequence: a collective under
  a data-dependent branch runs on the ranks whose predicate was true and
  deadlocks the rest.

The third half is lexical and lives in `lint.py` (`host-divergence`):
host-identity reads (`jax.process_index`, `os.getpid`, hostname) inside
traced-scope source files.
"""

from __future__ import annotations

from repro.analysis.costmodel import _axes_of, _eqn_subs, _unwrap, collective_kind
from repro.analysis.findings import Finding


def collective_signature(jaxpr, _path: str = "") -> tuple:
    """Ordered tuple of collective events in the traced graph —
    `(scope_path, primitive, axes, output shapes)` per site, in emission
    order, cond branches included under distinct paths (branch agreement
    is checked separately; for ordering purposes every branch is part of
    the signature)."""
    jx = _unwrap(jaxpr)
    sig = []
    for i, eqn in enumerate(jx.eqns):
        name = eqn.primitive.name
        if collective_kind(name) is not None:
            shapes = tuple(tuple(getattr(v.aval, "shape", ()))
                           for v in eqn.outvars)
            sig.append((_path, name, _axes_of(eqn), shapes))
        subs, _, is_cond = _eqn_subs(eqn)
        for bi, sub in enumerate(subs):
            tag = f"{_path}/{name}.{i}" + (f".b{bi}" if is_cond else "")
            sig.extend(collective_signature(sub, tag))
    return tuple(sig)


def branch_collective_mismatches(jaxpr) -> list[tuple[str, list]]:
    """Every `cond`/`switch` eqn whose branches disagree on their
    collective sequence: [(eqn label, per-branch signatures)]."""
    out = []

    def walk(jx, path):
        jx = _unwrap(jx)
        for i, eqn in enumerate(jx.eqns):
            subs, _, is_cond = _eqn_subs(eqn)
            if is_cond and len(subs) > 1:
                sigs = [tuple((n, a, s) for _, n, a, s in
                              collective_signature(b)) for b in subs]
                if len(set(sigs)) > 1:
                    out.append((f"{path}/{eqn.primitive.name}.{i}", sigs))
            for sub in subs:
                walk(sub, f"{path}/{eqn.primitive.name}.{i}")

    walk(jaxpr, "")
    return out


def check_fn_divergence(fn, args, location: str, mesh=None) -> list[Finding]:
    """Both divergence checks on one traceable step: trace twice, compare
    ordered collective signatures, then check cond-branch agreement on the
    first trace.  The second trace must be genuinely fresh: a jitted step
    caches its traced body on the pjit AND in jax's global trace caches
    (shard_map/custom_vjp bodies are keyed on the Python function object),
    either of which would hide a builder whose emission order flips
    between calls — so ALL of jax's caches are dropped between the two
    (later jit calls in this process simply retrace/recompile; this
    checker runs in the one-shot analysis CLI where that costs nothing)."""
    from repro.analysis.jaxpr_check import trace
    from jax import set_mesh
    import contextlib
    import jax
    ctx = set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        t1 = trace(fn, *args)
        jax.clear_caches()
        t2 = trace(fn, *args)
    findings = []
    s1, s2 = collective_signature(t1), collective_signature(t2)
    if s1 != s2:
        diverge_at = next((i for i, (a, b) in enumerate(zip(s1, s2))
                           if a != b), min(len(s1), len(s2)))
        findings.append(Finding(
            rule="divergence-order", layer="cost", location=location,
            message=f"two traces of the same step emit different collective "
                    f"sequences (lengths {len(s1)} vs {len(s2)}, first "
                    f"divergence at site {diverge_at}) — the builder's "
                    f"emission order is host-state-dependent, so ranks "
                    f"would build different programs and deadlock"))
    for label, sigs in branch_collective_mismatches(t1):
        findings.append(Finding(
            rule="divergence-cond", layer="cost", location=location,
            message=f"cond branches at {label} contain different collective "
                    f"sequences {[len(s) for s in sigs]} — ranks whose "
                    f"predicate differs would disagree on the next "
                    f"collective and deadlock"))
    return findings


def run_divergence_checks(variants=None) -> tuple[list[Finding], dict]:
    """Layer-3b over the whole step matrix (or a prebuilt subset)."""
    from repro.analysis.invariants import _smoke_parts, build_variants
    if variants is None:
        variants = build_variants()
    _, _, mesh = _smoke_parts()
    findings = []
    for v in variants:
        findings.extend(check_fn_divergence(v.fn, v.args, v.name, mesh))
    checked = {"variants": [v.name for v in variants],
               "checks": ["divergence-order", "divergence-cond"]}
    return findings, checked


__all__ = ["branch_collective_mismatches", "check_fn_divergence",
           "collective_signature", "run_divergence_checks"]
