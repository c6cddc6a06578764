"""Jaxpr/MLIR-level primitives for the static invariant checker (DESIGN §13).

Everything here operates on TRACED artifacts only — jaxprs from
`jax.make_jaxpr` and StableHLO text from `.lower().as_text()` — never on
executed code.  The flat-buffer entry points bind a zero-cost marker
primitive (`flatbuf.layout_marker_p`) on their buffers, so pack/unflatten/
adjoint events are real equations these walkers can count *through* jit,
scan, shard_map, and custom_vjp boundaries — unlike the removed
Python-call proxy (`count_packs`), which only saw host-level calls.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

import jax

LAYOUT_MARKER = "repro_layout_marker"

# Primitives that move data to the host (or run Python) mid-step: any of
# these inside a hot-path step graph is a per-step sync the schedules'
# measured step cost would silently absorb.
_HOST_PRIM_RE = re.compile(r"callback|debug_print|infeed|outfeed")


def iter_eqns(jaxpr):
    """Every equation in `jaxpr` and, recursively, in every sub-jaxpr
    carried by an equation's params (pjit/scan `jaxpr`, custom_vjp
    `call_jaxpr`, cond `branches`, shard_map bodies, ...)."""
    if hasattr(jaxpr, "jaxpr"):          # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_eqns(sub)


def trace(fn, *args, **kwargs):
    """Closed jaxpr of `fn` at the given abstract signature (no execution,
    no compilation; jitted callables keep their pjit eqn so shardings and
    donation flags remain inspectable)."""
    return jax.make_jaxpr(fn)(*args, **kwargs)


def count_layout_ops(target, *args, **kwargs) -> dict:
    """Count the flat-layout marker eqns in a traced graph.

    `target` is a jaxpr/ClosedJaxpr, or a callable traced at `*args`.
    Returns {"pack": [...], "unflatten": [...], "adjoint": [...]} — one
    entry per marker eqn, in jaxpr order, valued with the event's leaf
    count.  `len(result["pack"])` is the per-step flatten count the PR 3
    double-pack regression guard asserts on."""
    jaxpr = target if hasattr(target, "eqns") or hasattr(target, "jaxpr") \
        else trace(target, *args, **kwargs)
    out: dict = {"pack": [], "unflatten": [], "adjoint": []}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == LAYOUT_MARKER:
            out[eqn.params["kind"]].append(eqn.params["nleaves"])
    return out


def find_host_eqns(jaxpr) -> list[str]:
    """Names of equations that leave the device mid-graph: host callbacks,
    debug prints, infeed/outfeed, and Pallas calls forced into interpret
    mode at trace time (an interpreted kernel runs on host even on TPU)."""
    bad = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if _HOST_PRIM_RE.search(name):
            bad.append(name)
        elif name == "pallas_call" and eqn.params.get("interpret"):
            bad.append("pallas_call[interpret=True]")
    return bad


def top_pjit_params(jaxpr) -> dict | None:
    """Params of the outermost pjit eqn of a traced jitted callable (None
    when the trace has no pjit — e.g. a jit=False step).  Carries
    `in_shardings` (NamedSharding per flat input when explicit) and
    `donated_invars` (bool per flat input)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            return eqn.params
    return None


def in_specs(jaxpr) -> list | None:
    """PartitionSpec (or None when unspecified) per flat input of the
    outermost pjit eqn."""
    params = top_pjit_params(jaxpr)
    if params is None:
        return None
    return [getattr(s, "spec", None) for s in params["in_shardings"]]


# ----------------------------------------------------- lowered-MLIR side ----

@dataclass(frozen=True)
class ArgAttrs:
    """Attributes of one `@main` argument in lowered StableHLO text."""
    index: int
    aliased: bool          # XLA accepted the donation (tf.aliasing_output)
    sharding: str | None   # mhlo.sharding string, if any


def main_arg_attrs(lowered_text: str) -> list[ArgAttrs]:
    """Parse the `@main` signature of `.lower().as_text()` output.

    Donation that actually took effect annotates the argument with
    `tf.aliasing_output = N`; a donated input the compiler could NOT alias
    (shape/dtype matches no output — the donation silently does nothing)
    carries no attribute, which is exactly the regression this parser
    exists to catch."""
    start = lowered_text.index("@main(")
    # paren-balanced scan: attr strings never contain parens, but stop at
    # the signature's closing paren, not the first one
    depth, end = 0, None
    for i in range(start + len("@main"), len(lowered_text)):
        c = lowered_text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    sig = lowered_text[start + len("@main("):end]
    out = []
    # each chunk spans one argument: its attrs (incl. quoted shardings with
    # braces inside) end before the next `%arg`
    for chunk in sig.split("%arg")[1:]:
        idx = int(chunk[:chunk.index(":")])
        m = re.search(r'mhlo\.sharding = "([^"]*)"', chunk)
        out.append(ArgAttrs(index=idx,
                            aliased="tf.aliasing_output" in chunk,
                            sharding=m.group(1) if m else None))
    return out


def donation_effective(jitted, args) -> tuple[list[ArgAttrs], list[int]]:
    """Lower (never execute) a jitted callable and report which flat inputs
    XLA actually aliased.  Returns (per-arg attrs, indices of donated-but-
    unaliased args) — the second list should be empty for every step whose
    donated buffers are meant to be updated in place."""
    traced = trace(jitted, *args)
    params = top_pjit_params(traced)
    donated = params["donated_invars"] if params else ()
    attrs = main_arg_attrs(jitted.lower(*args).as_text())
    if len(attrs) != len(donated):
        raise RuntimeError(
            f"lowered @main has {len(attrs)} args but the jaxpr has "
            f"{len(donated)} inputs — argument pruning would misalign the "
            f"donation check")
    dead = [i for i, (a, d) in enumerate(zip(attrs, donated))
            if d and not a.aliased]
    return attrs, dead


__all__ = ["ArgAttrs", "LAYOUT_MARKER", "count_layout_ops",
           "donation_effective", "find_host_eqns", "in_specs", "iter_eqns",
           "main_arg_attrs", "top_pjit_params", "trace"]
