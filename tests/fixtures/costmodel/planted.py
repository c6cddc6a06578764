"""Planted cost/divergence regressions for the layer-3 analyzer tests.

Each pair here is (clean step, regressed twin) for one class the cost
model gates:

* `clean_step` / `extra_gather_step` — the twin performs one additional
  `all_gather` whose result feeds the output: a collective-volume
  regression (new kind, new bytes) the budget diff must flag exactly.
* `donating_update` / `dropped_donation_update` — the same params update
  with and without `donate_argnums`: the dropped donation doubles the
  resident params state, which the peak-memory watermark must price in.
* `make_flipping_step` — a builder whose collective EMISSION ORDER
  depends on mutable host state (a per-call counter standing in for
  `process_index()`): two traces of the same fn produce different
  ordered signatures, the divergence-order deadlock class.
* `cond_collective_step` — a `lax.cond` with a psum in only one branch:
  ranks whose predicate differs disagree on the next collective
  (divergence-cond).

All functions are trace-only fixtures — nothing here is ever compiled or
executed; meshes are host meshes over however many devices the test
process has (collectives emit at trace time even on size-1 axes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.launch.mesh import make_mesh


def fixture_mesh():
    """One manual data axis over every local device."""
    return make_mesh((jax.device_count(),), ("d",))


def _sharded(body, mesh):
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P("d"),
                             out_specs=P("d"), axis_names={"d"},
                             check_vma=False))


def clean_step(mesh):
    """Baseline: exactly one psum over the data axis."""
    def body(x):
        local = jnp.sum(x * x)
        total = lax.psum(local, "d")
        return x * total
    return _sharded(body, mesh)


def extra_gather_step(mesh):
    """The planted regression: same math plus one all_gather whose result
    feeds the output (so DCE cannot delete it)."""
    def body(x):
        local = jnp.sum(x * x)
        total = lax.psum(local, "d")
        gathered = lax.all_gather(x, "d")
        return x * total + jnp.sum(gathered)
    return _sharded(body, mesh)


def _update(params, grad):
    new_params = params - 0.1 * grad
    return new_params, jnp.sum(grad * grad)


def donating_update(n: int = 1 << 18):
    """(jitted fn, example args): params buffer donated, so XLA aliases it
    to the output and the update runs in place."""
    x = jnp.zeros((n,), jnp.float32)
    return jax.jit(_update, donate_argnums=(0,)), (x, x)


def dropped_donation_update(n: int = 1 << 18):
    """The planted regression: the identical update WITHOUT the donation —
    old and new params are simultaneously resident."""
    x = jnp.zeros((n,), jnp.float32)
    return jax.jit(_update), (x, x)


def make_flipping_step(mesh):
    """A builder with host-state-dependent emission order: odd calls emit
    psum-then-all_gather, even calls the reverse.  The mutable counter is
    the single-process stand-in for branching on `jax.process_index()` —
    two ranks (or two traces) build different programs."""
    calls = {"n": 0}

    def body(x):
        calls["n"] += 1
        if calls["n"] % 2:
            total = lax.psum(jnp.sum(x), "d")
            gathered = lax.all_gather(x, "d")
        else:
            gathered = lax.all_gather(x, "d")
            total = lax.psum(jnp.sum(x), "d")
        return x * total + jnp.sum(gathered)

    return _sharded(body, mesh)


def cond_collective_step(mesh):
    """A data-dependent branch where only the true arm psums: ranks whose
    predicate disagrees deadlock at the collective."""
    def body(x):
        def with_psum(v):
            return v * lax.psum(jnp.sum(v), "d")

        def without(v):
            return v * 2.0

        return lax.cond(jnp.sum(x) > 0, with_psum, without, x)
    return _sharded(body, mesh)
