"""Production mesh builders.

`make_production_mesh` is a FUNCTION (not a module constant) so importing this
module never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, **kwargs):
    """`jax.make_mesh` with all-Auto axis types: the steps place shardings
    through GSPMD propagation and `shard_map`, not explicit-sharding types
    (`jax.make_mesh` defaults to Explicit)."""
    kwargs.setdefault("axis_types", (AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: (16,16) = 256 chips single pod; (2,16,16) = 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (CPU tests)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel (norm-test worker) axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def num_workers(mesh) -> int:
    J = 1
    for a in data_axes(mesh):
        J *= mesh.shape[a]
    return J
