"""Shared helpers for model layers: initializers, dtype casting, params utils."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _normal(key, shape):
    """Standard normal draw, fenced so that a jitted (sharded) model init
    cannot fold the caller's scale into the draw's own constants: jitted
    and eager initialisation then give the same bits."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape))


def normal_init(key, shape, dtype, stddev: float = 0.02):
    return (stddev * _normal(key, shape)).astype(dtype)


def fan_in_init(key, shape, dtype, fan_in: int | None = None):
    fi = fan_in if fan_in is not None else shape[0]
    return (_normal(key, shape) / jnp.sqrt(jnp.maximum(fi, 1))).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))
