"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with `Auto` axes. JAX >= 0.9 defaults to `Explicit`
    axes, under which the shard_map executors and the sharding-rule
    constraints (`launch.sharding`) refuse implicit resharding; every mesh
    in this repo is built here so they all share the `Auto` semantics."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 2, model: int = 4):
    """Small mesh over host devices for distribution integration tests."""
    return make_mesh((data, model), ("data", "model"))
