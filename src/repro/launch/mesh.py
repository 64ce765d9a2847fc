"""Production mesh definitions.

A function, not a module-level constant: importing this module never touches
jax device state. Single pod: 16x16 = 256 chips (data x model). Multi-pod:
2 x 16 x 16 = 512 chips (pod x data x model); the pod axis is outer data
parallelism (gradient reduction crosses the pod interconnect once per step).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with ``Auto`` axes: the partitioner places what the
    sharding rules leave open (bare ``jax.make_mesh`` makes them
    ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def num_data_shards(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
