"""Production mesh builders (functions — importing never touches devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The code shards through
    ``with_sharding_constraint`` and ``shard_map`` specs, which the Explicit
    axes that ``jax.make_mesh`` builds by default refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh):
    """The same devices and axis names as ``mesh``, with Auto axes."""
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(AxisType.Auto,) * mesh.devices.ndim)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host offers (tests / smoke runs)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_shards(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
