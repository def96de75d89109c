"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Mirrors GSplit's hierarchy (§7.4): data parallelism
across pods/hosts, model ("split") parallelism within.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: shardings come from jit in/out_shardings and in-model
    # PartitionSpec hints, not from Explicit-axis array types
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that shard the batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh) -> str:
    assert "model" in mesh.axis_names
    return "model"
