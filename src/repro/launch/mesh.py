"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before the first jax init.
"""
from __future__ import annotations

import jax
from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_axis: int = 1):
    """Degenerate mesh over the locally available devices (CPU tests)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh(
        (n // model_axis, model_axis),
        ("data", "model"),
        axis_types=(AxisType.Auto, AxisType.Auto),
    )


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod','data') on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def data_axis_size(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out
