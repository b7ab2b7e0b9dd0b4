"""Production mesh builders (function, not module constant — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (GSPMD propagates shardings),
    over ``devices`` (default: all of them)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device subprocess tests (8 host devices)."""
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    """Batch-sharding axes: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def tp_size_of(mesh) -> int:
    return mesh.shape["model"]


def n_dp_of(mesh) -> int:
    n = 1
    for a in dp_axes_of(mesh):
        n *= mesh.shape[a]
    return n
