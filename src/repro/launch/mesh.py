"""Production mesh construction.

IMPORTANT: this module never touches jax device state at import time — the
mesh is built inside a function so the dry-run can set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes (jax defaults to Explicit axes,
    whose sharding-in-types rules the shard_map drivers do not use)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = one pod of 256 chips (data x model);
    (2, 16, 16) = 2 pods / 512 chips (pod x data x model).  The pod axis
    carries only data parallelism + gradient all-reduce, so cross-pod (DCN)
    traffic is one gradient reduction per step."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, axis: str = "model"):
    """Small CPU mesh for tests/examples (uses however many devices exist)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n,), (axis,))


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a != "model")


def data_parallelism(mesh) -> int:
    p = 1
    for a in batch_axes_of(mesh):
        p *= mesh.shape[a]
    return p
