"""Production meshes. Functions, not module-level constants — importing
this module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    return _mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for tests on the default host device count."""
    n = len(jax.devices())
    data = min(data, max(n // model, 1))
    if data * model > n:
        model = n // data
    return make_mesh((data, model), ("data", "model"))
