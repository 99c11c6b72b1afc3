"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model code places arrays by
    logical sharding constraints and lets the partitioner propagate them,
    which explicit axes (``jax.make_mesh``'s default) refuse for sharded
    gathers and contractions."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds the 2-pod outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(n_data: int = 1, n_model: int = 1):
    """Small test mesh for CI (requires xla_force_host_platform_device_count
    set by the caller's environment before jax initialization)."""
    return make_mesh((n_data, n_model), ("data", "model"))
