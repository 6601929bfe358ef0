"""Production mesh factory (DESIGN.md §6).

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets the 512-device XLA flag before
calling it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    # the sharding rules place arrays with NamedSharding/with_sharding_constraint
    # and let the compiler propagate the rest: Auto axes, not the Explicit
    # default of jax.make_mesh
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model"), devices=None):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return auto_mesh(shape, axes, devices)
