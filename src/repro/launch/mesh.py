"""Mesh construction.

A function (never a module-level constant) so importing this module never
touches JAX device state.  Multi-device tests set
``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` in a subprocess
*before* any jax import to obtain placeholder devices; the main test process
and the benchmarks see 1.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

try:  # AxisType landed after jax 0.4.x; older releases imply Auto axes
    from jax.sharding import AxisType
except ImportError:
    AxisType = None


def _axis_types_kwargs(n_axes: int) -> dict:
    if AxisType is None:
        return {}
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests use (2,2,2) etc. on fake devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_types_kwargs(len(axes)))
