"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.  The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import; tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 chips; two pods add a leading DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_cpu_mesh(n_data: int = 1, n_model: int = 1):
    """Small host mesh for tests / CPU validation runs."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_dp_mesh(n: int):
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
