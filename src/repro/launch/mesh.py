"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through the program and ``with_sharding_constraint`` steers them (the
    sharding-in-types ``Explicit`` default would make each op's output
    sharding part of its type instead)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 dual pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pp: int = 0):
    """Small mesh over whatever devices exist (tests on forced host devices)."""
    n = len(jax.devices())
    assert data * model * max(pp, 1) <= n, (data, model, pp, n)
    if pp:
        return make_mesh((pp, data, model), ("pp", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
