"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n_axes: int) -> tuple:
    """Every axis Auto: the compiler propagates shardings through the model
    (the default, Explicit, makes sharding part of each op's type)."""
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod:  2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes, *, devices=None):
    """Arbitrary mesh (tests / local runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)), devices=devices)


def make_local_mesh(model: int = 1):
    """Whatever this host has, folded into (data, model)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
