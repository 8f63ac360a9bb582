"""Production mesh definitions.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto: the model stack places its
    arrays with `NamedSharding`/`with_sharding_constraint` and lets XLA
    propagate the rest (make_mesh's default is Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over however many (host) devices exist — for tests/examples."""
    n = len(jax.devices())
    assert n % model == 0
    return auto_mesh((n // model, model), ("data", "model"))
