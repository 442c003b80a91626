"""Production mesh construction.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n_axes: int):
    # jax.make_mesh defaults to Explicit axes, which
    # with_sharding_constraint refuses; the sharding rules here are Auto
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))
