"""Production mesh factory (assignment-fixed shapes).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax


def make_mesh_auto(shape, axes):
    """jax.make_mesh with every axis typed Auto (GSPMD propagation)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)
