"""Meshes, all with Auto axis types.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked on first use).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """Auto-axis mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` of ``jax.devices()``).  The BBMM custom VJPs take their
    operands unsharded in type, which Explicit axes (``jax.make_mesh``'s
    default) would violate."""
    return jax.make_mesh(
        shape,
        axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 dual pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
