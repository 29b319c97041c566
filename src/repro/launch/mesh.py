"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches JAX device state (the dry-run must set XLA_FLAGS before any init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke paths (1x1)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod axis folds into DP)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
