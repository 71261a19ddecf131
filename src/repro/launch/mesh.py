"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh with Auto axes: the model places activations with
    ``with_sharding_constraint``, which Explicit axes (the
    ``jax.make_mesh`` default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    pure data-parallel (one cross-pod gradient all-reduce per step — the
    only DCN-crossing collective, by construction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess-based distribution tests."""
    return _auto_mesh(shape, axes)
