"""Mesh construction.  Functions, not module constants — importing this
module never touches jax device state.

Production topology (TPU v5e):
  single pod : (16, 16)    = ('data', 'model')   — 256 chips
  multi-pod  : (2, 16, 16) = ('pod', 'data', 'model') — 512 chips
The 'pod' axis carries only data parallelism (hierarchical gradient sync;
see core/collectives.py), 'model' carries TP/SP/EP.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (device count permitting)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_size(mesh) -> int:
    out = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            out *= int(mesh.shape[a])
    return out
