"""Production meshes.

Single pod : (data=16, model=16)           = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)    = 512 chips

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axis_names):
    """jax.make_mesh with Auto (GSPMD-propagated) sharding on every axis."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    data = n // model_parallel
    return _auto_mesh((data, model_parallel), ("data", "model"))
