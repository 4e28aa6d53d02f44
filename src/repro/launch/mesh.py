"""Production meshes. Defined as functions so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax import).

Single pod:  (data=16, model=16)          — 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)   — 512 chips across 2 pods;
             the pod axis carries pure data parallelism (gradient
             all-reduce over DCI), model parallelism never crosses pods.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
