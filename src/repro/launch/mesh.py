"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis crosses the DCN fabric whose topology the paper optimizes (MRLS);
``repro.fabric`` consumes the dry-run's cross-pod collective bytes to pick
the pod-axis strategy.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_test_mesh(shape=(1, 1, 1), axes=("pod", "data", "model")):
    """Tiny mesh for CPU smoke tests (1 device)."""
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))
