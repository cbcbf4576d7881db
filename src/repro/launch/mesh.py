"""Production meshes (assignment spec).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  Single-pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) -- the pod axis
is the slower DCN/ICI-superpod dimension; DP/FSDP spans (pod, data).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the step builders place arrays with with_sharding_constraint,
    # which Explicit axes (jax.make_mesh's default) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU tests (1 device)."""
    return _auto_mesh(shape, axes)
