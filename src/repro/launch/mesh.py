"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and only then builds the mesh.

Single pod : (16, 16)    axes ("data", "model")      = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips;
             the "pod" axis is an extra data-parallel dimension whose
             collectives cross the DCN/pod boundary — exactly the traffic
             SketchDP compresses (DESIGN.md §3.1).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the partitioner propagates shardings through the model's
    # gathers and reshapes (explicit axes would demand an out_sharding on
    # each of them)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU tests (requires XLA_FLAGS device count)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
