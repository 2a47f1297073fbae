"""Device meshes.

``make_production_mesh`` is the target topology: one TPU v5e pod is a
16x16 = 256-chip ("data", "model") mesh; the multi-pod variant adds a
leading "pod" axis (2 pods = 512 chips).  Defined as functions so that
importing this module never touches jax device state (the dry-run must
set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(model_axis: int = 1) -> Mesh:
    """Whatever this process actually has (CPU smoke / examples)."""
    n = jax.device_count()
    assert n % model_axis == 0
    return _mk((n // model_axis, model_axis), ("data", "model"))


def mesh_num_chips(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
