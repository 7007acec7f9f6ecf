"""Production mesh builders (task spec: single-pod 16x16, multi-pod
2x16x16).  Functions, not module constants — importing this module never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_pp_mesh(*, pipe: int = 4):
    """Extra lane (beyond the required meshes) for the Piper pipeline
    executor: ("pipe", "data", "model")."""
    return _mk((pipe, 256 // pipe // 16, 16), ("pipe", "data", "model"))


def dp_axes_for(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
