"""Production meshes (assignment §MULTI-POD DRY-RUN).

A function, not a module constant: importing this module never touches
jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the sharding rules constrain
    with ``with_sharding_constraint``, which refers to Auto axes only
    (``jax.make_mesh`` makes Explicit axes by default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests (axes match the production mesh
    so sharding rules resolve identically)."""
    return _auto_mesh((1, 1), ("data", "model"))
