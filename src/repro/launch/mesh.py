"""Production meshes.

Functions (not module-level constants) so importing this module never
touches jax device state — the 512-placeholder-device dry run must set
XLA_FLAGS before jax initializes.

Every mesh here has ``Auto`` axes: the sharding rules of ``repro.dist``
place params, optimizer state and batches with ``jit`` shardings and let
the compiler propagate the rest (``jax.make_mesh`` otherwise defaults to
``Explicit`` axes, under which ops such as the embedding gather demand
an output sharding of their own).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod:  2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
