"""Device-free mesh for the sharding planner, written for jax 0.9.

``jax.make_mesh`` and ``AbstractMesh`` there default to ``Explicit`` axes,
under which ``with_sharding_constraint`` may only name ``Auto`` axes. The
models shard by logical-axis rules (``repro.sharding.api``), so every mesh
the repo builds has ``Auto`` axes (see also ``repro.launch.mesh``). Model
code calls ``jax.shard_map`` directly.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device-free mesh for planning/spec generation (no jax device init)."""
    if len(shape) != len(axes):
        raise ValueError(f"shape {tuple(shape)} and axes {tuple(axes)} "
                         f"must have equal length")
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=(AxisType.Auto,) * len(axes))
