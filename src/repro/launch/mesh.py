"""Production mesh definitions.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init.

Every mesh has ``Auto`` axes: jax 0.9 defaults ``make_mesh`` to ``Explicit``
axes, which the logical-axis sharding rules cannot constrain.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

from ..compat import abstract_mesh

PRODUCTION_SHAPES = {
    False: ((16, 16), ("data", "model")),
    True: ((2, 16, 16), ("pod", "data", "model")),
}


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e production mesh: one pod = 16x16 = 256 chips; two pods = 512.

    Axes: "pod" extends data parallelism across pods (cross-pod DCI carries
    only the gradient all-reduce / batch split); "data" is in-pod data
    parallelism; "model" is the tensor/expert/sequence-parallel axis kept
    inside a pod (ICI-local).
    """
    shape, axes = PRODUCTION_SHAPES[multi_pod]
    return _make_mesh(shape, axes)


def make_abstract_production_mesh(*, multi_pod: bool = False):
    """Device-free production mesh for planners/spec generation (safe to call
    before jax device init — e.g. under the dry-run's XLA_FLAGS dance)."""
    shape, axes = PRODUCTION_SHAPES[multi_pod]
    return abstract_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over all devices: CPU tests on virtual host devices, or
    one multi-chip host (requires prod(shape) devices)."""
    return _make_mesh(shape, axes)
