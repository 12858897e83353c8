"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required for the dry-run's
device-count override ordering.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """A device mesh whose axes are all ``Auto`` (GSPMD-partitioned).

    ``jax.make_mesh`` otherwise makes ``Explicit`` axes, under which a
    gather from a sharded array needs an explicit out-sharding.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e production mesh: 16×16 per pod; 2 pods for multi-pod.

    Axes: ``data`` (batch / ZeRO / sequence-sharded caches), ``model``
    (tensor/expert parallel), plus ``pod`` (data-parallel across the
    inter-pod DCI links) for the 512-chip dry-run.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for subprocess tests (8 host devices)."""
    return _auto_mesh(shape, axes)


def mesh_num_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
