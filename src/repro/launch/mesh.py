"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single CPU device.

Every mesh is built here, with ``Auto`` axes: ``jax.make_mesh`` defaults
to ``Explicit`` axes, under which ``parallel.ctx.shard_activation``'s
``with_sharding_constraint`` calls are rejected.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small meshes for tests (must divide the available device count)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return mesh.devices.size
