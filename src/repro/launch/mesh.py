"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.core.materializer import MESHES, MeshSpec


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_from_spec(spec: MeshSpec) -> Mesh:
    return _make_mesh(spec.shape, spec.axes)


def mesh_spec(name: str) -> MeshSpec:
    return MESHES[name]


def make_local_mesh(axes: Tuple[str, ...] = ("data", "model"),
                    shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n,) + (1,) * (len(axes) - 1)
    return _make_mesh(shape, axes)


def attached_mesh_spec(name: str = "attached") -> MeshSpec:
    """Describe the devices this process is attached to: their count and
    kind (figures from the ``CHIPS`` table, which raises on an unknown
    kind), with HBM per device taken from ``memory_stats()["bytes_limit"]``
    where the backend reports it."""
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return MeshSpec.of_chip(name, (len(devices), 1), ("data", "model"),
                            devices[0].device_kind,
                            hbm_per_device=stats.get("bytes_limit"))
