"""Production mesh construction (multi-pod dry-run deliverable).

Defined as functions — importing this module never touches jax device
state.  Mesh axes:
  pod   — inter-pod data parallelism (2 pods × 256 chips)
  data  — intra-pod data/FSDP/sequence parallelism (16)
  model — tensor/expert parallelism (16)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def num_chips(mesh) -> int:
    return mesh.devices.size
