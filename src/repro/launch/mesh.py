"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must see the single real device.

Mesh geometry (TPU v5e):
  single-pod: (16, 16)      = 256 chips,  axes (data, model)
  multi-pod:  (2, 16, 16)   = 512 chips,  axes (pod, data, model)

The ``pod`` axis is pure data parallelism across pods (the only traffic that
crosses DCN is the once-per-step gradient all-reduce); ``data`` is in-pod
DP/FSDP; ``model`` is tensor/expert parallelism inside the pod where ICI
bandwidth lives.
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The mesh axes that carry the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
