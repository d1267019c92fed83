"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax import
and everything else must see the real (1-device) topology.

Mesh shapes:
  single pod:  (data=16, model=16)            = 256 chips  (TPU v5e pod)
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Axis roles (see DESIGN.md §5):
  pod   — pure data parallel across pods; lowest-bandwidth hop (DCN) gets the
          least-frequent collective (one gradient reduction per step).
  data  — FSDP: parameters/optimizer sharded, per-layer all-gather in-scan.
  model — tensor parallel: heads / d_ff / vocab / experts.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_allocation_mesh", "make_production_mesh", "make_smoke_mesh",
           "mesh_chips"]


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: shardings are propagated by XLA and named only in
    # ``with_sharding_constraint``/``shard_map`` specs (``jax.make_mesh``
    # defaults to Explicit axes, which type every array's sharding)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(shape: Tuple[int, ...] = (1, 1),
                    axes: Tuple[str, ...] = ("data", "model")):
    """Tiny mesh over however many devices the test process has."""
    return _mesh(shape, axes)


def make_allocation_mesh(n_shards: int):
    """Mesh for the sharded allocation fabric: a 1-D ``("shard",)`` axis
    with one device per replica when the host has that many, else a
    ``make_smoke_mesh``-style 1-device mesh. The sharded service runs its
    batched kernels under ``jax.shard_map`` only when the mesh really
    carries ``n_shards`` devices; on smaller hosts it loops over the shard
    axis (same math, one device)."""
    if n_shards >= 1 and len(jax.devices()) >= n_shards:
        return _mesh((n_shards,), ("shard",))
    return make_smoke_mesh((1,), ("shard",))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
