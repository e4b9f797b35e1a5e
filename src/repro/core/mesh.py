"""The device mesh of the sharded execution stack (DESIGN.md §10).

One 1-D mesh: shard rows ride on the "data" axis, the "model" axis is
trivial.  Building a mesh is a FUNCTION call (importing this module never
touches jax device state), so the single-device path, which reaches here
only through ``resolve_shard_mesh(None, None)``, sees no device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS


def make_shard_mesh(shards: int | None = None) -> Mesh:
    """The 1-D row-shard mesh: ``shards`` devices on the "data" axis
    (model axis trivial).  ``shards=None`` takes every visible device.
    Raises with the CPU simulation recipe when the host has too few
    devices."""
    n = len(jax.devices())
    if shards is None:
        shards = n
    if shards < 1:
        raise ValueError(f"shards must be >= 1 (got {shards})")
    if shards > n:
        raise ValueError(
            f"shards={shards} but only {n} device(s) visible; on CPU, "
            "export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{shards} BEFORE importing jax to simulate a {shards}-device "
            "mesh")
    devices = np.asarray(jax.devices()[:shards]).reshape(shards, 1)
    return Mesh(devices, ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axis names present in a mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_count(mesh) -> int:
    """Number of row shards a mesh carries = product of its data axes."""
    k = 1
    for a in dp_axes(mesh):
        k *= int(mesh.shape[a])
    return k


def resolve_shard_mesh(mesh=None, shards: int | None = None):
    """Normalize the ``mesh=`` / ``shards=`` constructor surface of the
    sharded apps: ``(None, None)`` selects the single-device stack
    (returns ``(None, 1)``), ``shards`` alone builds the 1-D shard mesh,
    and an explicit mesh is validated against ``shards`` when both are
    given.  Returns ``(mesh_or_None, num_shards)``."""
    if mesh is None and shards is None:
        return None, 1
    if mesh is None:
        return make_shard_mesh(int(shards)), int(shards)
    k = shard_count(mesh)
    if shards is not None and int(shards) != k:
        raise ValueError(f"shards={shards} does not match the mesh's "
                         f"{k} data-axis device(s)")
    return mesh, k


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis row sharding over the mesh's data axes — the
    placement of the padded ``(k, S, ...)`` fixpoint state in the
    sharded execution stack: shard ``i``'s rows live on data-axis device
    ``i`` between sweeps, so the resident loop never rebuilds the full
    state on one device."""
    dp = dp_axes(mesh)
    return NamedSharding(mesh, PS(dp if len(dp) > 1 else dp[0]))
