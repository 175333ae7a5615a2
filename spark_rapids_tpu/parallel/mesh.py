"""Device mesh helpers for SPMD execution over ICI/DCN.

The TPU-native replacement for the reference's executor topology: instead
of NCCL/UCX peer endpoints (reference: shuffle-plugin UCX.scala:71), a
jax.sharding.Mesh names the chips and XLA lowers collectives onto ICI.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "get_mesh", "P", "NamedSharding", "Mesh",
           "shard_rows", "mesh_topology_key", "mesh_fingerprint"]

_meshes: dict = {}


def mesh_topology_key(n_devices: int, axis_name: str = "data") -> tuple:
    """Program-cache key component for shard_map/mesh programs:
    (n_devices, axis name, device kind). A collective program's lowering
    bakes in the mesh topology — replica groups, ICI routing, the
    device target — so two topologies must never share a cache entry or
    a warm-pack manifest entry (the mesh-program-key lint rule polices
    that every mesh program in exec/ keys on this)."""
    return ("mesh", int(n_devices), str(axis_name), _device_kind())


def mesh_fingerprint() -> str:
    """Host-level mesh identity mixed into the warm-pack fingerprint:
    device kind + visible device count. A pack recorded on an 8-device
    mesh must not preload into a 1-device process (the sharded
    signatures could never dispatch there) and vice versa."""
    return f"mesh:{_device_kind()}:{len(jax.devices())}"


def _device_kind() -> str:
    return str(jax.devices()[0].device_kind)


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = "data") -> Mesh:
    """Mesh over the first ``n_devices`` devices of the DEFAULT platform.
    Too few devices raises: a mesh never moves to another platform on
    its own (virtual CPU meshes are asked for with JAX_PLATFORMS=cpu)."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)} on platform "
                f"{devs[0].platform!r}; set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"with JAX_PLATFORMS=cpu for virtual meshes")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def get_mesh(n_devices: int, axis_name: str = "data") -> Mesh:
    """The process's one Mesh over the first ``n_devices`` devices: the
    cached table's placement, the stages and the lockstep programs of a
    session all name the same devices in the same order."""
    key = (int(n_devices), axis_name)
    mesh = _meshes.get(key)
    if mesh is None:
        mesh = _meshes.setdefault(key, make_mesh(n_devices, axis_name))
    return mesh


def shard_rows(mesh: Mesh, arr, axis_name: str = "data"):
    """Place a [rows, ...] array row-sharded across the mesh."""
    return jax.device_put(arr, NamedSharding(mesh, P(axis_name)))
