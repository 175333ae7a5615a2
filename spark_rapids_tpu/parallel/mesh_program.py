"""One compiled program for a step that every shard of the mesh runs alike.

A per-partition step launched once a chip is compiled once a chip: jax
keys an executable on the devices its arguments are committed to, and the
program cache's key holds no device. Where the n partitions of a mesh
session run the same step on pieces of the same shapes, the step runs
instead as ONE `shard_map` program over the mesh — one compile, one launch,
and the n chips work at the same time. The pieces stay where they are: the
global arguments are assembled from the per-device arrays
(`make_array_from_single_device_arrays`, no copy) and the results are
handed back as per-device arrays (`addressable_shards`, no copy), so what
is between two such programs is ordinary per-partition data.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import get_mesh, mesh_topology_key

__all__ = ["MeshProgram", "local_pieces"]


def _on(piece, device):
    """`piece` on `device`, untouched where it already lives there."""
    if getattr(piece, "committed", False) and piece.devices() == {device}:
        return piece
    return jax.device_put(piece, device)


class MeshProgram:
    """`fn(tree) -> tree` of one shard, as one program over `n` shards.

    Called with a list of n pytrees (one a shard, leaves of equal shapes
    and at least one dimension, each on any device) and returns a list of
    n pytrees whose leaves live on the shards' devices. A 0-d result leaf
    comes back with shape (1,): it broadcasts as the scalar did, and can
    be fed to the next program as it is."""

    def __init__(self, fn, n: int, axis: str = "data", *, cls: str,
                 tag: str, key: tuple = ()):
        from ..runtime.program_cache import cached_program
        self.n, self.axis = n, axis
        mesh = get_mesh(n, axis)
        self._sharding = NamedSharding(mesh, P(axis))
        self._devices = list(mesh.devices.reshape(-1))

        def shard_fn(tree):
            return jax.tree_util.tree_map(
                lambda y: jnp.reshape(y, (1,)) if jnp.ndim(y) == 0 else y,
                fn(tree))

        def step(tree):
            return jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P(axis))(tree)

        # the key leads with the mesh topology: the lowering bakes in the
        # device assignment (mesh-program-key lint rule)
        self._prog = cached_program(
            step, cls=cls, tag=tag, key=(mesh_topology_key(n, axis),) + key)

    def _global(self, *pieces):
        shape = (self.n * pieces[0].shape[0],) + tuple(pieces[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, self._sharding,
            [_on(p, d) for p, d in zip(pieces, self._devices)])

    def __call__(self, shards: Sequence) -> List:
        out = self._prog(jax.tree_util.tree_map(self._global, *shards))
        leaves, treedef = jax.tree_util.tree_flatten(out)
        local = [local_pieces(a, self.n) for a in leaves]
        return [jax.tree_util.tree_unflatten(
            treedef, [pieces[s] for pieces in local]) for s in range(self.n)]


def local_pieces(arr, n: int) -> List:
    """The n per-device pieces of an array sharded n ways on its first
    axis, in shard order. Views of the device buffers: slicing the global
    array instead would launch an all-gather."""
    per = arr.shape[0] // n
    out = [None] * n
    for sh in arr.addressable_shards:
        out[(sh.index[0].start or 0) // per] = sh.data
    return out
