"""SPMD distributed query execution over a device mesh.

The multi-chip execution mode: data-parallel row shards per chip, XLA
collectives over ICI for the exchange (the reference's distributed shuffle,
RapidsShuffleManager + UCX, reference: RapidsShuffleInternalManagerBase.scala)
— redesigned as a single compiled SPMD program: each chip scans/filters its
shard, hash-exchanges rows to key-owning chips via all_to_all, then runs the
local segmented aggregation. One jit, one launch, no per-block RPC.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import sortkeys as sk
from ..ops.hash import partition_ids
from ..ops.kernel_utils import CV
from ..columnar import dtypes as dt
from .collectives import exchange_rows

__all__ = ["make_distributed_groupby_sum", "local_group_sum"]


def local_group_sum(keys, vals, mask):
    """Segmented sum by int64 key on one shard: returns (keys_out,
    sums_out, live_out) with capacity == input capacity."""
    cap = mask.shape[0]
    kcv = CV(keys, mask)
    arrays = [jnp.logical_not(mask).astype(jnp.uint8)]
    arrays += sk.order_keys(kcv, dt.INT64)
    # allow_host=False: this traces under shard_map, where the CPU
    # host-callback sort deadlocks (see ops.sortkeys.lexsort)
    perm = sk.lexsort(arrays, allow_host=False)
    sorted_arrays = [a[perm] for a in arrays]
    boundary = sk.group_boundaries(sorted_arrays)
    seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    live_sorted = mask[perm]
    v_sorted = jnp.where(live_sorted, vals[perm], 0)
    sums = jax.ops.segment_sum(v_sorted, seg_ids, cap)
    seg_live = jax.ops.segment_max(live_sorted.astype(jnp.int32),
                                   seg_ids, cap) > 0
    seg_start = jax.ops.segment_min(jnp.arange(cap), seg_ids, cap)
    src = perm[jnp.clip(seg_start, 0, cap - 1)]
    keys_out = jnp.where(seg_live, keys[src], 0)
    return keys_out, sums, seg_live


def make_distributed_groupby_sum(mesh: Mesh, axis_name: str = "data"):
    """Build the jitted SPMD step: filter -> hash exchange -> grouped sum.

    Input arrays are row-sharded [N] over the mesh; outputs are sharded
    [N * n_shards] per-chip group results (keys owned disjointly by chip).
    """
    n = mesh.devices.size

    def step(keys, vals, mask, threshold):
        def shard_fn(k, v, m, thr):
            # local filter (the scan+filter stage of the query)
            live = m & (v > thr[0])
            pids = partition_ids([CV(k, live)], [dt.INT64], n)
            (karr, varr), mask2 = exchange_rows([k, v], live, pids, n,
                                                axis_name)
            ko, so, lo = local_group_sum(karr, varr, mask2)
            return ko, so, lo

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(axis_name), P()),
            out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        )(keys, vals, mask, threshold)

    return jax.jit(step)
