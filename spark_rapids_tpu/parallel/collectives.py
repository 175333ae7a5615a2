"""ICI partition exchange: the all-to-all shuffle core.

Replaces the reference's UCX peer-to-peer transfer path
(reference: shuffle-plugin/.../UCXShuffleTransport.scala:49,
RapidsShuffleClient/Server) with a single XLA collective, and moves
nothing element by element on either side of it:

  send     the rows are sorted by target shard (stable, dead rows last)
           with the payload riding the sort (`sorted_by_target` of
           `ops/partition.py`: the tree's one sort-by-target, which the
           one-chip exchange map `exec/exchange.py:_finish_map` calls
           too), after which the rows for peer p are ONE contiguous run;
           peer p's bucket is a `dynamic_slice` at the run's start. No
           gather by a sorted index, no scatter into a zeroed (n, cap)
           buffer.
  wire     `jax.lax.all_to_all` moves the n buckets of every payload
           array over ICI; the n run lengths ride one small all_to_all
           beside them. No mask crosses.
  receive  source s's rows sit at the front of block s; the blocks are
           written end to end (`dynamic_update_slice` at the running sum
           of the counts, s ascending: each block's padded tail is
           overwritten by the next block). The result is a live PREFIX in
           (source shard, source row) order: no compaction follows.

String bytes go the same way: the column is brought into sorted row order
once (`take_strings`), the bytes for peer p are then the contiguous range
between the sorted offsets at the run's ends, and the received byte runs
are placed end to end; offsets are one cumsum of the received lengths.

All functions here run INSIDE shard_map (they reference an axis name).
A bucket holds B = the shard's capacity: the only size safe under any
skew (every row of a shard may target one peer) and it needs no overflow
path. The padded slots cost wire and HBM bandwidth, not element-wise
work; `shardSlotsReceived` beside `shardRowsReceivedMax` (SpmdStageExec)
says how full the wire is.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..ops.partition import runs_by_target, sorted_by_target

__all__ = ["exchange_cvs"]


def _all_to_all(blocks, axis_name: str):
    """blocks [n, ...] (block p is for peer p) -> [n, ...] (block s is
    what source s sent here)."""
    return jax.lax.all_to_all(blocks, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)


def _send_runs(a_sorted, starts, n_shards: int, axis_name: str):
    """all_to_all the n runs of `a_sorted` (leading axis in target-shard
    order) that begin at starts[p]: each travels as one bucket of the
    whole leading extent, its tail whatever follows the run. Returns
    [n, cap, ...]: block s is what source s sent here."""
    cap = a_sorted.shape[0]
    tail = (jnp.int32(0),) * (a_sorted.ndim - 1)
    padded = jnp.concatenate([a_sorted, jnp.zeros_like(a_sorted)])
    send = jnp.stack([
        jax.lax.dynamic_slice(padded, (starts[p],) + tail,
                              (cap,) + a_sorted.shape[1:])
        for p in range(n_shards)])
    return _all_to_all(send, axis_name)


def _place_runs(recv, counts):
    """Blocks [n, cap, ...] whose first counts[s] entries are live ->
    [n * cap, ...] with the live runs end to end (s ascending). A block
    written at the running sum never passes the end (every count <= cap),
    and its dead tail is overwritten by the next block or lies past the
    total."""
    n = recv.shape[0]
    tail = (jnp.int32(0),) * (recv.ndim - 2)
    offs = jnp.cumsum(counts) - counts
    out = recv.reshape((-1,) + recv.shape[2:])      # block 0 is in place
    for s in range(1, n):
        out = jax.lax.dynamic_update_slice(out, recv[s], (offs[s],) + tail)
    return out


def exchange_cvs(cvs: Sequence, mask, pids, n_shards: int,
                 axis_name: str = "data"):
    """Exchange the rows of a list of CVs (fixed-width and string columns)
    so each live row lands on shard pids[row].

    Returns (out_cvs, out_mask, count) with row capacity n_shards * cap.
    The rows arrive ALREADY COMPACTED: `out_mask` is the prefix
    `arange(n * cap) < count`, rows in (source shard, source row) order,
    validity false and string lengths 0 past the prefix. String columns
    arrive as packed (gap-free) byte buffers with rebuilt offsets.
    Runs INSIDE shard_map.
    """
    from ..ops.gather import take_strings
    from ..ops.kernel_utils import CV

    cap = mask.shape[0]
    eff_pid, starts = runs_by_target(mask, pids, n_shards)
    strs = [cv for cv in cvs if cv.offsets is not None]
    fixed = [cv.data for cv in cvs if cv.offsets is None]
    # one pass brings every fixed-width array into target order, and for
    # the string columns the row order itself
    payload = [cv.validity for cv in cvs] + fixed
    if strs:
        payload.append(jnp.arange(cap, dtype=jnp.int32))
    payload = sorted_by_target(eff_pid, payload)
    valids, datas = payload[:len(cvs)], iter(payload[len(cvs):])
    # per string column: the bytes in sorted row order, and where each
    # peer's byte run begins in them
    if strs:
        live_sorted = jnp.arange(cap, dtype=jnp.int32) < starts[n_shards]
        strs = [take_strings(cv, payload[-1], live_sorted) for cv in strs]
    sent = [starts] + [s.offsets[starts] for s in strs]
    counts = _all_to_all(
        jnp.stack([c[1:] - c[:-1] for c in sent], axis=1), axis_name).T
    count = jnp.sum(counts[0])
    out_mask = jnp.arange(n_shards * cap, dtype=jnp.int32) < count

    def rows(a_sorted):
        return _place_runs(_send_runs(a_sorted, starts, n_shards,
                                      axis_name), counts[0])

    out_cvs = []
    byte_runs = zip(strs, sent[1:], counts[1:])
    for cv, valid in zip(cvs, valids):
        valid = rows(valid) & out_mask
        if cv.offsets is None:
            out_cvs.append(CV(rows(next(datas)), valid))
            continue
        s, byte_starts, byte_counts = next(byte_runs)
        lens = (s.offsets[1:] - s.offsets[:-1]).astype(jnp.int32)
        lens_r = jnp.where(out_mask, rows(lens), 0)
        data = _place_runs(_send_runs(s.data, byte_starts, n_shards,
                                      axis_name), byte_counts)
        # bytes past the received total are garbage: zero for determinism
        data = jnp.where(jnp.arange(data.shape[0], dtype=jnp.int32)
                         < jnp.sum(byte_counts), data, 0).astype(jnp.uint8)
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(lens_r).astype(jnp.int32)])
        out_cvs.append(CV(data, valid, offsets))
    return out_cvs, out_mask, count
