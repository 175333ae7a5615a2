"""ICI partition exchange: the all-to-all shuffle core.

Replaces the reference's UCX peer-to-peer transfer path
(reference: shuffle-plugin/.../UCXShuffleTransport.scala:49,
RapidsShuffleClient/Server) with a single XLA collective, and moves
nothing element by element on either side of it:

  send     the rows are sorted by target shard (stable, dead rows last)
           with the payload riding the sort (`_sorted_by_target`), after
           which the rows for peer p are ONE contiguous run; peer p's
           bucket is a `dynamic_slice` at the run's start. No gather by
           a sorted index, no scatter into a zeroed (n, cap) buffer.
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

__all__ = ["exchange_cvs"]


def _runs_by_target(mask, pids, n_shards: int):
    """(eff_pid, starts): a row's effective target (dead rows go to
    bucket n, past every peer's) and, for rows brought into stable
    target order, where each peer's run begins: starts [n+1], starts[n]
    the live row count. Counted, not searched: n reductions."""
    eff_pid = jnp.where(mask, pids, n_shards).astype(jnp.int32)
    per_target = jnp.sum(
        eff_pid[None, :] == jnp.arange(n_shards, dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(per_target)])
    return eff_pid, starts


def _carrier(dtype):
    """The type an array travels as: itself, or int32 where narrower."""
    return dtype if dtype.itemsize >= 4 else jnp.dtype(jnp.int32)


def _to_words(arrays):
    """Every array [cap, ...] as rows of ONE uint32 [W, cap]: a 64-bit
    value is two rows, a narrow integer widens to one, trailing dims
    (decimal128 limb pairs) are rows of their own, and the bool arrays
    share rows a bit each."""
    cap = arrays[0].shape[0]
    rows, flags = [], []
    for a in arrays:
        if a.dtype == jnp.bool_:
            flags.append(a)
            continue
        w = jax.lax.bitcast_convert_type(a.astype(_carrier(a.dtype)),
                                         jnp.uint32).reshape(cap, -1)
        rows += [w[:, j] for j in range(w.shape[1])]
    for i in range(0, len(flags), 32):
        word = jnp.zeros(cap, jnp.uint32)
        for bit, f in enumerate(flags[i:i + 32]):
            word = word | (f.astype(jnp.uint32) << bit)
        rows.append(word)
    return jnp.stack(rows)


def _from_words(words, like):
    """Inverse of `_to_words`: arrays shaped and typed as `like`."""
    out, r, flags = [], 0, []
    for a in like:
        if a.dtype == jnp.bool_:
            flags.append(len(out))
            out.append(None)
            continue
        carrier = _carrier(a.dtype)
        per = carrier.itemsize // 4
        k = per * (a.size // a.shape[0])
        w = jnp.stack(list(words[r:r + k]), axis=1)
        r += k
        w = w.reshape(a.shape + ((2,) if per == 2 else ()))
        out.append(jax.lax.bitcast_convert_type(w, carrier).astype(a.dtype))
    for k, at in enumerate(flags):
        out[at] = ((words[r + k // 32] >> (k % 32)) & 1).astype(jnp.bool_)
    return out


def _sorted_by_target(eff_pid, arrays):
    """`arrays` in stable target order. The payload rides the sort: each
    32-bit word of it is the second operand of the SAME two-operand
    stable sort by target, one word a turn of a loop, so the program
    holds one sort to compile however wide the rows are. On a v5e a
    gather by a sorted index costs 20 ns an element (192 ms for six
    arrays of 1.5 M rows), these sorts 3.4 ms a word (25 ms); all words
    as operands of one variadic sort run in 11 ms but take 17 s more to
    compile for every word (PERF.md, PR 30)."""
    words = jax.lax.map(
        lambda w: jax.lax.sort((eff_pid, w), num_keys=1, is_stable=True)[1],
        _to_words(arrays))
    return _from_words(words, arrays)


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
    eff_pid, starts = _runs_by_target(mask, pids, n_shards)
    strs = [cv for cv in cvs if cv.offsets is not None]
    fixed = [cv.data for cv in cvs if cv.offsets is None]
    # one pass brings every fixed-width array into target order, and for
    # the string columns the row order itself
    payload = [cv.validity for cv in cvs] + fixed
    if strs:
        payload.append(jnp.arange(cap, dtype=jnp.int32))
    payload = _sorted_by_target(eff_pid, payload)
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
