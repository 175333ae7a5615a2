"""The groups of a grouped aggregate, as its reducers see them.

An aggregate's `g_update` and the merge's `_seg_reduce` ask one object
for a `sum`, `min`, `max` or `any` over every group and never learn how
the groups are laid out. Two layouts exist:

  - `ScatterGroups`: a row names its group by an id (the hash pass's
    bucket). A reduction is a scatter into `num_segments` slots
    (`jax.ops.segment_*`), and its result stands at slot id.
  - `RunGroups`: the rows are in key order, so a group is a contiguous
    run. A reduction is a segmented inclusive scan whose result stands
    at the run's last row; `slots` brings every run's results to slot k
    with ONE stable ride of all of them (`ops/partition.py`). On a v5e
    a scatter costs about 20 ns an element, a sorted 32-bit word about
    2 ns (PERF.md, PR 30 / PR 32 / PR 36).

The reducers that cannot be a scan (`"custom"` in an aggregate's
`state_reducers`: first/last, variance, HLL, t-digest) read `seg_ids`
and `num_segments` from either object and scatter as before; with
`RunGroups` live run k has id k, so their results stand at slot k too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .partition import sorted_by_target

__all__ = ["ScatterGroups", "RunGroups"]


class ScatterGroups:
    """Groups named by an id a row: reduced by scatter, results at slot
    id."""

    def __init__(self, seg_ids, num_segments: int):
        self.seg_ids = seg_ids
        self.num_segments = num_segments

    def sum(self, x):
        return jax.ops.segment_sum(x, self.seg_ids, self.num_segments)

    def min(self, x):
        return jax.ops.segment_min(x, self.seg_ids, self.num_segments)

    def max(self, x):
        return jax.ops.segment_max(x, self.seg_ids, self.num_segments)

    def any(self, flag):
        return jax.ops.segment_max(flag.astype(jnp.int32), self.seg_ids,
                                   self.num_segments) > 0


def _segmented_scan(op, first, x):
    """Inclusive scan of `op` over `x` that starts anew at every row
    where `first` is set (row 0 is). A floating sum adds only inside
    its run: no prefix of another run is ever added and taken away
    again. By doubling: log2(n) passes, each combining a row with the
    one 2^i before it unless a run started in between; `jnp.cumsum` and
    `lax.associative_scan` are the same scan in 4 ms but take 50-70 s
    to compile for a v5e at 1 Mi rows, this one a second (PERF.md,
    PR 36)."""
    n = x.shape[0]
    f, v = first, x
    d = 1
    while d < n:
        # rows under d hold a whole prefix already (f is set there), so
        # what is shifted in is never read
        before = jnp.concatenate([jnp.zeros((d,), v.dtype), v[:-d]])
        v = jnp.where(f, v, op(before, v))
        f = f | jnp.concatenate([jnp.ones((d,), jnp.bool_), f[:-d]])
        d *= 2
    return v


class RunGroups:
    """Groups that are runs of rows in key order. `order` holds the
    sorted key arrays (as `sortkeys.group_boundaries` takes them: a run
    starts where any of them changes), `live` is set on the rows that
    count; dead rows come last and are runs of their own. `boundary` is
    set at each run's first row, `count` is the number of live runs and
    `slot_live` the prefix of slots they fill."""

    def __init__(self, order, live):
        cap = live.shape[0]
        one = jnp.ones(1, jnp.bool_)
        changed = jnp.zeros(cap - 1, jnp.bool_)
        for k in order:
            changed = changed | (k[1:] != k[:-1])
        self.boundary = jnp.concatenate([one, changed])
        self.num_segments = cap
        self._not_last_live = jnp.logical_not(
            jnp.concatenate([changed, one]) & live)
        self.count = cap - jnp.sum(self._not_last_live, dtype=jnp.int32)
        self.slot_live = jnp.arange(cap, dtype=jnp.int32) < self.count

    @property
    def seg_ids(self):
        """A row's run number, for the reducers that scatter."""
        return jnp.cumsum(self.boundary.astype(jnp.int32)) - 1

    def sum(self, x):
        return _segmented_scan(jnp.add, self.boundary, x)

    def min(self, x):
        return _segmented_scan(jnp.minimum, self.boundary, x)

    def max(self, x):
        return _segmented_scan(jnp.maximum, self.boundary, x)

    def any(self, flag):
        return _segmented_scan(jnp.logical_or, self.boundary, flag)

    def slots(self, cols):
        """`cols` (scan results, or any row-aligned array whose value at
        a run's last row is the run's) with run k's value at slot k and
        zeros past the live runs: one stable ride keyed by "is not the
        last row of a live run"."""
        placed = sorted_by_target(self._not_last_live.astype(jnp.int32),
                                  list(cols))
        out = []
        for c in placed:
            keep = self.slot_live.reshape((-1,) + (1,) * (c.ndim - 1))
            out.append(jnp.where(keep, c, jnp.zeros_like(c)))
        return out
