"""DeviceBatch: the unit of execution — a Table plus a live-row mask.

TPU-first filter representation: instead of materializing a compacted table
after every Filter (cudf `apply_boolean_mask` in the reference), a batch
carries `row_mask` (bool[capacity]); padding rows and filtered rows are
False. Downstream projections compute garbage in dead lanes (free on the
VPU), and aggregation/compaction consume the mask. Compaction happens only
when an operator truly needs dense rows (shuffle, join build, sort).
"""
from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp

from ..columnar.table import Table
from ..ops.kernel_utils import CV

__all__ = ["DeviceBatch", "MeshBatch"]


class DeviceBatch:
    def __init__(self, table: Table, num_rows: Optional[int] = None,
                 row_mask=None, capacity: Optional[int] = None):
        self.table = table
        if num_rows is None:
            num_rows = table.num_rows
        self.num_rows = num_rows           # upper bound of live rows (host)
        if capacity is None:
            if table.columns:
                capacity = table.columns[0].capacity
            else:
                from ..columnar.column import bucket_capacity
                capacity = bucket_capacity(max(num_rows, 1))
        self.capacity = capacity
        if row_mask is None:
            row_mask = jnp.arange(capacity) < num_rows
        self.row_mask = row_mask

    def cvs(self) -> List[CV]:
        def as_cv(c):
            return CV(c.data, c.validity, c.offsets,
                      tuple(as_cv(ch) for ch in c.children))
        return [as_cv(c) for c in self.table.columns]

    @property
    def nbytes(self) -> int:
        return self.table.nbytes + self.capacity

    def __repr__(self):
        return (f"DeviceBatch(rows<={self.num_rows}, cap={self.capacity}, "
                f"cols={self.table.num_columns})")


class MeshBatch:
    """One batch a shard of a mesh, in shard order, all of the same
    capacities and each on its shard's device: what a lockstep operator
    (TpuExec.execute_mesh) hands the next, so that one program over the
    mesh (parallel/mesh_program.py) takes the n of them at once."""

    def __init__(self, shards: List[DeviceBatch]):
        self.shards = list(shards)

    def trees(self):
        """The argument a mesh program takes: (cvs, mask) a shard."""
        return [(b.cvs(), b.row_mask) for b in self.shards]


def maybe_compact(batch: DeviceBatch, schema, factor: int = 4):
    """Compact a sparse batch (live rows << capacity) down to
    bucket_capacity(live). Holey masks ride through filters and FK joins
    for free, but sort-based consumers (aggregate, sort, exchange, join
    build) pay O(capacity log capacity) — one gather here collapses that.
    Costs one scalar fetch + one gather; skipped unless the capacity
    shrinks by `factor` or more."""
    import jax.numpy as jnp

    from ..columnar.column import bucket_capacity, bucket_policy
    from ..ops.gather import compaction_perm, gather_cols
    from ..utils.transfer import fetch_int
    from .nodes import make_table

    # the policy floor, not the constant: under a coarse bucket grid a
    # batch at the floor capacity cannot shrink, so skip the fetch
    if batch.capacity <= bucket_policy()[0] * factor:
        return batch
    live = fetch_int(jnp.sum(batch.row_mask.astype(jnp.int32)))
    new_cap = bucket_capacity(max(live, 1))
    if new_cap * factor > batch.capacity:
        return batch
    perm, _ = compaction_perm(batch.row_mask)
    idx = perm[:new_cap]
    inb = jnp.arange(new_cap) < live
    out_cvs = gather_cols(batch.cvs(), idx, inb)
    return DeviceBatch(make_table(schema, out_cvs, live), live, inb,
                       new_cap)
