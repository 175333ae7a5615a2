"""Lockstep execution over a mesh: the glue between operators that run one
program over all shards (TpuExec.execute_mesh) and the rest of the tree.

  mesh_batches     a subtree's output as MeshBatches: the operator's own
                   lockstep form, or a fusable filter/project chain over
                   one, run as ONE mesh program a batch position
  MeshGatherExec   brings the partitions of a mesh-placed child to the
                   default device, for a parent that takes them all into
                   one single-device program (sort, limit, a broadcast
                   build, an ungrouped aggregate)
"""
from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from ..profiler import xla_stats
from .base import ExecContext, TpuExec, collapse_fusable
from .batch import DeviceBatch, MeshBatch

__all__ = ["mesh_batches", "cut_program", "MeshGatherExec"]


def mesh_batches(ctx: ExecContext, node: TpuExec, n: int,
                 axis: str = "data") -> Optional[Iterator[MeshBatch]]:
    """`node`'s output in lockstep over the n-device mesh, or None."""
    it = node.execute_mesh(ctx, n)
    if it is not None:
        return it
    base, stages, n_fused = collapse_fusable(node)
    if not n_fused:
        return None
    src = base.execute_mesh(ctx, n)
    if src is None:
        return None
    from ..parallel.mesh_program import MeshProgram
    from .nodes import make_table
    prog = MeshProgram(lambda t: stages(*t), n, axis, cls="LockstepChain",
                        tag="run", key=(stages._stage_fp,))
    schema = node.schema

    def run():
        for mb in src:
            ctx.check_cancel()
            outs = prog(mb.trees())
            xla_stats.count_dispatch()
            yield MeshBatch([
                DeviceBatch(make_table(schema, cvs, b.num_rows), b.num_rows,
                            mask, b.capacity)
                for b, (cvs, mask) in zip(mb.shards, outs)])
    return run()


def cut_program(n: int, axis: str, new_cap: int, bcaps: tuple,
                count_at: int):
    """The program that cuts every shard's (cvs, stats) down to the rows
    [:new_cap] (and each var-width column's bytes [:bcaps[i]]), with the
    live mask of `stats[count_at]` rows: one capacity for all shards,
    chosen on the host from the largest, so that the next lockstep
    operator takes the n results into one program."""
    from ..ops.kernel_utils import CV
    from ..parallel.mesh_program import MeshProgram

    def cut(tree):
        cvs, stats = tree
        out = [CV(cv.data[:bcaps[ci]], cv.validity[:new_cap],
                  cv.offsets[:new_cap + 1]) if cv.offsets is not None
               else CV(cv.data[:new_cap], cv.validity[:new_cap])
               for ci, cv in enumerate(cvs)]
        return out, jnp.arange(new_cap) < stats[count_at]

    return MeshProgram(cut, n, axis, cls="LockstepCut", tag="rows",
                        key=(new_cap, bcaps, count_at))


class MeshGatherExec(TpuExec):
    """The child's partitions, batch by batch, on the default device."""

    def __init__(self, child: TpuExec):
        super().__init__([child], child.schema)

    def describe(self):
        return "MeshGatherExec"

    def execute_partition(self, ctx: ExecContext, pid: int):
        from ..profiler import tracing
        from .nodes import make_table
        home = jax.devices()[0]
        m = ctx.metrics_for(self._op_id)
        for b in self.children[0].execute_partition(ctx, pid):
            ctx.check_cancel()
            if b.row_mask.devices() == {home}:
                yield b
                continue
            with tracing.span("mesh.gather", "collective", ctx,
                              bytes=int(b.nbytes)):
                cvs, mask = jax.device_put((b.cvs(), b.row_mask), home)
            m.add("meshGatherBytes", int(b.nbytes))
            yield DeviceBatch(make_table(self.schema, cvs, b.num_rows),
                              b.num_rows, mask, b.capacity)
