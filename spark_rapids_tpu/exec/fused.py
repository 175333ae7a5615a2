"""FusedStageExec: one jitted program per pipeline stage.

The WholeStageCodegen analog (reference: GpuExec chains fused by cudf
kernel launches; PAPERS.md "Rethinking Analytical Processing in the GPU
Era" on per-operator dispatch overhead): the plan-time fusion pass
(plan/fusion.py) collapses a maximal chain of narrow operators —
Filter, Project, limit-mask pre-chains — into one node whose single
`jax.jit` program composes the members' pure batch transforms
(TpuExec.fusable_stage) bottom-up. XLA then fuses the whole stage into
a handful of kernels: one dispatch per batch instead of one per
operator, and no intermediate DeviceBatch materialization between
members.

Member lore ids survive fusion: EXPLAIN renders
`FusedStage[loreId=N] { Filter[4] > Project[5] }` (top-down plan
order), and the profiler attributes one opTime to the fused node plus a
per-member `fusedRows.<Name>[<loreId>]` live-row counter (accumulated
on device, fetched once per partition — no per-batch sync).

Donation: dead input buffers (the child's cvs + mask) are donated on
real accelerators so XLA updates in place; on the CPU backend donation
is a warning-generating no-op, so it is skipped. Chains over
CachedScanExec are never fused (plan/fusion.py barrier), so donation
can never invalidate an HBM-cached batch.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..profiler import xla_stats
from .base import D128_MARK, ExecContext, TpuExec, report_d128
from .batch import DeviceBatch

__all__ = ["FusedStageExec"]


class FusedStageExec(TpuExec):
    """A fused chain of narrow operators compiled as one program.

    `members` is the original chain in plan order (parent-most first);
    `base` is the first non-fused descendant that actually produces
    batches. Members keep their lore ids for EXPLAIN/profiling but are
    no longer in the `children` tree — `children == [base]`.
    """

    def __init__(self, members: List[TpuExec], base: TpuExec):
        super().__init__([base], members[0].schema)
        self.members = list(members)
        # execution order is bottom-up: the deepest member runs first
        stages = [m.fusable_stage() for m in reversed(self.members)]
        self._exec_order = list(reversed(self.members))
        wide = [m.d128_exprs() for m in self._exec_order]
        self._d128 = sum(wide)

        def _run(cvs, mask, stats):
            # where a member computes in 128-bit decimals, one more
            # counter behind the members': the live rows into those
            counts, rows128 = [], jnp.zeros((), jnp.int64)
            for fn, w in zip(stages, wide):
                if w:
                    rows128 += (counts[-1] if counts
                                else jnp.sum(mask, dtype=jnp.int64))
                cvs, mask = fn(cvs, mask)
                counts.append(jnp.sum(mask, dtype=jnp.int64))
            if self._d128:
                counts.append(rows128)
            return cvs, mask, stats + jnp.stack(counts)

        # donation is a no-op (with a warning) on the CPU backend; on
        # device backends the child's batch buffers and the running
        # stats vector are dead after the call and donated
        donate = () if jax.default_backend() == "cpu" else (0, 1, 2)
        from ..runtime.program_cache import cached_program
        self._jit = cached_program(
            _run, cls="FusedStageExec",
            tag="run" + D128_MARK * bool(self._d128),
            key=self.stage_fingerprint(), donate_argnums=donate)

    # ------------------------------------------------------------------
    def fusable_stage(self):
        """A FusedStage is itself fusable: parents that collapse their
        child chain (aggregate/limit/sort/join pre-stages) compose
        straight through it."""
        fns = [m.fusable_stage() for m in self._exec_order]

        def fn(cvs, mask):
            for f in fns:
                cvs, mask = f(cvs, mask)
            return cvs, mask
        return fn

    def preserves_ordinals(self) -> bool:
        return all(m.preserves_ordinals() for m in self.members)

    def d128_exprs(self) -> int:
        return self._d128

    def stage_fingerprint(self) -> tuple:
        return ("FusedStage",) + tuple(
            m.stage_fingerprint() for m in self._exec_order)

    def describe(self) -> str:
        parts = " > ".join(
            f"{m.node_name().replace('Exec', '')}"
            f"[{getattr(m, 'lore_id', '?')}]" for m in self.members)
        return (f"FusedStage[loreId={getattr(self, 'lore_id', '?')}] "
                f"{{ {parts} }}")

    # ------------------------------------------------------------------
    def execute_partition(self, ctx: ExecContext, pid: int):
        from ..runtime import faults
        from ..utils.transfer import fetch
        from . import degrade
        from .nodes import make_table
        m = ctx.metrics_for(self._op_id)
        stats = jnp.zeros(len(self.members) + bool(self._d128),
                          dtype=jnp.int64)
        n_batches = 0
        for batch in self.children[0].execute_partition(ctx, pid):
            ctx.check_cancel()
            if self._op_id not in ctx.degraded:
                try:
                    if faults.ACTIVE:
                        faults.hit("device.dispatch",
                                   query_id=ctx.query_id,
                                   op="FusedStageExec")
                    with m.timer("opTime"):
                        cvs, mask, stats = self._jit(
                            batch.cvs(), batch.row_mask, stats)
                except Exception as e:  # noqa: BLE001 - classified below
                    if not (degrade.hostable_fused(self)
                            and degrade.should_degrade(ctx, self, e)):
                        raise
                else:
                    xla_stats.count_dispatch()
                    n_batches += 1
                    yield DeviceBatch(
                        make_table(self.schema, cvs, batch.num_rows),
                        batch.num_rows, mask, batch.capacity)
                    continue
            # degraded (or this batch's dispatch just failed): the host
            # interpreter runs the member chain bottom-up
            with m.timer("hostEvalTime"):
                hb = degrade.host_fused_batch(self, batch)
            m.add("degradedToHost", 1)
            if hb is None:
                continue
            n_batches += 1
            yield hb
        m.add("numOutputBatches", n_batches)
        if n_batches:
            # one partition-end fetch for every member counter
            vals = fetch(stats)
            for member, v in zip(self._exec_order, list(vals)):
                m.add(f"fusedRows.{member.node_name().replace('Exec', '')}"
                      f"[{getattr(member, 'lore_id', '?')}]", int(v))
            report_d128(m, self._d128, int(vals[-1]))
