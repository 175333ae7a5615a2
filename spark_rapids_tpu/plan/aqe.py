"""Adaptive query execution driver: replan at exchange boundaries.

The control-loop half of AQE (reference: Spark's AdaptiveSparkPlanExec
driving QueryStage materialization + GpuCustomShuffleReaderExec /
OptimizeSkewedJoin / DemoteBroadcastHashJoin in reverse). The reader
half — `exec/aqe.py` — computes coalesced/split task groups lazily from
materialized partition stats; this module makes execution STAGE-WISE:
before the consumer launches, the driver walks the physical plan
bottom-up, materializes each shuffle stage via the existing exchange
pool, and replans between stage completion and consumer launch:

  1. JOIN DEMOTION: a shuffled hash join whose build side materializes
     under `autoBroadcastJoinThreshold` is rewritten in place to a
     broadcast hash join over the already-shuffled build blocks — the
     stream-side map phase never runs (the biggest single win: q2/q16
     shapes where the CBO overestimates a filtered build side).
  2. COALESCE + SKEW-SPLIT: the per-plan task groups (AqeShufflePlan)
     are forced eagerly so every decision is taken — and logged — at a
     stage boundary rather than on first read.

Every decision is an `aqe_replan` event-log record (lore ids old→new)
and feeds the EXPLAIN ANALYZE annotations. The driver runs on the
query's own thread under the service's cancellation checkpoints
(`ctx.check_cancel` before every stage barrier) and takes no locks of
its own — stage materialization happens under each exchange's existing
lockdep-witnessed instance lock, never under a planner-wide lock.

Observed cardinalities harvested after the run (plan/stats.py
`harvest_calibration`) close the loop: the session-scoped calibration
table corrects CBO estimates for later plans of the same subtrees.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List

__all__ = ["run_stage_driver", "aqe_stats", "reset_stats"]

# session-process AQE decision counters (read through aqe_stats())
_STATS_LOCK = threading.Lock()
_STATS = {"coalesced_partitions": 0, "skew_splits": 0, "demotions": 0,
          "mesh_reshards": 0, "mesh_demotions": 0}


def aqe_stats() -> Dict[str, int]:
    """Process-lifetime AQE decision counters, merged with the
    calibration table's counters."""
    with _STATS_LOCK:
        out: Dict[str, int] = dict(_STATS)
    from .stats import calibration_stats
    out.update(calibration_stats())
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _bump(key: str, amount: int = 1) -> None:
    if amount:
        with _STATS_LOCK:
            _STATS[key] += amount


def _max_lore_id(root) -> int:
    mx = 0
    stack, seen = [root], set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        lid = getattr(node, "lore_id", None)
        if isinstance(lid, int):
            mx = max(mx, lid)
        stack.extend(node.children)
    return mx


def run_stage_driver(root, ctx, conf) -> List[Dict[str, Any]]:
    """Stage-wise AQE pass over a physical plan, between planning and
    the consumer launch. Returns the decision records for the
    `aqe_replan` event (re-served verbatim on re-execution of a cached
    root, so every run's event log is self-contained). Mutations are
    in-place and sticky — the same properties the exchange
    memoization already relies on."""
    from ..config import ADAPTIVE_ENABLED
    if not conf.get(ADAPTIVE_ENABLED) or getattr(ctx, "planning", False):
        return []
    from ..exec.aqe import AQEShuffleReadExec
    from ..exec.join import HashJoinExec
    from ..exec.spmd_stage import SpmdStageExec

    decisions: List[Dict[str, Any]] = []
    seen_plans: set = set()
    lore_alloc = [0]  # lazily seeded from the tree's max lore id

    def visit(node):
        ctx.check_cancel()
        if isinstance(node, HashJoinExec):
            # demotion must be judged BEFORE the stream subtree is
            # visited: forcing the stream reader's groups would run the
            # very map phase demotion exists to skip
            _maybe_demote(node, ctx, conf, decisions, lore_alloc, root)
            _maybe_demote_mesh(node, ctx, conf, decisions, lore_alloc,
                               root)
        for c in list(node.children):
            visit(c)
        if isinstance(node, SpmdStageExec):
            # mesh analog of partition coalescing: exact staged bytes
            # shrink the active mesh axis for small stages (the
            # decision logic lives on the stage, which owns the stats)
            from ..profiler import tracing
            with tracing.span("aqe.reshard", "aqe", ctx):
                d = node.plan_reshard(ctx, conf)
            if d is not None:
                decisions.append(d)
                if not getattr(node, "_reshard_counted", False):
                    node._reshard_counted = True
                    _bump("mesh_reshards")
        if isinstance(node, AQEShuffleReadExec):
            # stage barrier: materialize (exchange pool) + replan
            from ..profiler import tracing
            with tracing.span("aqe.stage_materialize", "aqe", ctx,
                              lore_id=getattr(node, "lore_id", None)):
                node.plan.groups(ctx)
            d = node.plan.decision
            if d is not None and id(node.plan) not in seen_plans:
                seen_plans.add(id(node.plan))
                decisions.append(d)
                if not getattr(node.plan, "_stats_counted", False):
                    node.plan._stats_counted = True
                    _bump("coalesced_partitions",
                          int(d.get("coalesced_away", 0)))
                    _bump("skew_splits", int(d.get("split_slices", 0)))

    visit(root)
    return decisions


def _maybe_demote(join, ctx, conf, decisions, lore_alloc, root) -> None:
    """Shuffled-hash-join → broadcast-join demotion at the build-side
    stage boundary (reference: Spark's DemoteBroadcastHashJoin /
    OptimizeLocalShuffleReader family, inverted: we PROMOTE to
    broadcast when runtime stats beat the estimate). The build
    exchange's materialized blocks become the broadcast child; the
    stream side drops its exchange entirely and reads the pre-shuffle
    subtree, so the stream map phase is skipped."""
    from ..config import ADAPTIVE_DEMOTE_ENABLED, BROADCAST_THRESHOLD
    prev = getattr(join, "_aqe_demoted", None)
    if prev is not None:
        decisions.append(prev)
        return
    thr = conf.get(BROADCAST_THRESHOLD)
    if not (conf.get(ADAPTIVE_DEMOTE_ENABLED) and thr >= 0
            and join.per_partition):
        return
    from ..exec.aqe import AQEShuffleReadExec
    from ..exec.exchange import ShuffleExchangeExec
    stream, build = join.children
    if not isinstance(stream, AQEShuffleReadExec) \
            or not isinstance(build, AQEShuffleReadExec):
        return
    sex = stream.children[0]
    # only a plain, not-yet-materialized stream exchange can be
    # skipped: a ReusedExchange has no children to unwrap (the shared
    # subtree belongs to its first occurrence), and a map phase that
    # already ran has nothing left to save
    if not isinstance(sex, ShuffleExchangeExec) or not sex.children \
            or sex._shuffle is not None:
        return
    bex = build.children[0]        # ShuffleExchangeExec or ReusedExchange
    if not hasattr(bex, "stage_stats"):
        return
    ctx.check_cancel()
    # stage barrier: the build map phase materializes NOW (under the
    # exchange's own lock, via the exchange pool) and reports exact
    # serialized bytes — the runtime stat the planning estimate missed
    from ..profiler import tracing
    with tracing.span("aqe.demote_build_materialize", "aqe", ctx):
        build_bytes = int(sum(bex.stage_stats(ctx)))
    if build_bytes > thr:
        return
    from ..exec.broadcast import BroadcastExchangeExec
    bcast = BroadcastExchangeExec(bex, bex.schema)
    if not lore_alloc[0]:
        lore_alloc[0] = _max_lore_id(root)
    lore_alloc[0] += 1
    bcast.lore_id = lore_alloc[0]
    old_lores = [getattr(n, "lore_id", None) for n in (stream, sex, build)]
    join.children = [sex.children[0], bcast]
    join.per_partition = False
    d = {"rule": "demote_broadcast_join",
         "join_lore": getattr(join, "lore_id", None),
         "old_lores": old_lores, "new_lores": [bcast.lore_id],
         "build_bytes": build_bytes, "threshold": int(thr)}
    join._aqe_demoted = d
    ctx.metrics_for(join._op_id).set("aqeDemotedBuildBytes", build_bytes)
    decisions.append(d)
    _bump("demotions")


def _maybe_demote_mesh(join, ctx, conf, decisions, lore_alloc,
                       root) -> None:
    """The mesh-path twin of `_maybe_demote`: a shuffled hash join whose
    inputs are bare SpmdStageExec exchange stages. The build stage is
    materialized to its STAGED handles only (map side runs, collective
    does not); when the exact staged bytes fit the broadcast threshold,
    the build side broadcasts straight from those handles and the
    stream side drops its stage entirely — NEITHER side's collective
    program runs."""
    from ..config import ADAPTIVE_DEMOTE_ENABLED, BROADCAST_THRESHOLD
    prev = getattr(join, "_aqe_mesh_demoted", None)
    if prev is not None:
        decisions.append(prev)
        return
    thr = conf.get(BROADCAST_THRESHOLD)
    if not (conf.get(ADAPTIVE_DEMOTE_ENABLED) and thr >= 0
            and join.per_partition):
        return
    from ..exec.spmd_stage import SpmdStageExec
    stream, build = join.children
    if not (isinstance(stream, SpmdStageExec)
            and isinstance(build, SpmdStageExec)
            and stream.kind == "exchange" and build.kind == "exchange"):
        return
    # only a cold stream stage can be skipped: once staged or degraded,
    # its map phase already ran and there is nothing left to save
    if stream._staged is not None or stream._degraded \
            or build._degraded or not stream.children:
        return
    ctx.check_cancel()
    # stage barrier: the build map phase drains into spill handles NOW
    # and reports exact device bytes (the mesh MapOutputStatistics)
    from ..profiler import tracing
    with tracing.span("aqe.demote_mesh_materialize", "aqe", ctx):
        build_bytes = int(build.stage_bytes(ctx))
    if build_bytes > thr:
        return
    from ..exec.broadcast import BroadcastExchangeExec
    from ..exec.lockstep import MeshGatherExec
    # the staged batches and the stream's map side lie on their shards'
    # devices; a broadcast join runs on one
    src = MeshGatherExec(build.staged_source(own=True))
    bcast = BroadcastExchangeExec(src, src.schema)
    if not lore_alloc[0]:
        lore_alloc[0] = _max_lore_id(root)
    lore_alloc[0] += 1
    bcast.lore_id = lore_alloc[0]
    old_lores = [getattr(n, "lore_id", None) for n in (stream, build)]
    join.children = [MeshGatherExec(stream.children[0]), bcast]
    join.per_partition = False
    d = {"rule": "demote_broadcast_join", "mesh": True,
         "join_lore": getattr(join, "lore_id", None),
         "old_lores": old_lores, "new_lores": [bcast.lore_id],
         "build_bytes": build_bytes, "threshold": int(thr)}
    join._aqe_mesh_demoted = d
    ctx.metrics_for(join._op_id).set("aqeDemotedBuildBytes", build_bytes)
    decisions.append(d)
    _bump("mesh_demotions")
