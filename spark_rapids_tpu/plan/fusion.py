"""Plan-time whole-stage fusion pass.

Runs after optimizer/CBO rewrites, conversion, lore-id assignment and
the static audit: greedily groups maximal chains of fusible narrow
operators (TpuExec.fusable_stage is non-None) into FusedStageExec
(exec/fused.py) — one jitted program per stage instead of one per
operator.

Fusion barriers (a chain never crosses them):
  * any operator without a pure batch transform (exchanges, shuffles,
    scans, host fallbacks, python exec, aggregates, joins, sorts —
    their fusable_stage() is None);
  * CachedScanExec bases: fusing over the HBM batch cache would break
    the aggregates' cached whole-input fast path and make buffer
    donation unsafe, so cached chains are left to the consuming
    operators' own collapse;
  * nodes the static auditor flagged `recompile_risk` — fusing them
    would multiply every recompile across the whole stage program;
  * per-node opt-out: `node.fusion_opt_out = True`.

Operators that already collapse their child chain into their own
program (aggregate update, limit clip, sort collect, join probe
pre-stage) declare `fuses_child_chain = True`; the pass leaves exactly
the prefix they will consume unfused so the same work is not wrapped
twice.
"""
from __future__ import annotations

from typing import List, Tuple

from ..analysis.audit import RECOMPILE_RISK
from ..exec.base import TpuExec
from ..exec.fused import FusedStageExec
from ..exec.nodes import CachedScanExec

__all__ = ["fuse_stages", "fuse_spmd_stages"]


def _max_lore(root: TpuExec) -> int:
    best = [0]

    def walk(n):
        lid = getattr(n, "lore_id", None)
        if isinstance(lid, int):
            best[0] = max(best[0], abs(lid))
        for m in getattr(n, "members", []) or []:
            walk(m)
        for c in n.children:
            walk(c)

    walk(root)
    return best[0]


def fuse_stages(root: TpuExec, conf,
                report=None) -> Tuple[TpuExec, List[str]]:
    """Rewrite `root`, grouping fusable chains into FusedStageExec.
    Returns (new_root, group_lines) where group_lines describe each
    group for explain("VALIDATE") / the plan_audit event."""
    from ..config import STAGE_FUSION_ENABLED, STAGE_FUSION_MAX_OPS
    if not conf.get(STAGE_FUSION_ENABLED):
        return root, []
    max_ops = max(2, int(conf.get(STAGE_FUSION_MAX_OPS)))
    risky = set()
    if report is not None:
        risky = {v.lore_id for v in report.of_kind(RECOMPILE_RISK)
                 if v.lore_id is not None}

    groups: List[FusedStageExec] = []

    def fusable(n: TpuExec) -> bool:
        return (len(n.children) == 1
                and not isinstance(n, FusedStageExec)
                and n.fusable_stage() is not None
                and not getattr(n, "fusion_opt_out", False)
                and getattr(n, "lore_id", None) not in risky)

    def walk(node: TpuExec) -> TpuExec:
        chain, cur = [], node
        while len(chain) < max_ops and fusable(cur):
            chain.append(cur)
            cur = cur.children[0]
        if len(chain) >= 2 and not isinstance(cur, CachedScanExec):
            fused = FusedStageExec(chain, walk(cur))
            groups.append(fused)
            return fused
        recurse(node)
        return node

    def recurse(node: TpuExec) -> None:
        if getattr(node, "fuses_child_chain", False) and node.children:
            # skip the prefix the operator collapses itself
            # (collapse_fusable in exec/base.py) so it is not fused twice
            ro = getattr(node, "fusion_require_ordinals", False)
            parent, cur = node, node.children[0]
            while (cur.children
                   and cur.fusable_stage() is not None
                   and not (ro and not cur.preserves_ordinals())):
                parent, cur = cur, cur.children[0]
            parent.children[0] = walk(cur)
            for i in range(1, len(node.children)):
                node.children[i] = walk(node.children[i])
        else:
            node.children = [walk(c) for c in node.children]

    new_root = walk(root)
    next_id = _max_lore(new_root)
    lines = []
    for g in groups:
        next_id += 1
        g.lore_id = next_id
        lines.append(g.describe())
    return new_root, lines


def fuse_spmd_stages(root: TpuExec, conf) -> Tuple[TpuExec, List[str]]:
    """Flip the mesh exchange from operator boundary to sharding
    annotation: group each `MeshExchangeExec` with its fusible consumer
    into a `SpmdStageExec` that runs partition ids + all_to_all +
    consumer inside ONE shard_map program (exec/spmd_stage.py).

    Runs after `fuse_stages`/`reuse_exchanges`/result-cache
    substitution so it sees the final operator tree (a filter/project
    chain over the exchange may already be one FusedStageExec — its
    composed `fusable_stage()` fuses as a single chain member).

    Patterns, matched top-down:
      * final-mode HashAggregateExec directly over a MeshExchangeExec
        (the partial→exchange→final shape `_agg` plants) -> kind "agg";
        aggregates carrying "custom" host-side state reducers
        (t-digest) cannot trace inside shard_map and are skipped;
      * a single-child fusable chain ending at a MeshExchangeExec ->
        kind "chain";
      * any remaining MeshExchangeExec (shuffled-join inputs) -> a bare
        kind "exchange" stage: one single-round collective program plus
        the staged-byte stats hook AQE's mesh rules read.

    The round-based exchange is NOT removed — it stays inside the stage
    as the bounded-memory / fault-degradation fallback."""
    from ..config import MESH_COMPRESS, MESH_DEVICES, SPMD_STAGE_ENABLED
    mesh_n = conf.get(MESH_DEVICES)
    if not conf.get(SPMD_STAGE_ENABLED) or not mesh_n or mesh_n <= 1:
        return root, []
    if conf.get(MESH_COMPRESS):
        # byte-plane shuffle compression is a feature of the STAGED
        # round-based exchange; the fused program moves shards
        # in-program where packing has nothing to act on
        return root, []
    from ..exec.aggregate import HashAggregateExec
    from ..exec.mesh_exchange import MeshExchangeExec
    from ..exec.spmd_stage import SpmdStageExec

    stages: List[SpmdStageExec] = []

    def agg_traceable(agg: HashAggregateExec) -> bool:
        # "custom" reducers merge through a host-side callback
        # (g_merge_custom) — untraceable inside shard_map
        return not any("custom" in a.state_reducers for a in agg.aggs)

    def fusable(n: TpuExec) -> bool:
        return (len(n.children) == 1
                and n.fusable_stage() is not None
                and not getattr(n, "fusion_opt_out", False))

    def walk(node: TpuExec) -> TpuExec:
        if (isinstance(node, HashAggregateExec) and node.mode == "final"
                and len(node.children) == 1
                and isinstance(node.children[0], MeshExchangeExec)
                and agg_traceable(node)):
            ex = node.children[0]
            st = SpmdStageExec(ex, consumer=node, kind="agg")
            stages.append(st)
            _walk_into(st)
            return st
        chain, cur = [], node
        while fusable(cur):
            chain.append(cur)
            cur = cur.children[0]
        if chain and isinstance(cur, MeshExchangeExec):
            st = SpmdStageExec(cur, chain=chain, kind="chain")
            stages.append(st)
            _walk_into(st)
            return st
        if isinstance(node, MeshExchangeExec):
            st = SpmdStageExec(node, kind="exchange")
            stages.append(st)
            _walk_into(st)
            return st
        node.children = [walk(c) for c in node.children]
        return node

    def _walk_into(st: "SpmdStageExec") -> None:
        # recurse into the shared map subtree, keeping the fallback
        # exchange's child pointer in sync with the wrapped tree
        st.children = [walk(c) for c in st.children]
        st.exchange.children = list(st.children)

    new_root = walk(root)
    next_id = _max_lore(new_root)
    lines = []
    for st in stages:
        next_id += 1
        st.lore_id = next_id
        lines.append(st.describe())
    return new_root, lines


def place_mesh_gathers(root: TpuExec, conf) -> TpuExec:
    """In a mesh session a partition's batches live on its device (a
    sharded cached table, the output of a mesh exchange or stage). An
    operator that works partition by partition runs where its input is;
    one that takes every partition into a single-device program (sort,
    limit, a broadcast build, an ungrouped aggregate, a join that is not
    co-partitioned) gets a `MeshGatherExec` over each such child, which
    brings the batches to the default device. The root's collect exports
    from wherever the batches are."""
    from ..config import MESH_DEVICES
    mesh_n = conf.get(MESH_DEVICES)
    if not mesh_n or mesh_n <= 1:
        return root
    from ..exec.aggregate import HashAggregateExec
    from ..exec.fused import FusedStageExec
    from ..exec.join import HashJoinExec
    from ..exec.lockstep import MeshGatherExec
    from ..exec.mesh_exchange import MeshExchangeExec
    from ..exec.nodes import CachedScanExec, FilterExec, ProjectExec
    from ..exec.spmd_stage import SpmdStageExec

    def partitionwise(n: TpuExec) -> bool:
        return (isinstance(n, (FilterExec, ProjectExec, FusedStageExec))
                or (isinstance(n, HashJoinExec) and n.per_partition)
                or (isinstance(n, HashAggregateExec)
                    and n.mode in ("partial", "final")))

    def walk(n: TpuExec) -> bool:
        """Whether `n`'s output may lie off the default device; wraps
        the placed children of a node that needs them in one place."""
        if isinstance(n, (SpmdStageExec, MeshExchangeExec)):
            for c in n.children:
                walk(c)         # they take their input from any device
            return True
        if isinstance(n, CachedScanExec):
            return bool(n.n_shards)
        placed = [walk(c) for c in n.children]
        if partitionwise(n):
            return any(placed)
        n.children = [MeshGatherExec(c) if p else c
                      for c, p in zip(n.children, placed)]
        return False

    walk(root)
    return root
