"""Planner: logical plan -> TPU physical plan with tagging + explain.

The GpuOverrides analog (reference: GpuOverrides.scala:5017-5191 apply path;
RapidsMeta.scala:87 tagging). Flow: wrap each logical node in a PlanMeta,
tag it (record `willNotWorkOnTpu` reasons), then convert — per-node
replacement rules live in `_RULES`, keyed by logical node class, mirroring
the reference's `execs` map (GpuOverrides.scala:4801).

Round-1 fallback policy: a node whose expressions cannot run on TPU raises
at conversion with the collected reasons (transparent CPU fallback execs
arrive with the host expression interpreter).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from ..config import TpuConf, EXPLAIN
from ..exec import aggregate as agg_exec
from ..exec import nodes as x
from ..exec.base import TpuExec
from ..expr.expressions import UnsupportedExpr
from . import logical as L

__all__ = ["Planner", "PlanMeta", "plan_query"]


class PlanMeta:
    """Wrapper recording per-node TPU support (RapidsMeta analog).

    Three states per node: runs on TPU (*), runs on the HOST CPU via the
    fallback interpreter (!cpu, query still succeeds), or cannot run at
    all (!, query fails at convert)."""

    def __init__(self, node: L.LogicalPlan):
        self.node = node
        self.children = [PlanMeta(c) for c in node.children]
        self.reasons: List[str] = []
        self.host_reasons: List[str] = []
        # the physical subtree this node converted to (set by _convert);
        # its lore_id surfaces in explain so a hot operator in a profile
        # report maps directly to a lore.idsToDump replay id
        self.exec_node: Optional[TpuExec] = None

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    def will_use_host(self, reason: str):
        self.host_reasons.append(reason)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    def explain_lines(self, only_not_on_tpu: bool, indent=0) -> List[str]:
        lines = []
        tag = ("!cpu" if self.host_reasons and not self.reasons
               else "*" if self.can_run_on_tpu else "!")
        lore = getattr(self.exec_node, "lore_id", None)
        lore_tag = f" [loreId={lore}]" if lore is not None else ""
        desc = f"{'  ' * indent}{tag}{lore_tag} {self.node.describe()}"
        if self.reasons:
            desc += "  <-- cannot run on TPU because " + "; ".join(
                self.reasons)
        elif self.host_reasons:
            desc += ("  <-- will run on CPU because "
                     + "; ".join(self.host_reasons))
        if not only_not_on_tpu or self.reasons or self.host_reasons:
            lines.append(desc)
        for c in self.children:
            lines.extend(c.explain_lines(only_not_on_tpu, indent + 1))
        return lines


_RULES: Dict[Type[L.LogicalPlan], Callable] = {}


def _rule(cls):
    def deco(fn):
        _RULES[cls] = fn
        return fn
    return deco


@_rule(L.InMemoryScan)
def _scan(meta: PlanMeta, conv, conf) -> TpuExec:
    return x.InMemoryScanExec(meta.node.arrow, meta.node.schema)


@_rule(L.CachedScan)
def _cached(meta, conv, conf):
    from ..exec.nodes import CachedScanExec
    return CachedScanExec(meta.node.batches, meta.node.schema,
                          meta.node.columns_cached, meta.node.n_shards)


@_rule(L.ParquetScan)
def _pq(meta, conv, conf):
    from ..config import BATCH_SIZE_ROWS
    from ..exec.coalesce import CoalesceBatchesExec
    n = meta.node
    scan = x.ParquetScanExec(n.paths, n.schema, n.columns,
                             filters=n.filters,
                             dv=getattr(n, "dv", None),
                             snapshot=getattr(n, "snapshot", None),
                             delta_version=getattr(n, "delta_version",
                                                   None))
    if len(n.paths) > 1:
        # many-small-files: coalesce toward the batch target
        # (GpuCoalesceBatches after scans, GpuTransitionOverrides.scala:77);
        # fan-in sized from the first file's row count (footer metadata)
        import pyarrow.parquet as pq
        try:
            counts = [pq.ParquetFile(p).metadata.num_rows
                      for p in n.paths]
            avg = sum(counts) // max(len(counts), 1)
        except Exception:
            avg = 0
        target = conf.get(BATCH_SIZE_ROWS)
        if 0 < avg < target // 2:
            fan_in = min(max(1, target // max(avg, 1)), len(n.paths))
            return CoalesceBatchesExec(scan, target, fan_in)
    return scan


@_rule(L.TextScan)
def _textscan(meta, conv, conf):
    from ..exec.text_scan import (AvroScanExec, CsvScanExec,
                                  JsonScanExec, OrcScanExec)
    n = meta.node
    cls = {"csv": CsvScanExec, "json": JsonScanExec,
           "orc": OrcScanExec, "avro": AvroScanExec}[n.fmt]
    return cls(n.paths, n._full_schema, n.columns, n.options)


@_rule(L.Project)
def _project(meta, conv, conf):
    child = conv(meta.children[0])
    n = meta.node
    if any(b is None for b in n.bound) or meta.host_reasons:
        # _tag already copied bind errors into host_reasons; dedupe
        reason = "; ".join(dict.fromkeys(
            [e for e in n.bind_errors if e] + meta.host_reasons))
        if not conf.allow_cpu_fallback:
            raise UnsupportedExpr(reason)
        from ..exec.host_fallback import HostProjectExec
        return HostProjectExec(child, n.exprs, n.schema, reason)
    return x.ProjectExec(child, n.bound, n.schema)


@_rule(L.Filter)
def _filter(meta, conv, conf):
    child = conv(meta.children[0])
    n = meta.node
    if n.bound is None or meta.host_reasons:
        reason = "; ".join(dict.fromkeys(
            ([n.bind_error] if n.bind_error else [])
            + meta.host_reasons))
        if not conf.allow_cpu_fallback:
            raise UnsupportedExpr(reason)
        from ..exec.host_fallback import HostFilterExec
        return HostFilterExec(child, n.condition, reason)
    return x.FilterExec(child, n.bound)


def _aqe_wrap(exchange, conf, allow_split=False, plan=None,
              role="stream"):
    """Wrap a file-shuffle exchange with an adaptive reader when enabled
    (GpuCustomShuffleReaderExec analog). Mesh exchanges re-plan at trace
    time instead, so they pass through."""
    from ..config import (ADAPTIVE_COALESCE_ENABLED, ADAPTIVE_ENABLED,
                          ADAPTIVE_SKEW_ENABLED, ADAPTIVE_SKEW_FACTOR,
                          ADAPTIVE_SKEW_MIN_BYTES, ADAPTIVE_TARGET_BYTES)
    from ..exec.exchange import ShuffleExchangeExec
    if not conf.get(ADAPTIVE_ENABLED) or \
            not isinstance(exchange, ShuffleExchangeExec):
        return exchange, None
    from ..exec.aqe import AqeShufflePlan, AQEShuffleReadExec
    if plan is None:
        plan = AqeShufflePlan([exchange],
                              conf.get(ADAPTIVE_TARGET_BYTES),
                              conf.get(ADAPTIVE_SKEW_FACTOR),
                              conf.get(ADAPTIVE_SKEW_MIN_BYTES),
                              allow_split
                              and conf.get(ADAPTIVE_SKEW_ENABLED),
                              allow_coalesce=conf.get(
                                  ADAPTIVE_COALESCE_ENABLED))
    else:
        plan.exchanges.append(exchange)
    return AQEShuffleReadExec(exchange, plan, role), plan


def _make_hash_exchange(child, bound_keys, conf):
    """Choose the exchange transport: mesh collective (all_to_all over
    ICI when spark.rapids.tpu.mesh.devices > 0) or the host file shuffle
    (the reference's UCX vs MULTITHREADED mode split,
    RapidsConf.scala:2216-2230)."""
    from ..config import MESH_DEVICES, SHUFFLE_PARTITIONS
    mesh_n = conf.get(MESH_DEVICES)
    if mesh_n and mesh_n > 1:
        from ..exec.mesh_exchange import MeshExchangeExec
        return MeshExchangeExec(child, mesh_n, bound_keys, child.schema)
    from ..exec.exchange import ShuffleExchangeExec
    return ShuffleExchangeExec(child, conf.get(SHUFFLE_PARTITIONS),
                               bound_keys, child.schema)


@_rule(L.Expand)
def _expand(meta, conv, conf):
    from ..exec.expand import ExpandExec
    n = meta.node
    return ExpandExec(conv(meta.children[0]), n.bound_keys,
                      n.include_masks, n.schema)


@_rule(L.Aggregate)
def _agg(meta, conv, conf):
    from ..config import MESH_DEVICES, SHUFFLE_PARTITIONS
    child = conv(meta.children[0])
    n = meta.node
    names = [nm for nm, _ in n.bound_aggs]
    aggs = [a for _, a in n.bound_aggs]
    for k in n.bound_keys:
        if k.dtype.is_nested:
            raise UnsupportedExpr(
                f"group-by key {k!r} has nested type {k.dtype}")
    has_collect = any(getattr(a, "is_collect", False) for a in aggs)
    if not n.keys:
        if has_collect:
            # ungrouped sort-path aggregates (count distinct, median,
            # collect_*): single-segment CollectAggExec
            return agg_exec.CollectAggExec(child, [], [], names, aggs,
                                           n.schema)
        return agg_exec.UngroupedAggExec(child, names, aggs, n.schema)
    key_names = [k.name for k in n.keys]
    if has_collect:
        # variable-width results can't ride the partial/final flat-state
        # wire: hash-exchange the raw rows on the grouping keys, then each
        # partition's sort-collect is final (disjoint keys)
        from ..exec.base import ExecContext as _Ctx
        nparts_c = conf.get(SHUFFLE_PARTITIONS)
        if child.num_partitions(_Ctx(conf, planning=True)) > 1 \
                and nparts_c > 1:
            exch = _make_hash_exchange(child, n.bound_keys, conf)
            exch, _ = _aqe_wrap(exch, conf, allow_split=False)
            return agg_exec.CollectAggExec(exch, key_names, n.bound_keys,
                                           names, aggs, n.schema,
                                           per_partition=True)
        return agg_exec.CollectAggExec(child, key_names, n.bound_keys,
                                       names, aggs, n.schema)
    # distributed topology: PARTIAL agg per input partition (rows shrink
    # to group count), exchange the partial states on the grouping keys,
    # FINAL merge per output partition (reference: partial/final
    # GpuHashAggregateExec around GpuShuffleExchangeExec)
    from ..exec.base import ExecContext
    nparts = conf.get(SHUFFLE_PARTITIONS)
    mesh_n = conf.get(MESH_DEVICES)
    multi_input = child.num_partitions(
        ExecContext(conf, planning=True)) > 1
    # Small HBM-cached input on a single host: complete mode can take
    # the one-round-trip whole-input program; at scale the
    # partial/exchange/final topology pipelines better
    base = child
    while len(base.children) == 1:
        base = base.children[0]
    from ..exec.nodes import CachedScanExec
    if isinstance(base, CachedScanExec) and mesh_n <= 1:
        total = sum(b.capacity for b in base.batches)
        if total <= (1 << 21):
            multi_input = False
    keys_ok = all(not (k.dtype.is_nested) for k in n.bound_keys)
    if keys_ok and ((multi_input and nparts > 1) or mesh_n > 1):
        from ..expr.expressions import BoundRef
        partial = agg_exec.HashAggregateExec(
            child, key_names, n.bound_keys, names, aggs, child.schema,
            mode="partial")
        pkeys = [BoundRef(i, k.dtype, f.name)
                 for i, (k, f) in enumerate(
                     zip(n.bound_keys, partial.schema.fields))]
        exch = _make_hash_exchange(partial, pkeys, conf)
        # adaptive coalescing of small reduce partitions (splitting would
        # break group completeness, so allow_split=False)
        exch, _ = _aqe_wrap(exch, conf, allow_split=False)
        return agg_exec.HashAggregateExec(exch, key_names, pkeys,
                                          names, aggs, n.schema,
                                          mode="final")
    return agg_exec.HashAggregateExec(child, key_names, n.bound_keys,
                                      names, aggs, n.schema)


@_rule(L.Limit)
def _limit(meta, conv, conf):
    return x.LimitExec(conv(meta.children[0]), meta.node.n)


@_rule(L.Union)
def _union(meta, conv, conf):
    return x.UnionExec([conv(c) for c in meta.children], meta.node.schema)


@_rule(L.Sort)
def _sort(meta, conv, conf):
    from ..exec.sort import SortExec
    for o in meta.node.bound_orders:
        if o.expr.dtype.is_nested:
            raise UnsupportedExpr(
                f"sort key {o.expr!r} has nested type "
                f"{o.expr.dtype} (not orderable on TPU)")
    return SortExec(conv(meta.children[0]), meta.node.bound_orders,
                    meta.node.schema)


def _estimate_rows(node: L.LogicalPlan):
    """Best-effort row estimate from scan metadata (the planner's
    broadcast-decision input; reference: size estimates feeding
    useSizedJoin / autoBroadcastJoinThreshold)."""
    if isinstance(node, L.InMemoryScan):
        return node.arrow.num_rows
    if isinstance(node, L.CachedScan):
        return sum(b.num_rows for b in node.batches)
    if isinstance(node, L.ParquetScan):
        cached = getattr(node, "_est_rows_cache", False)
        if cached is not False:
            return cached
        import pyarrow.parquet as pq
        try:
            rows = sum(pq.ParquetFile(p).metadata.num_rows
                       for p in node.paths)
        except Exception:
            rows = None
        node._est_rows_cache = rows
        return rows
    if isinstance(node, (L.Project, L.Filter, L.Sort, L.Repartition,
                         L.WindowOp)):
        # filters keep the upper bound (a conservative broadcast choice)
        return _estimate_rows(node.children[0])
    if isinstance(node, L.Limit):
        child = _estimate_rows(node.children[0])
        return node.n if child is None else min(node.n, child)
    if isinstance(node, L.Union):
        parts = [_estimate_rows(c) for c in node.children]
        return None if any(p is None for p in parts) else sum(parts)
    if isinstance(node, L.Aggregate):
        return _estimate_rows(node.children[0])
    return None


def _row_width_bytes(schema) -> int:
    w = 1  # validity
    for f in schema.fields:
        if f.dtype.is_variable_width:
            w += 24
        elif getattr(f.dtype, "is_decimal128", False):
            w += 16
        else:
            w += (f.dtype.np_dtype.itemsize if f.dtype.np_dtype else 8)
    return w


def _estimate_bytes(node: L.LogicalPlan):
    rows = _estimate_rows(node)
    if rows is None:
        return None
    return rows * _row_width_bytes(node.schema)


@_rule(L.Join)
def _join(meta, conv, conf):
    from ..config import BROADCAST_THRESHOLD, MESH_DEVICES, \
        SHUFFLE_PARTITIONS
    from ..exec.join import HashJoinExec
    n = meta.node
    for k in list(n.bound_left_keys or []) + list(n.bound_right_keys or []):
        if k.dtype.is_nested:
            raise UnsupportedExpr(
                f"join key {k!r} has nested type {k.dtype}")
    left, right = conv(meta.children[0]), conv(meta.children[1])
    mesh_n = conf.get(MESH_DEVICES)
    thr = conf.get(BROADCAST_THRESHOLD)
    est = _estimate_bytes(meta.children[1].node)
    broadcast_ok = thr >= 0 and est is not None and est <= thr
    equi = (n.how != "cross" and n.bound_left_keys
            and all(lk.dtype == rk.dtype for lk, rk in
                    zip(n.bound_left_keys, n.bound_right_keys)))
    cond = n.bound_condition
    if not equi and cond is not None:
        if n.bound_left_keys:
            # equi keys exist but are unusable (dtype mismatch): refusing
            # beats silently joining on the residual condition alone
            raise UnsupportedExpr(
                "equi-join keys have mismatched types "
                f"{[(lk.dtype, rk.dtype) for lk, rk in zip(n.bound_left_keys, n.bound_right_keys)]}; "
                "cast both sides to a common type")
        # no equi keys: broadcast nested-loop join on the condition
        # (GpuBroadcastNestedLoopJoinExecBase analog)
        from ..exec.join import NestedLoopJoinExec
        how = "inner" if n.how == "cross" else n.how
        return NestedLoopJoinExec(left, right, how, n.schema, cond)
    if mesh_n > 1 and equi and not broadcast_ok:
        # big build: hash-exchange both sides on the join keys over the
        # mesh, then each shard joins its co-partitioned slice
        # (GpuShuffledSizedHashJoinExec spirit over the collective)
        from ..exec.mesh_exchange import MeshExchangeExec
        lex = MeshExchangeExec(left, mesh_n, n.bound_left_keys,
                               left.schema)
        rex = MeshExchangeExec(right, mesh_n, n.bound_right_keys,
                               right.schema)
        return HashJoinExec(lex, rex, n.bound_left_keys,
                            n.bound_right_keys, n.how, n.schema,
                            per_partition=True, condition=cond)
    if mesh_n <= 1 and equi and not broadcast_ok and est is not None:
        # single-host big-build join: file-shuffle both sides so each
        # partition's build slice is bounded (sized-join analog)
        from ..exec.exchange import ShuffleExchangeExec
        nparts = conf.get(SHUFFLE_PARTITIONS)
        if nparts > 1:
            left, right = _maybe_bloom_prefilter(left, right, n, meta,
                                                 conf)
            lex = ShuffleExchangeExec(left, nparts, n.bound_left_keys,
                                      left.schema)
            rex = ShuffleExchangeExec(right, nparts, n.bound_right_keys,
                                      right.schema)
            # adaptive skew join: split oversized stream partitions into
            # row slices; the build reader replays the full partition per
            # slice. Splitting is only sound for joins where every output
            # row of a partition depends on (stream row, full build) —
            # right/full outer track matched-build state across the whole
            # partition, so those keep whole partitions.
            allow_split = n.how in ("inner", "left", "left_semi",
                                    "left_anti")
            lread, plan = _aqe_wrap(lex, conf, allow_split=allow_split)
            rread, _ = _aqe_wrap(rex, conf, plan=plan, role="build")
            return HashJoinExec(lread, rread, n.bound_left_keys,
                                n.bound_right_keys, n.how, n.schema,
                                per_partition=True, condition=cond)
    # broadcast hash join: build side collected once behind a
    # BroadcastExchangeExec (async background build + reuse-pass
    # dedupe target), stream partitions probe it
    # (GpuBroadcastHashJoinExecBase analog)
    from ..exec.broadcast import BroadcastExchangeExec
    return HashJoinExec(left,
                        BroadcastExchangeExec(right, right.schema),
                        n.bound_left_keys, n.bound_right_keys, n.how,
                        n.schema, condition=cond)


def _maybe_bloom_prefilter(left, right, n, meta, conf):
    """Wrap the stream (left) side of a shuffled equi-join in a runtime
    bloom filter built from the join's OWN build side, so non-matching
    rows never reach the exchange (reference: GpuBloomFilter* runtime
    filters via InSubqueryExec). The build subtree is wrapped in
    SharedBuildExec so the filter and the join's build exchange consume
    ONE materialization — no double scan, and no scan-shape
    restriction. Only for join types where an unmatched stream row
    contributes nothing. Returns (left', right')."""
    from ..config import (JOIN_BLOOM_ENABLED, JOIN_BLOOM_MAX_BUILD_ROWS)
    if not conf.get(JOIN_BLOOM_ENABLED):
        return left, right
    if n.how not in ("inner", "left_semi", "right"):
        return left, right
    if len(n.bound_left_keys or []) != 1:
        return left, right               # single-key filters only
    if n.bound_left_keys[0].dtype != n.bound_right_keys[0].dtype:
        # murmur3 hashes int32/int64 representations of equal values
        # differently: a mixed-width equi-join through the bloom filter
        # would silently drop matching stream rows
        return left, right
    from ..exec.runtime_filter import (RuntimeBloomFilterExec,
                                       SharedBuildExec)
    max_rows = conf.get(JOIN_BLOOM_MAX_BUILD_ROWS)
    est_rows = _estimate_rows(meta.children[1].node)
    if est_rows is None or est_rows > max_rows:
        # no estimate (unknown-cardinality shapes): a filter sized
        # blind can saturate (FPR ~1) and charge k probes per stream
        # row for zero pruning — skip. Aggregates/filters/scans DO
        # estimate (upper bounds), so non-scan builds stay eligible.
        return left, right
    shared = SharedBuildExec(right)
    return RuntimeBloomFilterExec(left, shared, n.bound_left_keys[0],
                                  n.bound_right_keys[0],
                                  max(64, int(est_rows))), shared


@_rule(L.WindowOp)
def _window(meta, conv, conf):
    """Stage window expressions: one WindowExec per distinct
    (partition, order) spec, chained — each appends its columns; a final
    projection restores the requested column order (the reference splits
    the same way, GpuWindowExecMeta.scala:182)."""
    from ..columnar.table import Field, Schema
    from ..exec.window import WindowExec, spec_signature
    n = meta.node
    groups = {}
    for nm, w in n.bound:
        groups.setdefault(spec_signature(w.spec), []).append((nm, w))
    child = conv(meta.children[0])
    if len(groups) == 1:
        return WindowExec(child, [nm for nm, _ in n.bound],
                          [w for _, w in n.bound], n.schema)
    cur = child
    cur_fields = list(meta.children[0].node.schema.fields)
    nchild = len(cur_fields)
    appended = {}
    for cols in groups.values():
        out_fields = cur_fields + [Field(nm, w.dtype) for nm, w in cols]
        for j, (nm, _) in enumerate(cols):
            appended[nm] = len(cur_fields) + j
        cur = WindowExec(cur, [nm for nm, _ in cols],
                         [w for _, w in cols], Schema(out_fields))
        cur_fields = out_fields
    # reorder appended columns back to request order
    from ..exec.nodes import ProjectExec
    from ..expr.expressions import BoundRef
    refs = ([BoundRef(i, f.dtype, f.name)
             for i, f in enumerate(n.schema.fields[:nchild])]
            + [BoundRef(appended[f.name], f.dtype, f.name)
               for f in n.schema.fields[nchild:]])
    return ProjectExec(cur, refs, n.schema)


@_rule(L.Generate)
def _generate(meta, conv, conf):
    from ..exec.generate import GenerateExec
    n = meta.node
    return GenerateExec(conv(meta.children[0]), n.bound, n.schema)


@_rule(L.MapInPandas)
def _map_in_pandas(meta, conv, conf):
    from ..exec.python_exec import ArrowEvalPythonExec
    n = meta.node
    return ArrowEvalPythonExec(conv(meta.children[0]), n.fn, n.schema)


@_rule(L.GroupedMapInPandas)
def _grouped_map_in_pandas(meta, conv, conf):
    from ..config import SHUFFLE_PARTITIONS
    from ..exec.exchange import ShuffleExchangeExec
    from ..exec.python_exec import GroupedMapPythonExec
    from ..expr.expressions import col as _col
    n = meta.node
    child = conv(meta.children[0])
    nparts = max(1, conf.get(SHUFFLE_PARTITIONS))
    keys = [_col(k).bind(n.children[0].schema) for k in n.key_names]
    # ALWAYS exchange: even at nparts=1 a multi-partition child must
    # gather so a key spanning source partitions stays one group
    child = ShuffleExchangeExec(child, nparts, keys, child.schema)
    return GroupedMapPythonExec(child, n.fn, n.schema, n.key_names)


@_rule(L.CoGroupInPandas)
def _cogroup_in_pandas(meta, conv, conf):
    from ..config import SHUFFLE_PARTITIONS
    from ..exec.exchange import ShuffleExchangeExec
    from ..exec.python_exec import CoGroupPythonExec
    from ..expr.expressions import col as _col
    n = meta.node
    left = conv(meta.children[0])
    right = conv(meta.children[1])
    nparts = max(1, conf.get(SHUFFLE_PARTITIONS))
    # ALWAYS exchange (even nparts=1): aligns partition counts across
    # the two sides and gathers split groups
    lkeys = [_col(k).bind(n.children[0].schema) for k in n.lkeys]
    rkeys = [_col(k).bind(n.children[1].schema) for k in n.rkeys]
    left = ShuffleExchangeExec(left, nparts, lkeys, left.schema)
    right = ShuffleExchangeExec(right, nparts, rkeys, right.schema)
    return CoGroupPythonExec(left, right, n.fn, n.schema)


@_rule(L.Repartition)
def _repart(meta, conv, conf):
    from ..config import MESH_DEVICES
    n = meta.node
    child = conv(meta.children[0])
    # the mesh collective produces exactly mesh-many partitions; honor an
    # explicit different repartition count via the file shuffle instead
    if n.bound_keys and conf.get(MESH_DEVICES) == n.num_partitions \
            and n.num_partitions > 1:
        from ..exec.mesh_exchange import MeshExchangeExec
        return MeshExchangeExec(child, conf.get(MESH_DEVICES),
                                n.bound_keys, n.schema)
    from ..exec.exchange import ShuffleExchangeExec
    return ShuffleExchangeExec(child, n.num_partitions, n.bound_keys,
                               n.schema)


class Planner:
    def __init__(self, conf: Optional[TpuConf] = None):
        self.conf = conf or TpuConf()

    # explain lines of the most recent plan() call (set whenever the
    # explain mode requests them; DataFrame.explain returns them)
    last_explain: List[str] = []
    # AuditReport of the most recent plan() call (analysis/audit.py)
    last_audit = None

    def plan(self, root: L.LogicalPlan) -> TpuExec:
        # calibration lookups (observed cardinalities from earlier runs
        # in this session) are live for the whole planning pass —
        # optimizer join-reorder included — and only there, so a
        # session with AQE off plans as if the table did not exist
        from ..config import ADAPTIVE_CALIBRATION, ADAPTIVE_ENABLED
        from .stats import calibration_scope
        with calibration_scope(self.conf.get(ADAPTIVE_ENABLED)
                               and self.conf.get(ADAPTIVE_CALIBRATION)):
            return self._plan_scoped(root)

    def _plan_scoped(self, root: L.LogicalPlan) -> TpuExec:
        from .optimizer import optimize
        root = optimize(root, self.conf)
        meta = PlanMeta(root)
        self._tag(meta)
        from ..config import CBO_ENABLED
        if self.conf.get(CBO_ENABLED):
            from .cbo import apply_cbo
            apply_cbo(meta, self.conf)
        explain_mode = self.conf.explain
        # convert BEFORE printing explain: lore ids live on the physical
        # nodes, and explain surfaces them ([loreId=N]) so profile-report
        # sinks map straight to lore.idsToDump replay ids. A conversion
        # failure still prints the tagged tree first, then re-raises.
        root_exec, conv_err = None, None
        try:
            root_exec = self._convert(meta)
        except UnsupportedExpr as e:
            conv_err = e
        from ..utils.lore import apply_lore_dump, assign_lore_ids
        if root_exec is not None:
            assign_lore_ids(root_exec)
        # static plan audit: a pure tree walk predicting fallback /
        # will-not-work / recompile-risk per node BEFORE any execution
        # (analysis/audit.py; the NOT_ON_TPU tagging discipline)
        from ..analysis.audit import audit_plan
        report = audit_plan(meta, self.conf)
        self.last_audit = report
        if root_exec is not None:
            # whole-stage fusion pass (plan/fusion.py): runs after the
            # audit because recompile_risk lore ids are fusion barriers,
            # and before explain so VALIDATE can render the groups
            from .fusion import fuse_stages
            root_exec, fusion_groups = fuse_stages(root_exec, self.conf,
                                                   report)
            report.fusion_groups = fusion_groups
            # exchange reuse (Spark's ReuseExchange analog): duplicate
            # exchange subtrees collapse to ReusedExchange nodes AFTER
            # fusion (fused chains are part of the subtree identity)
            from .reuse import reuse_exchanges
            root_exec, reuse_hits = reuse_exchanges(root_exec, self.conf)
            root_exec.exchange_reuse_hits = reuse_hits
            # fragment tier of the cross-query result cache: an
            # exchange subtree whose map output is already cached (from
            # a PREVIOUS query) becomes a CachedFragmentExec source —
            # cross-query what reuse_exchanges is intra-query
            from ..runtime import result_cache
            root_exec, frag_hits = result_cache.substitute_fragments(
                root_exec, self.conf)
            root_exec.result_cache_fragment_hits = frag_hits
            # SPMD stage grouping (plan/fusion.py): each surviving mesh
            # exchange fuses with its consumer into ONE shard_map
            # program — runs last so it sees the final tree (reused /
            # cache-substituted exchanges must not be double-wrapped)
            from .fusion import fuse_spmd_stages
            root_exec, spmd_groups = fuse_spmd_stages(root_exec,
                                                      self.conf)
            report.fusion_groups = fusion_groups + spmd_groups
            from .fusion import place_mesh_gathers
            root_exec = place_mesh_gathers(root_exec, self.conf)
            # ride the physical root so the profiler wrapper can emit
            # the plan_audit event without re-walking
            root_exec.audit_report = report
        self.last_explain = []
        if explain_mode in ("ALL", "NOT_ON_TPU", "VALIDATE"):
            if explain_mode == "VALIDATE":
                self.last_explain = report.lines()
            else:
                self.last_explain = meta.explain_lines(
                    explain_mode == "NOT_ON_TPU")
                self.last_explain.extend(
                    v.describe() for v in report.findings)
            for line in self.last_explain:
                print(line)
        if conv_err is not None:
            raise conv_err
        from ..config import AUDIT_STRICT
        if self.conf.get(AUDIT_STRICT):
            report.raise_if_blocked()
        return apply_lore_dump(root_exec, self.conf)

    def _tag(self, meta: PlanMeta):
        node = meta.node
        if type(node) not in _RULES:
            meta.will_not_work(
                f"no TPU replacement rule for {node.node_name()}")
        if isinstance(node, L.Filter) and node.bound is None:
            if self.conf.allow_cpu_fallback:
                meta.will_use_host(node.bind_error)
            else:
                meta.will_not_work(node.bind_error)
        if isinstance(node, L.Project) and any(b is None
                                               for b in node.bound):
            reason = "; ".join(e for e in node.bind_errors if e)
            if self.conf.allow_cpu_fallback:
                meta.will_use_host(reason)
            else:
                meta.will_not_work(reason)
        for c in meta.children:
            self._tag(c)

    def _convert(self, meta: PlanMeta) -> TpuExec:
        if not meta.can_run_on_tpu:
            raise UnsupportedExpr("; ".join(meta.reasons))
        rule = _RULES[type(meta.node)]
        try:
            meta.exec_node = rule(meta, self._convert, self.conf)
            # stamp calibration fingerprints (no-op outside an enabled
            # calibration scope) so post-run harvest can key observed
            # cardinalities without re-deriving the logical tree
            try:
                from .stats import attach_calibration_fps
                attach_calibration_fps(meta.node, meta.exec_node)
            except Exception:
                pass
            return meta.exec_node
        except ModuleNotFoundError as e:
            raise UnsupportedExpr(
                f"{meta.node.node_name()} not yet implemented on TPU "
                f"({e.name} missing)") from e


def plan_query(root: L.LogicalPlan,
               conf: Optional[TpuConf] = None) -> TpuExec:
    return Planner(conf).plan(root)
