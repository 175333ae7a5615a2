"""Logical plan nodes (the input to TpuOverrides planning).

The host "Catalyst" analog: since this framework is standalone (no Spark JVM
in-process for round 1), the DataFrame API builds these nodes directly; the
planner (plan/planner.py) then plays the role of GpuOverrides
(reference: GpuOverrides.scala:5017) — wrap, tag, convert to Tpu execs, and
insert transitions.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..columnar import dtypes as dt
from ..columnar.table import Schema, Field
from ..expr.expressions import Alias, Expression, ColumnRef
from ..expr import aggregates as agg
from .typesig import check_tree as _tsig

__all__ = ["LogicalPlan", "InMemoryScan", "CachedScan", "ParquetScan", "Project", "Filter", "Expand",
           "Aggregate", "Join", "Sort", "SortOrder", "Limit", "Union",
           "Repartition", "WindowOp", "Generate", "TextScan"]


class LogicalPlan:
    children: List["LogicalPlan"] = []

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def node_name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent=0) -> str:
        s = "  " * indent + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def describe(self) -> str:
        return self.node_name()


class InMemoryScan(LogicalPlan):
    """Scan over a host (pyarrow) table; batches stream host->HBM."""

    def __init__(self, arrow_table):
        self.arrow = arrow_table
        self.children = []
        self._schema = Schema.from_arrow(arrow_table.schema)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"InMemoryScan[rows={self.arrow.num_rows}] {self._schema}"


class CachedScan(LogicalPlan):
    """Scan over HBM-resident device batches — the analog of the
    reference's GpuInMemoryTableScanExec + ParquetCachedBatchSerializer
    (reference: ParquetCachedBatchSerializer.scala): df.cache() pins the
    columnar data on device so repeated queries skip host decode + H2D."""

    def __init__(self, batches, schema, columns_cached=None, table_id=None,
                 n_shards=0):
        self.batches = list(batches)
        self._schema = schema
        self.children = []
        # a mesh session's cache() divides the rows over n_shards devices:
        # `batches` is then shard after shard, each with the same number
        # of batches at the same capacities; 0 is one device, as ever
        self.n_shards = n_shards
        # width of the table df.cache() pinned; a pruned view reads fewer
        self.columns_cached = (len(schema.fields) if columns_cached is None
                               else columns_cached)
        # what a view shares with its leaf: the rows are the same table's
        # whichever columns a plan reads (plan/stats.py:_card_fp)
        self.table_id = object() if table_id is None else table_id
        # ordinals -> pruned view; a `_*_cache` name, so expr_fp skips it
        self._pruned_cache = {}
        # plan/stats.py's NDV memo, one for the table: the join reorder
        # asks the leaf (before pruning), the planner and AQE the view
        self._ndv_cache = {}

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CachedScan[{len(self.batches)} device batches] {self._schema}"

    def pruned(self, required) -> "CachedScan":
        """This scan over the columns named in `required` only, in the
        cached table's order; `self` where that is every column. The
        batches of a view are new DeviceBatch / Table shells around the
        SAME Column objects: no device operation, no copy, no HBM. One
        view per column set is kept on the leaf, so every fresh query
        tree over one cached DataFrame plans the identical node (and
        plan/stats.py's per-node memos survive re-planning); it pins
        nothing the leaf does not and dies with it."""
        fields = self._schema.fields
        keep = tuple(i for i, f in enumerate(fields) if f.name in required)
        if not keep:
            # count(*): one fixed-width column, the narrowest, so that
            # the batches still have a length
            fixed = [i for i, f in enumerate(fields)
                     if not (f.dtype.is_variable_width or f.dtype.is_nested)]
            if not fixed:
                return self
            keep = (min(fixed,
                        key=lambda i: fields[i].dtype.np_dtype.itemsize),)
        if len(keep) == len(fields):
            return self
        view = self._pruned_cache.get(keep)
        if view is None:
            from ..columnar.table import Table
            from ..exec.batch import DeviceBatch
            names = [fields[i].name for i in keep]
            # row_mask and capacity pass through: DeviceBatch's defaults
            # would launch an eager arange per batch
            view = CachedScan(
                [DeviceBatch(Table(names, [b.table.columns[i] for i in keep]),
                             b.num_rows, b.row_mask, b.capacity)
                 for b in self.batches],
                Schema([fields[i] for i in keep]),
                self.columns_cached, self.table_id, self.n_shards)
            view._ndv_cache = self._ndv_cache
            view = self._pruned_cache.setdefault(keep, view)
        return view


class ParquetScan(LogicalPlan):
    def __init__(self, paths: Sequence[str], schema: Optional[Schema] = None,
                 columns: Optional[Sequence[str]] = None, filters=None,
                 dv=None, delta_version=None):
        import pyarrow.parquet as pq
        self.paths = list(paths)
        self.columns = list(columns) if columns is not None else None
        # (name, op, value) conjuncts for row-group pruning, attached by
        # the optimizer from a Filter directly above the scan
        self.filters = list(filters) if filters else None
        # {path: (table_root, deletionVector descriptor)}: dead-row
        # masks applied lazily inside the scan (Delta DVs)
        self.dv = dict(dv) if dv else None
        # bind-time snapshot: (path, mtime_ns, size) per file, plus the
        # Delta table version when read through read_delta. An overwrite
        # between actions refreshes the plan (DataFrame._execute); one
        # mid-query raises (io/snapshot.py). Public attrs on purpose —
        # both flow into the structural plan fingerprint, which is how
        # a table write invalidates dependent result-cache entries.
        from ..io.snapshot import scan_snapshot
        self.snapshot = scan_snapshot(self.paths)
        self.delta_version = delta_version
        if schema is None:
            schema = Schema.from_arrow(pq.read_schema(self.paths[0]))
            if self.columns is not None:
                schema = Schema([f for f in schema.fields
                                 if f.name in self.columns])
        self._schema = schema
        self.children = []

    def refresh_snapshot(self) -> bool:
        """Re-stat the pinned files; True when anything changed."""
        from ..io.snapshot import scan_snapshot
        cur = scan_snapshot(self.paths)
        if cur != self.snapshot:
            self.snapshot = cur
            return True
        return False

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"ParquetScan[{len(self.paths)} files] {self._schema}"


class TextScan(LogicalPlan):
    """Lazy CSV / JSON-lines / ORC scan (reference: GpuCSVScan.scala:57,
    GpuJsonScan.scala, GpuOrcScan.scala:78). Schema comes from metadata or
    a first-block sample; decode happens per batch at execution."""

    def __init__(self, paths: Sequence[str], fmt: str,
                 schema: Optional[Schema] = None, columns=None,
                 options=None):
        from ..exec.text_scan import infer_text_schema
        from ..io.snapshot import scan_snapshot
        self.children = []
        self.paths = list(paths)
        self.fmt = fmt
        self.columns = list(columns) if columns else None
        self.options = options
        # bind-time file pinning, same contract as ParquetScan.snapshot
        self.snapshot = scan_snapshot(self.paths)
        if schema is not None and not isinstance(schema, Schema):
            schema = Schema.from_arrow(schema)   # accept pyarrow schemas
        self._full_schema = schema or infer_text_schema(
            self.paths[0], fmt, options)
        if self.columns is not None:
            want = set(self.columns)
            self._schema = Schema([f for f in self._full_schema.fields
                                   if f.name in want])
        else:
            self._schema = self._full_schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        cols = f", columns={self.columns}" if self.columns else ""
        return f"TextScan[{self.fmt}, {len(self.paths)} files{cols}]"


class Project(LogicalPlan):
    def __init__(self, child: LogicalPlan, exprs: Sequence[Expression]):
        from ..expr.expressions import UnsupportedExpr
        from ..expr.host_eval import host_output_dtype
        self.child = child
        self.children = [child]
        self.exprs = list(exprs)
        self.bound = []
        self.bind_errors: List[Optional[str]] = []
        fields = []
        for e in self.exprs:
            try:
                b = _tsig(e.bind(child.schema),
                          where=f"Project expr {e.name!r}")
                self.bound.append(b)
                self.bind_errors.append(None)
                fields.append(Field(e.name, b.dtype))
            except UnsupportedExpr as err:
                # TPU cannot run this expression; keep the unbound tree
                # for the host-fallback exec (GpuCpuBridge analog) when
                # the output dtype is still derivable
                hd = host_output_dtype(e)
                if hd is None:
                    raise
                self.bound.append(None)
                self.bind_errors.append(str(err))
                fields.append(Field(e.name, hd))
        self._schema = Schema(fields)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"Project[{', '.join(map(repr, self.exprs))}]"


class Filter(LogicalPlan):
    def __init__(self, child: LogicalPlan, condition: Expression):
        from ..expr.expressions import UnsupportedExpr
        self.child = child
        self.children = [child]
        self.condition = condition
        self.bind_error: Optional[str] = None
        try:
            self.bound = _tsig(condition.bind(child.schema),
                               where="Filter condition")
        except UnsupportedExpr as err:
            self.bound = None
            self.bind_error = str(err)

    @property
    def schema(self):
        return self.child.schema

    def describe(self):
        return f"Filter[{self.condition!r}]"


class Aggregate(LogicalPlan):
    """Grouped or ungrouped aggregation.

    aggs are (output_name, AggExpr) pairs; keys are grouping expressions.
    """

    def __init__(self, child: LogicalPlan, keys: Sequence[Expression],
                 aggs: Sequence[Tuple[str, agg.AggExpr]]):
        self.child = child
        self.children = [child]
        self.keys = list(keys)
        self.aggs = list(aggs)
        self.bound_keys = [_tsig(k.bind(child.schema),
                                 where=f"Aggregate key {k.name!r}")
                           for k in self.keys]
        self.bound_aggs = [(n, _tsig(a.bind(child.schema),
                                     where=f"Aggregate agg {n!r}"))
                           for n, a in self.aggs]
        fields = [Field(k.name, bk.dtype)
                  for k, bk in zip(self.keys, self.bound_keys)]
        fields += [Field(n, a.dtype) for n, a in self.bound_aggs]
        self._schema = Schema(fields)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return (f"Aggregate[keys={[repr(k) for k in self.keys]}, "
                f"aggs={[n for n, _ in self.aggs]}]")


class Expand(LogicalPlan):
    """GROUPING SETS expansion feeding an Aggregate (reference:
    GpuExpandExec.scala). Output = child columns ++ grouping-key columns
    (validity dropped where a set excludes the key) ++ grouping_id."""

    def __init__(self, child: LogicalPlan, key_exprs: Sequence[Expression],
                 key_names: Sequence[str], include_masks, gid_name: str):
        self.child = child
        self.children = [child]
        self.key_exprs = list(key_exprs)
        self.key_names = list(key_names)
        self.include_masks = [tuple(m) for m in include_masks]
        self.gid_name = gid_name
        self.bound_keys = [_tsig(k.bind(child.schema),
                                 where=f"Expand key {k.name!r}")
                           for k in self.key_exprs]
        fields = list(child.schema.fields)
        fields += [Field(n, k.dtype)
                   for n, k in zip(self.key_names, self.bound_keys)]
        fields.append(Field(gid_name, dt.INT64))
        self._schema = Schema(fields)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return (f"Expand[{len(self.include_masks)} sets, "
                f"keys={self.key_names}]")


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], how: str = "inner",
                 condition: Optional[Expression] = None):
        assert how in ("inner", "left", "right", "full", "left_semi",
                       "left_anti", "cross")
        self.left, self.right = left, right
        self.children = [left, right]
        self.how = how
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.bound_left_keys = [_tsig(k.bind(left.schema),
                                      where=f"Join left key {k.name!r}")
                                for k in self.left_keys]
        self.bound_right_keys = [_tsig(k.bind(right.schema),
                                       where=f"Join right key {k.name!r}")
                                 for k in self.right_keys]
        lf = list(left.schema.fields)
        rf = list(right.schema.fields)
        # non-equi condition binds over the COMBINED schema (the
        # reference's AST-compiled join conditions, AstUtil.scala)
        self.condition = condition
        self.bound_condition = (_tsig(condition.bind(Schema(lf + rf)),
                                      where="Join condition")
                                if condition is not None else None)
        if how in ("left_semi", "left_anti"):
            fields = lf
        else:
            fields = lf + rf
        self._schema = Schema(fields)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"Join[{self.how}, on={list(zip(self.left_keys, self.right_keys))}]"


class SortOrder:
    def __init__(self, expr: Expression, ascending: bool = True,
                 nulls_first: Optional[bool] = None):
        self.expr = expr
        self.ascending = ascending
        # Spark default: nulls first for asc, nulls last for desc
        self.nulls_first = ascending if nulls_first is None else nulls_first

    def __repr__(self):
        d = "ASC" if self.ascending else "DESC"
        nf = "NULLS FIRST" if self.nulls_first else "NULLS LAST"
        return f"{self.expr!r} {d} {nf}"


class Sort(LogicalPlan):
    def __init__(self, child: LogicalPlan, orders: Sequence[SortOrder],
                 global_sort: bool = True):
        self.child = child
        self.children = [child]
        self.orders = list(orders)
        self.global_sort = global_sort
        self.bound_orders = [SortOrder(
            _tsig(o.expr.bind(child.schema),
                  where=f"Sort key {o.expr!r}"),
            o.ascending, o.nulls_first)
                             for o in self.orders]

    @property
    def schema(self):
        return self.child.schema

    def describe(self):
        return f"Sort[{self.orders}]"


class Limit(LogicalPlan):
    def __init__(self, child: LogicalPlan, n: int):
        self.child = child
        self.children = [child]
        self.n = n

    @property
    def schema(self):
        return self.child.schema

    def describe(self):
        return f"Limit[{self.n}]"


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = list(children)
        s0 = self.children[0].schema
        for c in self.children[1:]:
            if [f.dtype for f in c.schema.fields] != [f.dtype for f in
                                                      s0.fields]:
                raise ValueError("UNION schema mismatch")
        self._schema = s0

    @property
    def schema(self):
        return self._schema


class WindowOp(LogicalPlan):
    """Appends window-function columns (reference: GpuWindowExec planning
    in GpuWindowExecMeta.scala — round-1 requires one shared spec)."""

    def __init__(self, child: LogicalPlan, wcols):
        self.child = child
        self.children = [child]
        self.wcols = list(wcols)          # (name, WindowExpr) unbound
        self.bound = [(n, w.bind(child.schema)) for n, w in self.wcols]
        for _n, _w in self.bound:
            if getattr(_w, 'child', None) is not None:
                _tsig(_w.child, where=f"WindowOp column {_n!r}")
        self._schema = Schema(list(child.schema.fields)
                              + [Field(n, w.dtype) for n, w in self.bound])

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"WindowOp[{[n for n, _ in self.wcols]}]"


class Generate(LogicalPlan):
    """Explode/posexplode: appends generated columns, one output row per
    element (reference: GpuGenerateExec.scala GpuExplode/GpuPosExplode).
    Output = all child columns + [pos]? + (col | key,value)."""

    def __init__(self, child: LogicalPlan, generator, out_names):
        self.child = child
        self.children = [child]
        self.generator = generator              # unbound Explode/PosExplode
        self.bound = _tsig(generator.bind(child.schema),
                           where="Generate generator")
        self.out_names = list(out_names)
        gen_dt = self.bound.dtype
        gen_fields = []
        if self.bound.with_position:
            gen_fields.append(Field(self.out_names[0], dt.INT32))
        if isinstance(self.bound.child.dtype, dt.MapType):
            # map explode: key + value columns
            for f, nm in zip(gen_dt.fields, self.out_names[-2:]):
                gen_fields.append(Field(nm, f.dtype))
        else:
            gen_fields.append(Field(self.out_names[-1], gen_dt))
        self._schema = Schema(list(child.schema.fields) + gen_fields)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"Generate[{self.generator!r}]"


class MapInPandas(LogicalPlan):
    """Batch-wise pandas transform in a pooled python worker process
    (reference: GpuMapInPandasExec)."""

    def __init__(self, child: LogicalPlan, fn, schema: Schema):
        self.child = child
        self.children = [child]
        self.fn = fn
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        name = getattr(self.fn, "__name__", "fn")
        return f"MapInPandas[{name}]"


class GroupedMapInPandas(LogicalPlan):
    """Per-group pandas transform (applyInPandas / AggregateInPandas):
    the planner repartitions by key so groups are whole per partition
    (reference: GpuFlatMapGroupsInPandasExec,
    GpuAggregateInPandasExec.scala:51). `fn` is the worker-side wrapper
    (already closed over the user function + keys)."""

    def __init__(self, child: LogicalPlan, fn, schema: Schema,
                 key_names):
        self.child = child
        self.children = [child]
        self.fn = fn
        self._schema = schema
        self.key_names = list(key_names)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"GroupedMapInPandas[keys={self.key_names}]"


class CoGroupInPandas(LogicalPlan):
    """Cogrouped pandas transform (reference:
    GpuFlatMapCoGroupsInPandasExec): both children repartition by their
    keys; fn is the worker-side _CoGroupApply wrapper."""

    def __init__(self, left: LogicalPlan, right: LogicalPlan, fn,
                 schema: Schema, lkeys, rkeys):
        self.children = [left, right]
        self.fn = fn
        self._schema = schema
        self.lkeys = list(lkeys)
        self.rkeys = list(rkeys)

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CoGroupInPandas[{self.lkeys} x {self.rkeys}]"


class Repartition(LogicalPlan):
    def __init__(self, child: LogicalPlan, num_partitions: int,
                 keys: Optional[Sequence[Expression]] = None):
        self.child = child
        self.children = [child]
        self.num_partitions = num_partitions
        self.keys = list(keys) if keys else None
        self.bound_keys = ([_tsig(k.bind(child.schema),
                                  where=f"Repartition key {k.name!r}")
                            for k in self.keys]
                           if self.keys else None)

    @property
    def schema(self):
        return self.child.schema

    def describe(self):
        return f"Repartition[{self.num_partitions}]"
