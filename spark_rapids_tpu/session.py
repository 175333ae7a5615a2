"""Session + DataFrame: the host-facing API that drives TPU execution.

In the reference, Spark provides this surface and the plugin rewrites plans
underneath (Plugin.scala:56 ColumnarOverrideRules). Standalone round-1: the
DataFrame builds logical plans directly and `collect()` runs
plan -> TpuOverrides-style planner -> TPU physical plan. Method names track
pyspark.sql.DataFrame so workloads port mechanically.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .columnar.table import Schema
from .config import TpuConf
from .exec.base import ExecContext
from .exec.nodes import collect_to_arrow
from .expr.expressions import Expression, col, lit
from .expr.aggregates import AggExpr
from .functions import _to_expr
from .plan import logical as L
from .plan.planner import Planner

__all__ = ["TpuSession", "DataFrame"]

# per-process counter uniquifying the hidden right-side key renames of
# name-based joins (see DataFrame.join)
_JOIN_RENAME_COUNTER = [0]

_QM_LOCK = __import__("threading").Lock()

# reentrancy guard: a nested action on a thread that already holds an
# admission grant (e.g. a runtime-filter subquery collected inside a
# parent query) runs under the OUTER query's handle instead of asking
# the scheduler for a second grant (which could deadlock at
# maxConcurrentQueries=1)
_ACTION_TLS = __import__("threading").local()


class TpuSession:
    _active: Optional["TpuSession"] = None

    def __init__(self, conf: Optional[Dict] = None):
        self.conf = TpuConf(conf)
        self.read = DataFrameReader(self)
        # path of the most recent query's event log (set by the profiler
        # wrapper when sql.eventLog.enabled)
        self.last_event_log: Optional[str] = None
        TpuSession._active = self
        from .config import RETRY_COVERAGE_ENABLED
        from .memory.diagnostics import enable_retry_coverage
        enable_retry_coverage(bool(self.conf.get(RETRY_COVERAGE_ENABLED)))
        from .runtime import faults, ledger, lockdep, racedep
        lockdep.maybe_enable_from_conf(self.conf)
        ledger.maybe_enable_from_conf(self.conf)
        racedep.maybe_enable_from_conf(self.conf)
        # conf-carried fault plan (sql.debug.faults.plan) activates here
        # so distributed fragments — executors rebuild TpuSession(conf)
        # — inject under the same plan as the driver
        faults.install_from_conf(self.conf)

    @staticmethod
    def builder_get_or_create(conf: Optional[Dict] = None) -> "TpuSession":
        if TpuSession._active is None:
            TpuSession(conf)
        return TpuSession._active

    def set_conf(self, key, value):
        # tpulint: allow[unlocked-shared-write] conf snapshots are immutable; readers see the old or new frozen conf, never a torn one
        self.conf = self.conf.set(key, value)

    def cluster_manager(self):
        """Lazily start the driver/executor runtime (cluster/driver.py)
        when spark.rapids.tpu.cluster.executors > 0."""
        from .config import CLUSTER_EXECUTORS, CLUSTER_HEARTBEAT_TIMEOUT
        cm = getattr(self, "_cluster", None)
        if cm is None:
            from .cluster import ClusterManager
            cm = ClusterManager(
                self.conf.get(CLUSTER_EXECUTORS),
                heartbeat_timeout=self.conf.get(
                    CLUSTER_HEARTBEAT_TIMEOUT))
            cm.start()
            self._cluster = cm
            import atexit
            atexit.register(cm.shutdown)
        return cm

    def query_manager(self):
        """Lazily build the concurrent query service (service/): every
        action routes through it for admission, fair scheduling,
        cancellation, and deadlines (docs/service.md)."""
        import threading
        mgr = getattr(self, "_query_manager", None)
        if mgr is None:
            with _QM_LOCK:
                mgr = getattr(self, "_query_manager", None)
                if mgr is None:
                    from .service.query_manager import QueryManager
                    mgr = QueryManager(self.conf)
                    self._query_manager = mgr
                    # admission-awareness for the background compile
                    # pool: speculative (warm-pack) compiles defer
                    # while any admitted query is running; weakref so
                    # the hook never outlives session.stop()
                    import weakref

                    from .runtime import compile_pool
                    ref = weakref.ref(mgr)

                    def _busy(_ref=ref):
                        m = _ref()
                        return m is not None and m._running > 0
                    compile_pool.set_busy_hook(_busy)
        return mgr

    def serve(self, host: str = "127.0.0.1", port: int = 0):
        """Start the JSON-lines gateway (service/server.py) multiplexing
        client sessions onto this engine process; returns the server
        (its .host/.port carry the bound address)."""
        from .service.server import QueryServer
        # AOT warm pack: when sql.service.warmPack.path is set, replay
        # the recorded key set through the background compile pool
        # before accepting connections — the first client query finds
        # its programs warm (or compiling) instead of paying the full
        # cold tail inline. Advisory: any pack problem logs and serves
        # cold.
        from .runtime import warm_pack
        self._warm_pack_summary = warm_pack.preload(self)
        srv = QueryServer(self, host, port)
        srv.start()
        # multi-host serving fabric: when sql.fleet.directory is set,
        # register this process in the fleet (peer cache tier + sticky
        # routing + warm-state pull from the longest-lived peer); a
        # no-fleet session skips all of it in one conf read
        from . import fleet
        try:
            self._fleet_member = fleet.join(
                self, gateway_addr=(srv.host, srv.port))
        except Exception:
            import logging
            logging.getLogger(__name__).warning(
                "fleet join failed; serving solo", exc_info=True)
            self._fleet_member = None
        return srv

    def save_warm_pack(self, path: Optional[str] = None):
        """Write the warm-pack manifest (recorded SQL + observed
        program signatures) to `path` or sql.service.warmPack.record;
        returns the path written or None when disabled."""
        from .runtime import warm_pack
        return warm_pack.save(self.conf, path)

    def stop(self):
        member = getattr(self, "_fleet_member", None)
        if member is not None:
            try:
                member.leave()
            except Exception:
                pass
            self._fleet_member = None
        cm = getattr(self, "_cluster", None)
        if cm is not None:
            cm.shutdown()
            self._cluster = None
        # pair with query_manager()'s double-checked build: clearing
        # outside _QM_LOCK could interleave with a concurrent build and
        # resurrect a manager the session just tore down
        with _QM_LOCK:
            self._query_manager = None
        if TpuSession._active is self:
            TpuSession._active = None

    # ------------------------------------------------------------------
    def create_dataframe(self, data, schema=None) -> "DataFrame":
        import pyarrow as pa
        if isinstance(data, pa.Table):
            at = data
        elif isinstance(data, dict):
            if schema is not None:
                at = pa.table(data, schema=schema.to_arrow()
                              if isinstance(schema, Schema) else schema)
            else:
                at = pa.table(data)
        else:
            raise TypeError("create_dataframe expects a pyarrow Table or dict")
        return DataFrame(self, L.InMemoryScan(at))

    def sql(self, query: str) -> "DataFrame":
        from .sql.parser import parse_sql
        from .runtime import warm_pack
        warm_pack.note_query(query, self.conf)
        return parse_sql(self, query)


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self._session = session

    def parquet(self, *paths: str, columns=None) -> "DataFrame":
        import glob as _glob
        import os
        expanded: List[str] = []
        for p in paths:
            if os.path.isdir(p):
                expanded.extend(sorted(
                    _glob.glob(os.path.join(p, "*.parquet"))))
            elif any(ch in p for ch in "*?["):
                expanded.extend(sorted(_glob.glob(p)))
            else:
                expanded.append(p)
        return DataFrame(self._session,
                         L.ParquetScan(expanded, columns=columns))

    def csv(self, *paths: str, header=True, schema=None, delimiter=",",
            quote='"', escape="\\", comment=None,
            null_value="") -> "DataFrame":
        """Lazy streaming CSV scan (reference: GpuCSVScan.scala:57);
        schema from a first-block sample unless given."""
        from .exec.text_scan import CsvOptions
        opts = CsvOptions(header=header, delimiter=delimiter, quote=quote,
                          escape=escape, comment=comment,
                          null_value=null_value)
        return DataFrame(self._session,
                         L.TextScan(list(paths), "csv", schema,
                                    options=opts))

    def orc(self, *paths: str) -> "DataFrame":
        """Lazy stripe-streaming ORC scan (reference: GpuOrcScan.scala:78
        PERFILE reader)."""
        return DataFrame(self._session, L.TextScan(list(paths), "orc"))

    def avro(self, *paths: str) -> "DataFrame":
        """Lazy block-streaming Avro scan (reference: GpuAvroScan)."""
        return DataFrame(self._session, L.TextScan(list(paths), "avro"))

    def iceberg(self, path: str, snapshot_id=None,
                as_of_timestamp=None) -> "DataFrame":
        """Iceberg table read: metadata json -> manifest list -> manifests
        -> live parquet files (reference: the iceberg module's
        GpuIcebergParquetScan); supports snapshot time travel."""
        from .io.iceberg import read_iceberg
        return read_iceberg(self._session, path, snapshot_id,
                            as_of_timestamp)

    def delta(self, path: str, version=None) -> "DataFrame":
        from .io.delta import read_delta
        return read_delta(self._session, path, version)

    def json(self, *paths: str, schema=None) -> "DataFrame":
        """Lazy block-streaming JSON-lines scan (reference:
        GpuJsonScan.scala); schema from a first-block sample unless
        given."""
        return DataFrame(self._session,
                         L.TextScan(list(paths), "json", schema))


def _split_join_condition(expr, lschema, rschema):
    """Decompose a join-on expression: (left_keys, right_keys, residual).
    Top-level AND conjuncts of the form left_expr == right_expr become
    equi keys; everything else stays in the residual non-equi condition
    (the reference's extraction in GpuHashJoin + AstUtil)."""
    from .expr.expressions import And, ColumnRef, Eq

    lnames, rnames = set(lschema.names), set(rschema.names)

    def refs(e):
        out = set()
        stack = [e]
        while stack:
            x_ = stack.pop()
            if isinstance(x_, ColumnRef):
                out.add(x_._name if hasattr(x_, "_name") else x_.name)
            stack.extend(getattr(x_, "children", []))
        return out

    def side(e):
        r = refs(e)
        if r and r <= lnames and not (r & rnames):
            return "left"
        if r and r <= rnames and not (r & lnames):
            return "right"
        return None

    def conjuncts(e):
        if isinstance(e, And):
            return conjuncts(e.children[0]) + conjuncts(e.children[1])
        return [e]

    lkeys, rkeys, residual = [], [], None
    for c in conjuncts(expr):
        if isinstance(c, Eq):
            a, b = c.children
            sa, sb = side(a), side(b)
            if sa == "left" and sb == "right":
                lkeys.append(a)
                rkeys.append(b)
                continue
            if sa == "right" and sb == "left":
                lkeys.append(b)
                rkeys.append(a)
                continue
        residual = c if residual is None else (residual & c)
    return lkeys, rkeys, residual


class GroupingID:
    """Marker accepted in rollup/cube agg lists: resolves to the Spark
    grouping_id of the row's grouping set."""

    name = "grouping_id()"

    def alias(self, name):
        from .expr.expressions import Alias
        return Alias(self, name)


class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence[Expression],
                 grouping_sets=None):
        self._df = df
        self._keys = list(keys)
        # list of include-masks (one bool per key) or None for plain
        # GROUP BY; reference: GpuExpandExec.scala projections
        self._grouping_sets = grouping_sets

    def _key_names(self):
        names = [getattr(k, "name", None) for k in self._keys]
        if any(n is None for n in names):
            raise ValueError("pandas group transforms need plain column "
                             "keys (got computed expressions)")
        return names

    @staticmethod
    def _out_schema(schema):
        from .columnar import dtypes as _dt
        from .columnar.table import Field, Schema as _Schema
        if isinstance(schema, _Schema):
            return schema
        if isinstance(schema, (list, tuple)):
            return _Schema([Field(n, t) for n, t in schema])
        return _Schema([Field(f.name, _dt.from_arrow(f.type))
                        for f in schema])

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """Per-group pandas transform: `fn(pandas.DataFrame) ->
        pandas.DataFrame` runs once per group in a pooled python worker
        (reference: GroupedData.applyInPandas /
        GpuFlatMapGroupsInPandasExec). Groups are repartitioned whole;
        oversized partitions chunk at group boundaries."""
        from .exec.python_exec import _GroupApply
        out = self._out_schema(schema)
        names = self._key_names()
        return DataFrame(self._df._session, L.GroupedMapInPandas(
            self._df._plan, _GroupApply(fn, names), out, names))

    applyInPandas = apply_in_pandas

    def agg_in_pandas(self, _types=None, **named) -> "DataFrame":
        """AggregateInPandas (reference:
        GpuAggregateInPandasExec.scala:51): each kwarg is
        name=(fn, col[, col...]); fn receives pandas Series (one per
        col) for ONE group and returns a scalar. Output: key columns +
        one row per group. Aggregate outputs default to FLOAT64;
        non-float results declare their dtype via
        `_types={name: DataType}`."""
        from .columnar import dtypes as _dt
        from .columnar.table import Field, Schema as _Schema
        from .exec.python_exec import _AggApply
        names = self._key_names()
        aggs = {}
        for out_name, spec in named.items():
            fn = spec[0]
            cols = [getattr(c, "name", c) for c in spec[1:]]
            aggs[out_name] = (fn, cols)
        child_schema = self._df._plan.schema
        fields = [Field(n, child_schema[child_schema.index_of(n)].dtype)
                  for n in names]
        fields += [Field(n, (_types or {}).get(n, _dt.FLOAT64))
                   for n in aggs]
        out = _Schema(fields)
        return DataFrame(self._df._session, L.GroupedMapInPandas(
            self._df._plan, _AggApply(aggs, names), out, names))

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair two grouped frames for applyInPandas over matching key
        groups (reference: GpuFlatMapCoGroupsInPandasExec)."""
        return CoGroupedData(self, other)

    def agg(self, *aggs, **named_aggs) -> "DataFrame":
        pairs = []
        gid_cols = []
        from .expr.expressions import Alias
        for a in aggs:
            name = getattr(a, "_alias", None) or a.name
            inner = a
            if isinstance(a, Alias):
                name = a._name
                inner = a.child
            if isinstance(inner, GroupingID):
                gid_cols.append(name)
                continue
            if not isinstance(inner, AggExpr):
                raise TypeError(f"not an aggregate: {a!r}")
            pairs.append((name, inner))
        for name, a in named_aggs.items():
            inner = a.child if hasattr(a, "child") and not isinstance(
                a, AggExpr) else a
            pairs.append((name, inner))
        if self._grouping_sets is None:
            if gid_cols:
                raise ValueError("grouping_id() requires rollup/cube/"
                                 "grouping_sets")
            return DataFrame(self._df._session,
                             L.Aggregate(self._df._plan, self._keys,
                                         pairs))
        return self._agg_grouping_sets(pairs, gid_cols)

    def _agg_grouping_sets(self, pairs, gid_cols) -> "DataFrame":
        """ROLLUP/CUBE/GROUPING SETS: Expand (one block per set, excluded
        keys nulled, + grouping_id) then aggregate by
        (keys..., grouping_id), then project user columns."""
        from .expr.expressions import Alias, ColumnRef
        child = self._df._plan
        knames = [f"#gset_k{i}" for i in range(len(self._keys))]
        gid = "#gset_gid"
        expand = L.Expand(child, self._keys, knames,
                          self._grouping_sets, gid)
        gkeys = [ColumnRef(kn) for kn in knames] + [ColumnRef(gid)]
        agg_node = L.Aggregate(expand, gkeys, pairs)
        out = []
        for k, kn in zip(self._keys, knames):
            out.append(Alias(ColumnRef(kn), k.name))
        for nm, _ in pairs:
            out.append(ColumnRef(nm))
        for nm in gid_cols:
            out.append(Alias(ColumnRef(gid), nm))
        return DataFrame(self._df._session, L.Project(agg_node, out))

    def count(self) -> "DataFrame":
        from .expr.aggregates import CountStar
        return self.agg(CountStar().alias("count"))


class CoGroupedData:
    def __init__(self, left: GroupedData, right: GroupedData):
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """`fn(left_df, right_df) -> pandas.DataFrame` per matching key
        group (either side may be empty)."""
        from .exec.python_exec import _CoGroupApply
        out = GroupedData._out_schema(schema)
        lnames = self._left._key_names()
        rnames = self._right._key_names()
        if len(lnames) != len(rnames):
            raise ValueError("cogroup key counts differ")
        lcols = list(self._left._df.schema.names)
        rcols = list(self._right._df.schema.names)
        wrapper = _CoGroupApply(fn, lnames, rnames, lcols, rcols)
        return DataFrame(self._left._df._session, L.CoGroupInPandas(
            self._left._df._plan, self._right._df._plan, wrapper, out,
            lnames, rnames))

    applyInPandas = apply_in_pandas


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self._session = session
        self._plan = plan

    def __del__(self):
        # release long-lived plan resources (mesh-exchange output
        # handles parked for re-execution) when the DataFrame goes away
        try:
            cached = getattr(self, "_cached", None)
            if cached is not None:
                cached[1].release()
        except Exception:
            pass

    # -- plan builders --------------------------------------------------
    @property
    def schema(self) -> Schema:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return self._plan.schema.names

    def select(self, *exprs) -> "DataFrame":
        from .window import WindowExpr
        from .expr.expressions import Alias, ColumnRef
        from .expr.collection_exprs import Explode
        es = [_to_expr(e) for e in exprs]
        # lift explode/posexplode into a Generate stage (the reference's
        # GenerateExec planning: GpuGenerateExec.scala)
        gens = [(i, (e.child if isinstance(e, Alias) else e), e)
                for i, e in enumerate(es)
                if isinstance(e.child if isinstance(e, Alias) else e,
                              Explode)]
        if gens:
            if len(gens) > 1:
                raise ValueError("only one explode per select")
            i, gen, orig = gens[0]
            from .columnar import dtypes as dt
            bound_child = gen.child.bind(self._plan.schema)
            is_map = isinstance(bound_child.dtype, dt.MapType)
            if is_map:
                names = ["key", "value"]
            else:
                names = [orig._name if isinstance(orig, Alias) else "col"]
            if gen.with_position:
                names = ["pos"] + names
            # generated columns get collision-proof internal names in the
            # Generate schema (a pre-existing 'col'/'key'/'pos' column
            # would otherwise shadow them), then alias back for the user
            internal = [f"#gen{id(gen) & 0xFFFF:04x}_{n}" for n in names]
            from .expr.expressions import Alias as _Alias
            gplan = L.Generate(self._plan, gen, internal)
            repl = [_Alias(ColumnRef(ii), n)
                    for ii, n in zip(internal, names)]
            es2 = es[:i] + repl + es[i + 1:]
            return DataFrame(self._session, L.Project(gplan, es2))
        # extract window expressions into a WindowOp stage (the planner
        # split the reference does in GpuWindowExecMeta)
        wcols, plain = [], []
        for e in es:
            inner = e.child if isinstance(e, Alias) else e
            if isinstance(inner, WindowExpr):
                name = e._name if isinstance(e, Alias) else \
                    f"_w{len(wcols)}"
                wcols.append((name, inner))
                plain.append(ColumnRef(name))
            else:
                plain.append(e)
        if wcols:
            return DataFrame(self._session,
                             L.Project(L.WindowOp(self._plan, wcols),
                                       plain))
        return DataFrame(self._session, L.Project(self._plan, es))

    def with_column(self, name: str, e) -> "DataFrame":
        # route through select() so window-expression extraction applies
        es = [col(n) for n in self.columns if n != name]
        es.append(_to_expr(e).alias(name))
        return self.select(*es)

    withColumn = with_column

    def filter(self, cond) -> "DataFrame":
        return DataFrame(self._session, L.Filter(self._plan, _to_expr(cond)))

    where = filter

    def group_by(self, *keys) -> GroupedData:
        return GroupedData(self, [_to_expr(k) for k in keys])

    groupBy = group_by

    def rollup(self, *keys) -> GroupedData:
        """GROUP BY ROLLUP: (k1..kn), (k1..kn-1), ..., ()."""
        ks = [_to_expr(k) for k in keys]
        n = len(ks)
        sets = [[i < j for i in range(n)] for j in range(n, -1, -1)]
        return GroupedData(self, ks, grouping_sets=sets)

    def cube(self, *keys) -> GroupedData:
        """GROUP BY CUBE: all 2^n key subsets."""
        ks = [_to_expr(k) for k in keys]
        n = len(ks)
        sets = [[not (m >> (n - 1 - i)) & 1 == 1 for i in range(n)]
                for m in range(1 << n)]
        return GroupedData(self, ks, grouping_sets=sets)

    def grouping_sets(self, keys, sets) -> GroupedData:
        """Explicit GROUPING SETS: `sets` is a list of key-name lists
        (subsets of `keys`)."""
        ks = [_to_expr(k) for k in keys]
        names = [k.name for k in ks]
        masks = []
        for s_ in sets:
            want = set(s_)
            unknown = want - set(names)
            if unknown:
                raise ValueError(f"grouping set refers to unknown keys "
                                 f"{sorted(unknown)}")
            masks.append([nm in want for nm in names])
        return GroupedData(self, ks, grouping_sets=masks)

    def agg(self, *aggs, **named) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs, **named)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        """Join on equi-key column names (`on`) plus an optional non-equi
        `condition` expression over the combined schema (ambiguous names
        resolve to the left side). With no `on` and a `condition`, a
        broadcast nested-loop join runs (reference:
        GpuBroadcastNestedLoopJoinExecBase.scala)."""
        if isinstance(on, str):
            on = [on]
        if on is None:
            on = []
        if isinstance(on, Expression):
            # decompose: equality conjuncts between the two sides become
            # equi keys, the rest joins the non-equi condition
            lk_x, rk_x, extra = _split_join_condition(
                on, self._plan.schema, other._plan.schema)
            if condition is not None:
                extra = condition if extra is None else (extra & condition)
            return self._join_positional(other, [], how, lk_x, rk_x,
                                         condition=extra)
        if not (isinstance(on, (list, tuple))
                and all(isinstance(c, str) for c in on)):
            raise TypeError("join `on` must be column name(s) or an "
                            "expression")
        lk = [col(c) for c in on]
        rk = [col(c) for c in on]
        if not on and condition is None and how != "cross":
            raise ValueError("join needs `on` keys or a `condition`")
        if condition is not None or not on:
            # conditions bind positionally over the combined schema;
            # skip the rename machinery (ambiguous names -> left side)
            return self._join_positional(other, list(on), how, lk, rk,
                                         condition=condition)
        if how in ("left_semi", "left_anti"):
            return DataFrame(self._session,
                             L.Join(self._plan, other._plan, lk, rk, how))
        lnames_list = list(self._plan.schema.names)
        rnames_list = list(other._plan.schema.names)
        if (len(set(lnames_list)) < len(lnames_list)
                or len(set(rnames_list)) < len(rnames_list)):
            # a side already carries duplicate column names (e.g. the
            # output of a previous join): name-based projection would
            # collapse the duplicates, so keep the positional form
            return self._join_positional(other, on, how, lk, rk)
        # Rename colliding right-side columns before the join so every
        # name in the joined schema is unique — the post-join projection
        # then stays purely name-based, which keeps the optimizer's
        # column pruning and filter pushdown working above joins.
        from .expr.expressions import Coalesce
        lnames = set(self._plan.schema.names)
        # collision-proof internal names: a unique counter per join keeps
        # the hidden key columns of DIFFERENT joins in one chain distinct,
        # which the join-reorder pass relies on when it flattens a chain
        _JOIN_RENAME_COUNTER[0] += 1
        tag = _JOIN_RENAME_COUNTER[0]
        rename = {f.name: f"__join_r{tag}_{f.name}"
                  for f in other._plan.schema.fields if f.name in lnames}
        rplan = other._plan
        if rename:
            rplan = L.Project(rplan, [
                col(f.name).alias(rename[f.name]) if f.name in rename
                else col(f.name) for f in other._plan.schema.fields])
        rk = [col(rename.get(c, c)) for c in on]
        jplan = L.Join(self._plan, rplan, lk, rk, how)
        # pyspark semantics: the `on` columns appear once, then left rest,
        # then right rest. For right joins take the key from the right
        # side; for full outer coalesce both sides.
        on_set = set(on)
        exprs = []
        for name in on:
            rn = rename.get(name, name)
            if how == "right":
                exprs.append(col(rn).alias(name))
            elif how == "full":
                exprs.append(Coalesce(col(name), col(rn)).alias(name))
            else:
                exprs.append(col(name))
        for f in self._plan.schema.fields:
            if f.name not in on_set:
                exprs.append(col(f.name))
        for f in other._plan.schema.fields:
            if f.name in on_set:
                continue
            rn = rename.get(f.name, f.name)
            exprs.append(col(rn).alias(f.name) if rn != f.name
                         else col(f.name))
        return DataFrame(self._session, L.Project(jplan, exprs))

    def _join_positional(self, other: "DataFrame", on, how, lk, rk,
                         condition=None):
        """Positional (BoundRef) post-join projection: exact for
        duplicate-named inputs, at the cost of disabling name-based
        pruning above this join."""
        from .expr.expressions import BoundRef, Coalesce
        jplan = L.Join(self._plan, other._plan, lk, rk, how,
                       condition=condition)
        if how in ("left_semi", "left_anti"):
            return DataFrame(self._session, jplan)
        nl = len(self._plan.schema.fields)
        on_set = set(on)
        exprs = []
        jschema = jplan.schema
        for name in on:
            li = self._plan.schema.index_of(name)
            ri = nl + other._plan.schema.index_of(name)
            lref = BoundRef(li, jschema[li].dtype, name)
            rref = BoundRef(ri, jschema[ri].dtype, name)
            if how == "right":
                exprs.append(rref)
            elif how == "full":
                exprs.append(Coalesce(lref, rref).alias(name))
            else:
                exprs.append(lref)
        for i, f in enumerate(jschema.fields):
            if f.name in on_set:
                continue
            exprs.append(BoundRef(i, f.dtype, f.name))
        return DataFrame(self._session, L.Project(jplan, exprs))

    def sort(self, *orders, ascending=True) -> "DataFrame":
        sos = []
        for o in orders:
            if isinstance(o, L.SortOrder):
                sos.append(o)
            else:
                sos.append(L.SortOrder(_to_expr(o), ascending))
        return DataFrame(self._session, L.Sort(self._plan, sos))

    orderBy = sort

    def create_or_replace_temp_view(self, name: str):
        from .sql.parser import register_view
        register_view(self._session, name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def distinct(self) -> "DataFrame":
        ks = [col(n) for n in self.columns]
        return DataFrame(self._session, L.Aggregate(self._plan, ks, []))

    dropDuplicates = distinct

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self._session, L.Limit(self._plan, n))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self._session, L.Union([self._plan, other._plan]))

    def repartition(self, n: int, *keys) -> "DataFrame":
        ks = [_to_expr(k) for k in keys] or None
        return DataFrame(self._session, L.Repartition(self._plan, n, ks))

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """Apply `fn(pandas.DataFrame) -> pandas.DataFrame` batch-wise in
        a pooled python WORKER PROCESS, batches crossing as Arrow IPC
        (reference: DataFrame.mapInPandas / GpuMapInPandasExec). `fn`
        must be picklable; `schema` is the output schema
        (Schema | list[(name, DataType)] | arrow schema)."""
        from .columnar.table import Field, Schema as _Schema
        from .columnar import dtypes as _dt
        if isinstance(schema, _Schema):
            out = schema
        elif isinstance(schema, (list, tuple)):
            out = _Schema([Field(n, t) for n, t in schema])
        else:  # arrow schema
            out = _Schema([Field(f.name, _dt.from_arrow(f.type))
                           for f in schema])
        return DataFrame(self._session,
                         L.MapInPandas(self._plan, fn, out))

    mapInPandas = map_in_pandas

    def cache(self) -> "DataFrame":
        """Materialize this DataFrame into HBM-resident device batches
        (GpuInMemoryTableScan analog); later queries skip decode + H2D."""
        from .config import MESH_DEVICES
        n = int(self._session.conf.get(MESH_DEVICES) or 0)
        if n > 1:
            return self._cache_sharded(n)

        def body(root, ctx):
            return list(root.execute_all(ctx))
        batches = self._run_action("cache", body)
        return DataFrame(self._session,
                         L.CachedScan(batches, self._plan.schema))

    def _cache_sharded(self, n: int) -> "DataFrame":
        """cache() of a mesh session: the rows divided evenly over the
        mesh's n devices, one partition a device (a table that is not a
        host table yet is brought to the host first)."""
        from .columnar.table import Table
        from .exec.batch import DeviceBatch
        from .parallel.mesh import get_mesh
        at = (self._plan.arrow if isinstance(self._plan, L.InMemoryScan)
              else self.to_arrow())
        devices = list(get_mesh(n).devices.reshape(-1))
        groups = Table.sharded_from_arrow(
            at, devices, self._session.conf.batch_size_rows)
        batches = [DeviceBatch(t, rows, mask, mask.shape[0])
                   for s in range(n) for (t, rows, mask) in
                   (g[s] for g in groups)]
        return DataFrame(self._session, L.CachedScan(
            batches, self._plan.schema, n_shards=n))

    def cached_devices(self) -> list:
        """The devices that hold rows of this cached DataFrame, in mesh
        order; [] where it is not the result of cache()."""
        if not isinstance(self._plan, L.CachedScan):
            return []
        held = []
        for b in self._plan.batches:
            if b.num_rows:
                for d in b.row_mask.devices():
                    if d not in held:
                        held.append(d)
        return sorted(held, key=lambda d: d.id)

    def uncache(self) -> "DataFrame":
        """Release this DataFrame's cached physical plan (exec nodes,
        their device state, materialized shuffles). The next action
        re-plans from the logical tree: a FRESH execution, which reuses
        no resident operator state."""
        cached = self._cached
        if cached is not None:
            try:
                cached[1].release()
            except Exception:
                pass
            self._cached = None
        # uncache promises the NEXT action is a fresh execution — the
        # cross-query result cache must not answer it from a prior run,
        # and in a fleet no PEER may either: invalidate_plan broadcasts
        # the plan fingerprint to every live member (best-effort; the
        # requester-side snapshot re-stat backstops a lost delivery)
        try:
            from .runtime import result_cache
            result_cache.invalidate_plan(self._plan)
        except Exception:
            pass
        return self

    # -- actions --------------------------------------------------------
    _cached: Optional[tuple] = None
    _last_root = None

    def _execute(self, conf=None):
        # Cache the physical plan: exec nodes own their jitted kernels, so
        # re-collecting a DataFrame reuses compiled programs (the analog of
        # Spark's executedPlan reuse). `conf` is the per-query snapshot
        # taken at submission — concurrent queries must not observe a
        # session conf mutated mid-flight.
        if conf is None:
            conf = self._session.conf
        # scan-snapshot staleness: re-stat every pinned data file; a
        # mid-session overwrite drops the cached physical plan (replan
        # rebinds against the new files) and invalidates dependent
        # result-cache entries — stale bytes are never served, cache on
        # or off (io/snapshot.py)
        from .io.snapshot import refresh_plan_snapshots
        changed = refresh_plan_snapshots(self._plan)
        if changed:
            from .runtime import result_cache
            result_cache.invalidate_paths(changed)
            if self._cached is not None:
                try:
                    self._cached[1].release()
                except Exception:
                    pass
                self._cached = None
        if self._cached is not None and self._cached[0] is conf:
            root = self._cached[1]
        else:
            planner = Planner(conf)
            root = planner.plan(self._plan)
            self._cached = (conf, root)
        ctx = ExecContext(conf, self._session)
        return root, ctx

    def _run_action(self, action: str, body):
        """Run one query action through the query service: admission by
        the fair scheduler, a CancelToken + query_id on the ExecContext,
        and the profiler wrapper (query_queued/query_admitted/
        query_start/.../query_end events when sql.eventLog.enabled).
        Runs on the CALLER's thread once admitted; `DataFrame.submit`
        is the async counterpart."""
        import time as _time
        from .runtime import result_cache
        conf = self._session.conf  # per-query conf snapshot
        outer = getattr(_ACTION_TLS, "handle", None)
        # whole-query tier of the cross-query result cache: collects
        # consult it BEFORE admission — a hit is served on the service
        # fast path (no slot consumed, still metered + event-logged)
        token = None
        if action == "collect" and result_cache.enabled(conf):
            t0 = _time.perf_counter()
            hit, token = result_cache.lookup_query(self._plan, conf)
            if hit is not None:
                if outer is None:
                    mgr = self._session.query_manager()
                    handle = mgr.fast_path(plan=self._plan, conf=conf,
                                           action=action, result=hit)
                    from .profiler.event_log import log_fast_path
                    log_fast_path(self._session, conf, handle, action,
                                  hit.num_rows,
                                  _time.perf_counter() - t0)
                self._last_metrics = {"ResultCache": {
                    "resultCacheHits": 1,
                    "numOutputRows": hit.num_rows}}
                return hit
        if outer is not None:
            # nested action (subquery collected inside a parent query):
            # ride the outer grant + token, skip re-admission
            return self._execute_action(action, body, conf,
                                        outer, nested=True,
                                        cache_token=token)
        mgr = self._session.query_manager()
        # service-level transparent retry: a CLASSIFIED-transient
        # failure (is_transient_error — injected faults, FetchFailed,
        # executor loss; never cancellation/deadline/user errors)
        # re-admits the query as a fresh attempt, with the FIRST
        # attempt's deadline still binding. Each attempt is its own
        # query_id, so admission accounting, the event log, and the
        # resource-ledger per-query balance check all see it whole.
        from .config import SERVICE_MAX_QUERY_RETRIES
        from .profiler import tracing
        from .runtime.faults import is_transient_error, note_recovery
        max_retries = int(conf.get(SERVICE_MAX_QUERY_RETRIES))
        attempt = 0
        deadline = None      # original deadline, binding across retries
        retry_of = None
        while True:
            timeout = None
            if deadline is not None:
                timeout = max(deadline - _time.monotonic(), 1e-3)
            # admission is the session's own time: a span of its own
            # beside the query's, on the profiler's clock (the waited
            # part is the back-dated `admission.queue` dict span)
            with tracing.span("admit", "queue"):
                handle = mgr.open_query(plan=self._plan, conf=conf,
                                        action=action, timeout=timeout)
            if deadline is None:
                deadline = handle.token.deadline
            try:
                out = self._execute_action(action, body, conf, handle,
                                           cache_token=token,
                                           retry_of=retry_of)
            except BaseException as e:
                with tracing.span("admit", "queue"):
                    mgr.close_query(handle, error=e)
                if (attempt < max_retries and is_transient_error(e)
                        and (deadline is None
                             or _time.monotonic() < deadline)):
                    attempt += 1
                    note_recovery("query_retries")
                    retry_of = {"attempt": attempt,
                                "prior_query_id": handle.query_id,
                                "error": repr(e)}
                    continue
                raise
            with tracing.span("admit", "queue"):
                mgr.close_query(handle, result=out)
            return out

    def submit(self, action: str = "collect", pool=None, timeout=None):
        """Async action through the query service: returns a QueryHandle
        immediately; `handle.result()` blocks for the arrow table (or
        re-raises). The gateway submits here."""
        if action != "collect":
            raise ValueError("submit() supports the 'collect' action")
        import time as _time
        from .exec.nodes import collect_to_arrow as _collect
        from .runtime import result_cache
        mgr = self._session.query_manager()
        conf = self._session.conf

        token = None
        if result_cache.enabled(conf):
            t0 = _time.perf_counter()
            hit, token = result_cache.lookup_query(self._plan, conf)
            if hit is not None:
                # cache fast path: answered without an admission slot;
                # handle.result() returns immediately
                handle = mgr.fast_path(plan=self._plan, conf=conf,
                                       action="collect", pool=pool,
                                       result=hit)
                from .profiler.event_log import log_fast_path
                log_fast_path(self._session, conf, handle, "collect",
                              hit.num_rows, _time.perf_counter() - t0)
                return handle

        # the admitted body runs on a QueryManager worker thread, so
        # the submitter's fleet member (thread-local) must be captured
        # HERE and re-entered there — a multi-member process would
        # otherwise publish gateway B's results as member A
        from .fleet import context as _fleet_ctx
        member = _fleet_ctx.active_member()

        def run(handle):
            if member is None:
                return self._execute_action(
                    "collect", lambda root, ctx: _collect(root, ctx),
                    conf, handle, cache_token=token)
            with _fleet_ctx.scoped(member):
                return self._execute_action(
                    "collect", lambda root, ctx: _collect(root, ctx),
                    conf, handle, cache_token=token)

        return mgr.submit(run, plan=self._plan, conf=conf,
                          action="collect", pool=pool, timeout=timeout)

    def _execute_action(self, action: str, body, conf, handle,
                        nested: bool = False, cache_token=None,
                        retry_of=None):
        """The admitted half of an action: plan (or reuse the cached
        physical tree), execute under the profiler wrapper, then attach
        the per-query XLA/semaphore/queue-wait accounting to the root
        node's MetricSet. On ANY failure — including cooperative
        cancellation — the physical plan is released deterministically
        (exchange handles, spill files, parked device buffers) instead
        of waiting for GC."""
        from .profiler import tracing, xla_stats
        from .profiler.event_log import profile_query
        from .service.query_manager import _query_scope
        # distributed tracing: one trace per query (trace_id ==
        # query_id). A nested action joins the enclosing query's trace
        # (the outer action installed its context on this thread);
        # otherwise the sampling decision is taken here, before
        # planning, so the plan span is part of the trace.
        tc = tracing.current() if nested else (
            tracing.start_trace(handle.query_id, conf)
            if handle is not None else None)
        rsp = None
        if not nested:
            # open the root span BEFORE planning so the plan span and
            # the back-dated admission wait parent under it — the trace
            # is one rooted tree, not a forest of top-level siblings.
            # Off-trace it is the profiler's annotation alone.
            # tpulint: allow[span-leak] query root span: ended by tracing.finish() in this action's finally (idempotent close-out)
            rsp = tracing.open_span("query", "query", tc, action=action)
            if tc is not None:
                tc = tracing.TraceContext(tc.trace_id, rsp.span_id, True)
                if handle is not None:
                    tracing.record_queue_span(tc, handle.queue_wait_ms,
                                              pool=handle.pool)
        try:
            with tracing.span("plan", "plan", tc):
                root, ctx = self._execute(conf)
        except BaseException:
            if rsp is not None:
                rsp.end()
            raise
        ctx.trace = tc
        if handle is not None:
            ctx.cancel = handle.token
            ctx.query_id = handle.query_id
            mgr = getattr(self._session, "_query_manager", None)
            if mgr is not None:
                ctx.sem_priority = mgr.scheduler.priority_of(handle)
        if rsp is not None:
            ctx._root_span = rsp
        # stage-ahead compilation: submit this tree's programs whose
        # signatures were observed before (earlier query or warm-pack
        # seed) to the background pool; downstream stage programs
        # compile while upstream stages execute. Best-effort, never
        # blocks the launch.
        from .runtime import compile_pool
        _cpool = compile_pool.get_pool(conf)
        if _cpool is not None:
            from .exec.base import prewarm_tree
            try:
                # under use(): the pool snapshots the submitter's trace
                # context so background compiles land in this trace
                with tracing.use(ctx.trace), \
                        tracing.span("prewarm", "compile"):
                    prewarm_tree(root, _cpool,
                                 handle.query_id if handle else None)
            except Exception:
                pass
        sem = getattr(self._session, "_semaphore", None)
        sem_acq0 = sem.metrics["acquires"] if sem is not None else 0
        xla0 = xla_stats.snapshot()
        from .runtime import ledger as _ledger
        lg = _ledger.ledger()
        lease_acq0 = (lg.report()["kinds"].get("staging_lease", {})
                      .get("acquires", 0) if lg is not None else 0)
        _ACTION_TLS.handle = handle if not nested else \
            getattr(_ACTION_TLS, "handle", None)
        from .runtime import result_cache
        rc_on = result_cache.enabled(conf)
        rc0 = result_cache.stats() if rc_on else None
        try:
            with _query_scope(handle.query_id if handle else "?"), \
                    tracing.use(ctx.trace):
                with profile_query(self._session, root, ctx, action,
                                   handle=None if nested else handle) as w:
                    if retry_of and w is not None:
                        # this attempt is a service-level transparent
                        # retry of a transient failure; link it to the
                        # prior attempt's query_id in the event log
                        w.emit("query_retry", action=action, **retry_of)
                    try:
                        # AQE stage driver: materialize shuffle stages
                        # bottom-up and replan (coalesce / skew-split /
                        # join demotion) between stage completion and
                        # consumer launch. Decisions are re-served on a
                        # cached root so every run's event log is
                        # self-contained. Errors (cancellation
                        # included) propagate — a stage that ran IS
                        # query execution.
                        from .plan.aqe import run_stage_driver
                        decisions = run_stage_driver(root, ctx, conf)
                        if decisions and w is not None:
                            w.emit("aqe_replan", action=action,
                                   decisions=decisions)
                        out = body(root, ctx)
                        # observed-cardinality harvest: close the AQE
                        # feedback loop (plan/stats.py calibration
                        # table); advisory, never fails the query
                        from .plan.stats import harvest_calibration
                        try:
                            harvest_calibration(root, ctx)
                        except Exception:
                            pass
                        if rc_on:
                            # a successful run feeds BOTH cache tiers:
                            # tagged exchange map outputs (fragment
                            # misses from planning) and, for collects,
                            # the whole-query arrow result
                            try:
                                result_cache.harvest_fragments(root, ctx)
                            except Exception:
                                pass
                            if cache_token is not None:
                                result_cache.put_query(cache_token, out,
                                                       conf)
                    finally:
                        # recovery events queued mid-execution
                        # (degrade_to_host and friends) drain into the
                        # query's event log even when the run failed
                        if w is not None and ctx.pending_events:
                            for ev in ctx.pending_events:
                                kw = dict(ev)
                                name = kw.pop("event")
                                try:
                                    w.emit(name, **kw)
                                except Exception:
                                    pass
                            ctx.pending_events = []
                        ctx.close()
        except BaseException:
            try:
                root.release()
            except Exception:
                pass
            if self._cached is not None and self._cached[1] is root:
                self._cached = None
            # cooperative prewarm cancellation: a dead query's queued
            # stage-ahead compiles are dropped (a task already
            # compiling finishes — the result is cached for a retry)
            if handle is not None and _cpool is not None:
                try:
                    _cpool.cancel_query(handle.query_id)
                except Exception:
                    pass
            raise
        finally:
            if not nested:
                _ACTION_TLS.handle = None
                # event-log-off fallback: the profiler wrapper normally
                # drains the trace (and emits trace_span records);
                # without it the trace must still close so EXPLAIN
                # ANALYZE gets its summary and the buffers drain
                try:
                    tracing.finish(ctx)
                except Exception:
                    pass
        # per-query XLA accounting rides the root node's MetricSet so it
        # flows into last_metrics() / EXPLAIN ANALYZE / op_metrics events
        xla1 = xla_stats.snapshot()
        rm = ctx.metrics_for(root._op_id)
        rm.add("xlaCompiles", int(xla1["compiles"] - xla0["compiles"]))
        rm.add("xlaDispatches",
               int(xla1["dispatches"] - xla0["dispatches"]))
        rm.add("programCacheHits",
               int(xla1.get("program_cache_hits", 0)
                   - xla0.get("program_cache_hits", 0)))
        rm.add("programCacheMisses",
               int(xla1.get("program_cache_misses", 0)
                   - xla0.get("program_cache_misses", 0)))
        # compile-tail accounting: wall ms spent in XLA compilation
        # attributed to this action (sync misses on this thread plus
        # background prewarms that completed during it) and how many of
        # those compiles ran off the dispatch path
        cms = (xla1.get("program_cache_compile_ms", 0.0)
               - xla0.get("program_cache_compile_ms", 0.0))
        if cms:
            rm.add("compileMs", round(cms, 3))
        bg = int(xla1.get("program_cache_background_compiles", 0)
                 - xla0.get("program_cache_background_compiles", 0))
        if bg:
            rm.add("backgroundCompiles", bg)
        if handle is not None and not nested:
            rm.add("queueWaitMs", round(handle.queue_wait_ms, 3))
        # critical-path decomposition of this action's wall clock
        # (profiler/critical_path.py): per-edge percentage shares ride
        # the root MetricSet so EXPLAIN ANALYZE prints criticalPath=
        summ = getattr(ctx, "trace_summary", None)
        if summ:
            for c, pct in summ["share_pct"].items():
                if pct:
                    rm.add(f"criticalPathShare.{c}", pct)
        if rc_on:
            # per-action cache accounting on the root MetricSet (flows
            # into EXPLAIN ANALYZE / op_metrics); global-counter diffs,
            # so concurrent queries' events can interleave — counters,
            # not invariants
            rc1 = result_cache.stats()
            for metric, counter in (
                    ("resultCacheHits", "result_cache_hits"),
                    ("resultCacheMisses", "result_cache_misses"),
                    ("resultCacheFragmentHits",
                     "result_cache_fragment_hits"),
                    ("resultCacheEvictions", "result_cache_evictions"),
                    ("resultCacheInvalidationEvents",
                     "result_cache_invalidations")):
                d = int(rc1[counter] - rc0[counter])
                if d:
                    rm.add(metric, d)
            if cache_token is not None:
                # this action's own whole-query lookup missed (it was
                # counted in _run_action, before the rc0 snapshot)
                rm.add("resultCacheMisses", 1)
        sem = getattr(self._session, "_semaphore", None)
        if sem is not None:
            acq = sem.metrics["acquires"] - sem_acq0
            if acq:
                rm.add("semaphoreAcquires", int(acq))
        if lg is not None:
            # resource-ledger accounting on the root MetricSet (flows
            # into EXPLAIN ANALYZE): lease traffic this action plus the
            # per-query balance verdict — global-counter diffs, like the
            # cache counters above
            rep = lg.report()
            sk = rep["kinds"].get("staging_lease", {})
            d = int(sk.get("acquires", 0) - lease_acq0)
            if d:
                rm.add("ledgerLeaseAcquires", d)
            rm.add("ledgerPeakLeases", int(sk.get("peakOutstanding", 0)))
            rm.add("ledgerBalanced", int(bool(rep["balanceOk"])))
        self._last_root = root
        self._last_metrics = {op: ms.snapshot(ctx.metrics_level)
                              for op, ms in ctx.metrics.items()}
        return out

    def to_arrow(self):
        return self._run_action(
            "collect", lambda root, ctx: collect_to_arrow(root, ctx))

    def last_metrics(self):
        """Per-operator metrics of the most recent action (GpuMetric
        analog; levels per spark.rapids.tpu.sql.metrics.level)."""
        return getattr(self, "_last_metrics", {})

    def to_jax(self):
        """Zero-copy export of the result as device arrays — the
        ColumnarRdd analog (reference: sql-plugin-api ColumnarRdd,
        zero-copy GPU handoff to ML/XGBoost). Returns
        {column: (data, validity)} of jax Arrays already resident in
        HBM; fixed-width columns only (strings keep Arrow export)."""
        from .columnar import dtypes as _dt
        from .ops.concat import concat_cvs, concat_masks
        from .ops.gather import compact
        for f in self.schema.fields:
            if f.dtype.is_variable_width or f.dtype.is_nested:
                raise TypeError(
                    f"to_jax exports fixed-width columns; {f.name} is "
                    f"{f.dtype.simple_name()} (use to_arrow)")
        def body(root, ctx):
            out = []
            for pid in range(root.num_partitions(ctx)):
                out.extend(root.execute_partition(ctx, pid))
            return out

        batches = self._run_action("to_jax", body)
        if not batches:
            import jax.numpy as jnp
            return {f.name: (jnp.zeros(0, f.dtype.np_dtype),
                             jnp.zeros(0, jnp.bool_))
                    for f in self.schema.fields}
        cvs = [concat_cvs([b.cvs()[i] for b in batches],
                          self.schema.fields[i].dtype)
               for i in range(len(self.schema.fields))]
        mask = concat_masks([b.row_mask for b in batches])
        from .utils.transfer import fetch_int
        dense, count = compact(cvs, mask)
        n = fetch_int(count)
        return {f.name: (c.data[:n], c.validity[:n])
                for f, c in zip(self.schema.fields, dense)}

    def collect(self) -> List[tuple]:
        at = self.to_arrow()
        cols = [at.column(i).to_pylist() for i in range(at.num_columns)]
        return list(zip(*cols)) if cols else []

    def to_pydict(self) -> Dict[str, list]:
        return self.to_arrow().to_pydict()

    def count(self) -> int:
        from .expr.aggregates import CountStar
        df = DataFrame(self._session,
                       L.Aggregate(self._plan, [], [("count", CountStar())]))
        return df.collect()[0][0]

    def explain(self, mode: str = "ALL"):
        """Print (and return) the plan. Modes: ALL / NOT_ON_TPU show
        TPU-placement tagging with per-node lore ids (plus static-audit
        findings); VALIDATE renders the plan auditor's full verdict tree
        (ok / will_fallback / will_not_work / recompile_risk per node,
        docs/static_analysis.md) WITHOUT executing anything; ANALYZE
        runs the query and renders the tree annotated with runtime
        metrics (rows/batches/op-time/shuffle/spill per node, top time
        sinks flagged) — the SQL-UI metric display analog."""
        mode_u = str(mode).upper()
        if mode_u == "ANALYZE":
            return self._explain_analyze()
        old = self._session.conf
        planner = Planner(old.set("spark.rapids.tpu.sql.explain", mode_u))
        planner.plan(self._plan)
        return "\n".join(planner.last_explain)

    def _explain_analyze(self) -> str:
        from .profiler.analyze import render_analyze
        from .profiler.event_log import op_metrics_records, plan_tree
        # drop (and release) any cached physical plan: stateful operators
        # in a previously executed plan (a materialized
        # ShuffleExchangeExec) would short-circuit re-execution, leaving
        # every operator below them metric-less — ANALYZE must measure a
        # full fresh run
        self.uncache()
        self.to_arrow()
        root = self._last_root
        recs = op_metrics_records(root, self._last_metrics)
        by_lore = {r["lore_id"]: r["metrics"] for r in recs}
        text = render_analyze(plan_tree(root), by_lore,
                              title="== EXPLAIN ANALYZE ==")
        print(text)
        return text

    def write_parquet(self, path: str, **kw):
        from .io.parquet import write_parquet
        write_parquet(self, path, **kw)

    def write_delta(self, path: str, mode: str = "append") -> int:
        from .io.delta import write_delta
        return write_delta(self, path, mode)

    @property
    def write(self):
        """Builder-style writer: df.write.mode(...).partitionBy(...)
        .parquet/orc/csv/json/hive_text/delta(path) (reference:
        GpuFileFormatWriter surface)."""
        from .io.writer import DataFrameWriter
        return DataFrameWriter(self)

    def _iter_partition_tables(self):
        """Stream the result partition-by-partition as compacted host
        arrow tables (shared by every file writer). Writers hold their
        admission grant for the generator's whole lifetime (the query
        service's open/close pair brackets the stream)."""
        import pyarrow as pa
        from .exec.nodes import _batch_to_arrow
        from .profiler.event_log import profile_query
        outer = getattr(_ACTION_TLS, "handle", None)
        mgr = self._session.query_manager() if outer is None else None
        conf = self._session.conf
        handle = outer if outer is not None else mgr.open_query(
            plan=self._plan, conf=conf, action="write")
        root, ctx = self._execute(conf)
        ctx.cancel = handle.token
        ctx.query_id = handle.query_id
        from .profiler import tracing
        tc = tracing.current() if outer is not None else \
            tracing.start_trace(handle.query_id, conf)
        ctx.trace = tc
        if tc is not None and outer is None:
            # root first, so the back-dated admission wait parents
            # under it (same rooted-tree shape as _execute_action)
            # tpulint: allow[span-leak] query root span: ended by tracing.finish() in the write path's finally
            rsp = tracing.open_span("query", "query", tc, action="write")
            ctx._root_span = rsp
            ctx.trace = tracing.TraceContext(tc.trace_id, rsp.span_id,
                                             True)
            tracing.record_queue_span(ctx.trace, handle.queue_wait_ms,
                                      pool=handle.pool)
        try:
            with tracing.use(ctx.trace), \
                    profile_query(self._session, root, ctx, "write",
                                  handle=None if outer else handle) as w:
                try:
                    from .plan.aqe import run_stage_driver
                    decisions = run_stage_driver(root, ctx, conf)
                    if decisions and w is not None:
                        w.emit("aqe_replan", action="write",
                               decisions=decisions)
                    for pid in range(root.num_partitions(ctx)):
                        ctx.check_cancel()
                        tables = [_batch_to_arrow(b)
                                  for b in root.execute_partition(ctx, pid)]
                        if tables:
                            yield pa.concat_tables(tables)
                    from .plan.stats import harvest_calibration
                    try:
                        harvest_calibration(root, ctx)
                    except Exception:
                        pass
                finally:
                    ctx.close()
        except BaseException as e:
            try:
                root.release()
            except Exception:
                pass
            if self._cached is not None and self._cached[1] is root:
                self._cached = None
            if mgr is not None:
                # an abandoned generator is a clean early stop, not a
                # query failure
                mgr.close_query(handle, error=None if isinstance(
                    e, GeneratorExit) else e)
            raise
        else:
            if mgr is not None:
                mgr.close_query(handle)
        finally:
            # profile_query normally finishes the trace with the true
            # wall clock; this is the event-log-off fallback
            try:
                tracing.finish(ctx)
            except Exception:
                pass
        self._last_root = root
        self._last_metrics = {op: ms.snapshot(ctx.metrics_level)
                              for op, ms in ctx.metrics.items()}
