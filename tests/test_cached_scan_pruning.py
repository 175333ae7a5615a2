"""Column pruning reaches the HBM-cached scan (plan/optimizer.py:prune ->
plan/logical.py:CachedScan.pruned): a plan over df.cache() carries
zero-copy views of the columns it reads, planned once per column set."""
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.functions import col
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.optimizer import optimize
from spark_rapids_tpu.profiler import xla_stats
from spark_rapids_tpu.workloads import tpch

from asserts import assert_rows_equal

# the specification's widths (clause 1.4): the generators of
# workloads/tpch.py make the columns q1/q3/q6 read, these fill the rest
FILLER = {
    "lineitem": ["l_partkey", "l_suppkey", "l_linenumber", "l_commitdate",
                 "l_receiptdate", "l_shipinstruct", "l_shipmode",
                 "l_comment"],
    "orders": ["o_orderstatus", "o_clerk", "o_orderpriority", "o_comment"],
    "customer": ["c_name", "c_address", "c_nationkey", "c_phone",
                 "c_acctbal", "c_comment"],
}
STRINGS = ("name", "address", "phone", "comment", "clerk", "priority",
           "status", "instruct", "mode")


def _full_width(name, at):
    n = at.num_rows
    for f in FILLER[name]:
        if f.endswith(STRINGS):
            arr = pa.array([f"{f}#{i % 97}" * (1 + i % 3) for i in range(n)])
        else:
            arr = pa.array(np.arange(n, dtype=np.int64) % 1013)
        at = at.append_column(f, arr)
    return at


@pytest.fixture(scope="module")
def tables():
    return {"lineitem": _full_width("lineitem",
                                    tpch.gen_lineitem(sf=0.002, seed=7)),
            "orders": _full_width("orders", tpch.gen_orders(sf=0.002, seed=6)),
            "customer": _full_width("customer",
                                    tpch.gen_customer(sf=0.01, seed=5))}


@pytest.fixture(scope="module")
def frames(session, tables):
    """(cached, uncached) DataFrames of the same three tables."""
    plain = {n: session.create_dataframe(t) for n, t in tables.items()}
    return {n: df.cache() for n, df in plain.items()}, plain


def _nodes(node):
    yield node
    for c in list(node.children) + list(getattr(node, "members", [])):
        yield from _nodes(c)


def _named(root, cls):
    return [n for n in _nodes(root) if type(n).__name__ == cls]


def _leaves(plan):
    if not plan.children:
        return [plan]
    return [leaf for c in plan.children for leaf in _leaves(c)]


READS = {
    6: {16: ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]},
    1: {16: ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
             "l_returnflag", "l_linestatus", "l_shipdate"]},
    3: {8: ["c_custkey", "c_mktsegment"],
        9: ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
        16: ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]},
}


@pytest.mark.parametrize("q", [3, 6, 1])
def test_query_over_cached_tables_scans_what_it_reads(frames, q):
    cached, plain = frames
    df = tpch.queries()[q](cached)
    got = df.to_arrow()
    scans = _named(df._last_root, "CachedScanExec")
    assert {s.columns_cached: s.schema.names for s in scans} == READS[q]
    for s in scans:
        assert all(b.table.names == s.schema.names for b in s.batches)
    if q == 3:
        # customer's 2 and orders' 4: the gathers of the c-o join carry
        # nothing a later operator does not read
        joins = _named(df._last_root, "HashJoinExec")
        inner = [j for j in joins
                 if len(_named(j, "HashJoinExec")) == 1]
        assert [len(j.schema.fields) for j in inner] == [6]
    assert_rows_equal(got, tpch.queries()[q](plain).to_arrow(),
                      ignore_order=(q != 3))


def test_views_are_the_cached_arrays_and_planned_once(session, frames,
                                                       monkeypatch):
    cached, _ = frames
    # a second cache of its own: this test sees the views being made
    leaf_df = session.create_dataframe(
        cached["customer"].to_arrow()).cache()
    leaf = leaf_df._plan
    assert isinstance(leaf, L.CachedScan) and not leaf._pruned_cache

    def no_launch(*a, **k):
        raise AssertionError("a view launched an eager arange")
    from spark_rapids_tpu.exec import batch as batch_mod
    monkeypatch.setattr(batch_mod.jnp, "arange", no_launch)
    before = xla_stats.snapshot()

    def plan():
        q = leaf_df.filter(col("c_mktsegment") == "BUILDING").select(
            "c_custkey", "c_name")
        (view,) = _leaves(optimize(q._plan, session.conf))
        return view

    view = plan()
    assert view is not leaf and isinstance(view, L.CachedScan)
    assert view.schema.names == ["c_custkey", "c_mktsegment", "c_name"]
    assert (view.columns_cached, len(view.batches)) == (8, len(leaf.batches))
    for vb, b in zip(view.batches, leaf.batches):
        assert vb.row_mask is b.row_mask
        assert (vb.num_rows, vb.capacity) == (b.num_rows, b.capacity)
        for name, vc in zip(vb.table.names, vb.table.columns):
            c = b.table.column(name)
            assert vc is c
            assert vc.data is c.data and vc.validity is c.validity
            assert vc.offsets is c.offsets
    # a fresh tree over the same cached DataFrame: the identical node
    assert plan() is view and len(leaf._pruned_cache) == 1
    after = xla_stats.snapshot()
    assert (after["dispatches"], after["compiles"]) == (
        before["dispatches"], before["compiles"])


def test_select_star_keeps_the_cached_node(session, frames):
    cached, plain = frames
    for df in (cached["orders"],
               cached["orders"].filter(col("o_orderdate") < 9204),
               cached["orders"].limit(7)):
        (leaf,) = _leaves(optimize(df._plan, session.conf))
        assert leaf is cached["orders"]._plan
    df = cached["orders"].filter(col("o_orderdate") < 9204)
    got = df.to_arrow()
    (scan,) = _named(df._last_root, "CachedScanExec")
    assert len(scan.schema.fields) == scan.columns_cached == 9
    assert "CachedScanExec[9 of 9 columns, " in df._last_root.tree_string()
    assert_rows_equal(
        got, plain["orders"].filter(col("o_orderdate") < 9204).to_arrow())


def test_count_star_keeps_one_narrow_column(session, frames, tables):
    cached, _ = frames
    assert cached["customer"].count() == tables["customer"].num_rows
    df = cached["lineitem"].agg(F.count("*").alias("n"))
    assert df.to_arrow().column("n").to_pylist() \
        == [tables["lineitem"].num_rows]
    (scan,) = _named(df._last_root, "CachedScanExec")
    # l_shipdate (date, 4 bytes) is lineitem's narrowest fixed-width column
    assert scan.schema.names == ["l_shipdate"]
    # a table of strings alone has no such column: the node stays
    words = session.create_dataframe(
        pa.table({"a": ["x", "yy", None], "b": ["p", "q", "r"]})).cache()
    assert words._plan.pruned(set()) is words._plan
    assert words.count() == 3


def test_union_of_two_cached_frames(session, frames):
    cached, plain = frames

    late = plain["orders"].filter(col("o_orderdate") >= 9204)

    def q(a, b):
        return a.filter(col("o_orderdate") < 9300).union(b).group_by(
            "o_shippriority").agg(F.count("*").alias("n"),
                                  F.sum("o_custkey").alias("s"))
    late_cached = late.cache()
    df = q(cached["orders"], late_cached)
    # Union's children are planned with no required set: the full nodes
    assert _leaves(optimize(df._plan, session.conf)) \
        == [cached["orders"]._plan, late_cached._plan]
    assert_rows_equal(df.to_arrow(), q(plain["orders"], late).to_arrow())


def test_nested_columns_prune_by_top_level_name(session):
    at = pa.table({
        "k": pa.array([1, 2, 3, 4], pa.int32()),
        "s": pa.array([{"x": 1, "y": "a"}, {"x": 2, "y": None}, None,
                       {"x": 4, "y": "d"}]),
        "l": pa.array([[1, 2], [], None, [3]], pa.list_(pa.int64())),
        "v": pa.array([10, 20, 30, 40], pa.int64())})
    plain = session.create_dataframe(at)
    cached = plain.cache()

    def q(df):
        return df.filter(col("k") > 1).select("k", "s")
    df = q(cached)
    got = df.to_arrow()
    (scan,) = _named(df._last_root, "CachedScanExec")
    assert scan.schema.names == ["k", "s"] and scan.columns_cached == 4
    leaf_cols = cached._plan.batches[0].table
    assert scan.batches[0].table.column("s") is leaf_cols.column("s")
    assert got.equals(q(plain).to_arrow())
    assert got.column("s").to_pylist() == [{"x": 2, "y": None}, None,
                                          {"x": 4, "y": "d"}]
    got = cached.select("l", "v").to_arrow()
    assert got.equals(plain.select("l", "v").to_arrow())


def test_stats_memos_survive_replanning(session, frames, monkeypatch):
    from spark_rapids_tpu.plan import stats
    cached, _ = frames
    leaf = cached["orders"]._plan

    def view():
        q = cached["orders"].select("o_custkey", "o_orderdate")
        (v,) = _leaves(optimize(q._plan, session.conf))
        return v
    v = view()
    ndv = stats.scan_column_ndv(v, "o_custkey")
    assert ndv and ndv > 1
    monkeypatch.setattr(stats, "_sample_arrow_column",
                        lambda node, name: pytest.fail("sampled again"))
    # the same node after a second planning, and one memo for the table:
    # the join reorder asks the leaf, the planner and AQE the view
    assert stats.scan_column_ndv(view(), "o_custkey") == ndv
    assert stats.scan_column_ndv(leaf, "o_custkey") == ndv
    # observed cardinalities are looked up before pruning and harvested
    # after it: leaf and view key alike, another cache does not
    assert stats.logical_fp(v) == stats.logical_fp(leaf)
    assert stats.logical_fp(cached["customer"]._plan) \
        != stats.logical_fp(leaf)


def test_whole_input_aggregate_over_a_pruned_scan(frames):
    cached, plain = frames
    df = tpch.q6(cached["lineitem"])
    df.to_arrow()          # compiles
    df = tpch.q6(cached["lineitem"])
    got = df.to_arrow()
    metrics = df.last_metrics()
    root = metrics[df._last_root._op_id]
    assert root["xlaDispatches"] == 1 and root["xlaCompiles"] == 0
    (scan,) = _named(df._last_root, "CachedScanExec")
    assert (metrics[scan._op_id]["columnsRead"],
            metrics[scan._op_id]["columnsCached"]) == (4, 16)
    assert scan.describe() == (
        f"CachedScanExec[4 of 16 columns, {len(scan.batches)} batches]")
    assert got.equals(tpch.q6(plain["lineitem"]).to_arrow())
    # the grouped whole-input path reads the pruned scan's batches too
    df = tpch.q1(cached["lineitem"])
    assert_rows_equal(df.to_arrow(), tpch.q1(plain["lineitem"]).to_arrow())
    (scan,) = _named(df._last_root, "CachedScanExec")
    assert (df.last_metrics()[scan._op_id]["columnsRead"],
            df.last_metrics()[scan._op_id]["columnsCached"]) == (7, 16)


def test_concurrent_planners_get_one_view(session, frames):
    """Queries of the service plan on their own threads over one cached
    DataFrame: whoever comes first, every plan holds the same view."""
    import sys
    import threading
    cached, _ = frames
    leaf = session.create_dataframe(cached["orders"].to_arrow()).cache()._plan
    workers, rounds = 16, 50
    got = [[] for _ in range(workers)]
    start = threading.Barrier(workers)

    def plan(out):
        start.wait(timeout=30)
        for r in range(rounds):
            out.append(leaf.pruned({"o_orderkey", ("o_custkey", "o_clerk",
                                                   "o_comment")[r % 3]}))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=plan, args=(out,)) for out in got]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert all(len(out) == rounds for out in got)
    assert len(leaf._pruned_cache) == 3
    assert {id(v) for out in got for v in out} \
        == {id(v) for v in leaf._pruned_cache.values()}
