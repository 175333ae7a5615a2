"""Fused SPMD stages (exec/spmd_stage.py): exchange-as-sharding-
annotation on the 8-device virtual CPU mesh.

Covers the PR's acceptance surface: byte parity of the fused
one-program path against BOTH the round-based mesh exchange and the
single-host shuffle (q3/q6 distributed shapes included, plus nulls /
empty shards / string-heavy / skewed keys), the one-compiled-program-
per-stage and zero-compiles-on-warm-rerun contracts, mesh-topology-
keyed program-cache misses, the AQE mesh re-shard rule's on/off gates,
fault-driven degradation to the round-based exchange, and leak-free
cancellation mid-stage under the resource-ledger witness.
"""
import decimal
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pytest

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.exec.mesh_exchange import MeshExchangeExec
from spark_rapids_tpu.exec.spmd_stage import SpmdStageExec
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.parallel.mesh import (mesh_fingerprint,
                                            mesh_topology_key)
from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.runtime.program_cache import drain_compile_events
from spark_rapids_tpu.workloads import tpch

N_DEV = 8


def _conf(**extra):
    conf = {"spark.rapids.tpu.sql.batchSizeRows": 256,
            "spark.rapids.tpu.sql.resultCache.enabled": "false"}
    conf.update({f"spark.rapids.tpu.{k}": v for k, v in extra.items()})
    return conf


def _host():
    return st.TpuSession(_conf())


def _round():
    return st.TpuSession(_conf(**{"mesh.devices": N_DEV,
                                  "mesh.spmdStage.enabled": "false"}))


def _fused(**extra):
    return st.TpuSession(_conf(**{"mesh.devices": N_DEV, **extra}))


def _walk(node):
    yield node
    for m in getattr(node, "members", []) or []:
        yield m
    for c in node.children:
        yield from _walk(c)


def _msum(df, key):
    """Sum `key` over the per-operator metrics of `df`'s last action."""
    return int(sum(m.get(key, 0) for m in df.last_metrics().values()))


def _groupby(s, data, aggs=None):
    df = s.create_dataframe(data)
    aggs = aggs or [F.sum("v").alias("sv"), F.count("v").alias("c"),
                    F.min("v").alias("mn"), F.max("v").alias("mx")]
    return df.group_by("k").agg(*aggs)


def _to_map(tbl):
    ncol = tbl.num_columns
    return {tbl.column(0)[i].as_py():
            tuple(tbl.column(j)[i].as_py() for j in range(1, ncol))
            for i in range(tbl.num_rows)}


def _parity_three_paths(data, aggs=None):
    """Run the same grouped agg through host / round-based / fused and
    require identical contents; returns the fused DataFrame for metric
    assertions."""
    want = _to_map(_groupby(_host(), data, aggs).to_arrow())
    rq = _groupby(_round(), data, aggs)
    assert _to_map(rq.to_arrow()) == want
    fq = _groupby(_fused(), data, aggs)
    assert _to_map(fq.to_arrow()) == want
    return fq, rq


# ---------------------------------------------------------------------
# topology keys and warm-pack fingerprints
# ---------------------------------------------------------------------
def test_mesh_topology_key_distinguishes_topologies():
    assert mesh_topology_key(8) != mesh_topology_key(4)
    assert mesh_topology_key(8, "data") != mesh_topology_key(8, "model")
    assert mesh_topology_key(8) == mesh_topology_key(8)


def test_mesh_fingerprint_names_device_count():
    fp = mesh_fingerprint()
    assert fp.startswith("mesh:")
    assert fp.endswith(f":{N_DEV}")


def test_warm_pack_fingerprint_includes_mesh_topology():
    from spark_rapids_tpu.runtime import warm_pack
    assert mesh_fingerprint() in warm_pack._fingerprint()


# ---------------------------------------------------------------------
# planning: the exchange+consumer group becomes one SpmdStageExec
# ---------------------------------------------------------------------
def test_plan_groups_exchange_and_final_agg_into_stage():
    s = _fused()
    df = s.create_dataframe({"k": pa.array([1, 2, 3], pa.int64()),
                             "v": pa.array([4, 5, 6], pa.int64())})
    root, _ = df.group_by("k").agg(F.sum("v").alias("sv"))._execute()
    stages = [n for n in _walk(root) if isinstance(n, SpmdStageExec)]
    assert len(stages) == 1
    kinds = [type(m).__name__ for m in stages[0].members]
    assert "MeshExchangeExec" in kinds
    assert "HashAggregateExec" in kinds
    # the exchange lives INSIDE the stage, not as a plan-tree operator
    bare = [n for n in _walk(root)
            if isinstance(n, MeshExchangeExec)
            and all(n not in st_.members for st_ in stages)]
    assert not bare


# ---------------------------------------------------------------------
# byte parity: fused vs round-based vs host
# ---------------------------------------------------------------------
def test_groupby_parity_three_paths_int_keys():
    rng = np.random.default_rng(21)
    n = 4096
    data = {"k": pa.array(rng.integers(0, 200, n).astype(np.int64)),
            "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64))}
    fq, rq = _parity_three_paths(data)
    assert _msum(fq, "spmdStages") >= 1
    assert _msum(fq, "spmdDegraded") == 0
    assert _msum(fq, "collectiveBytes") > 0
    # the round-based path reports its per-round dispatches instead
    assert _msum(rq, "meshRounds") >= 1
    assert _msum(rq, "spmdStages") == 0


def test_groupby_parity_string_heavy_with_nulls():
    rng = np.random.default_rng(22)
    n = 1536
    pool = ["alpha", "beta-longer-key-material", "", None, "gamma",
            "delta" * 12, "x"]
    keys = [pool[int(i)] for i in rng.integers(0, len(pool), n)]
    data = {"k": pa.array(keys, pa.string()),
            "v": pa.array(rng.integers(0, 100, n).astype(np.int64))}
    fq, _ = _parity_three_paths(data, [F.sum("v").alias("sv"),
                                       F.count("v").alias("c")])
    assert _msum(fq, "spmdStages") >= 1


def test_groupby_parity_empty_shards():
    """Fewer distinct keys than devices: most shards receive nothing
    and must emit nothing (and a 3-row input exercises the degenerate
    tiny-stage path)."""
    data = {"k": pa.array([7, 7, 9], pa.int64()),
            "v": pa.array([1, 2, 3], pa.int64())}
    fq, _ = _parity_three_paths(data, [F.sum("v").alias("sv")])
    assert _msum(fq, "spmdStages") >= 1


def test_groupby_parity_skewed_keys():
    rng = np.random.default_rng(23)
    n = 6000
    k = np.where(rng.random(n) < 0.97, 0, rng.integers(1, 50, n))
    data = {"k": pa.array(k.astype(np.int64)),
            "v": pa.array(rng.integers(0, 1000, n).astype(np.int64))}
    fq, _ = _parity_three_paths(data)
    assert _msum(fq, "spmdStages") >= 1


def _tpch_frames(s, sf=0.003):
    return {name: s.create_dataframe(gen(sf=sf, seed=seed))
            for name, gen, seed in (("lineitem", tpch.gen_lineitem, 7),
                                    ("orders", tpch.gen_orders, 8),
                                    ("customer", tpch.gen_customer, 9))}


def _canon(tbl):
    """Rows sorted by every column: the three paths shard rows
    differently, so content, not order, is the parity contract."""
    if tbl.num_rows <= 1:
        return tbl
    return tbl.take(pc.sort_indices(
        tbl, sort_keys=[(name, "ascending") for name in tbl.column_names]))


def _q6_shape(lineitem):
    """TPC-H Q6's predicate stack feeding a grouped revenue sum (plain
    Q6 is a global reduction with no exchange to fuse, so this groups by
    return flag to route the same filter+agg through the mesh)."""
    d = decimal.Decimal
    return (lineitem.filter(
                (col("l_shipdate") >= 8766) & (col("l_shipdate") < 9131)
                & (col("l_discount") >= lit(d("0.05")))
                & (col("l_discount") <= lit(d("0.07")))
                & (col("l_quantity") < lit(d("24"))))
            .group_by("l_returnflag")
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def _q3_shape(customer, orders, lineitem):
    """TPC-H Q3 without its top-10 tail (ties at the limit would make
    parity across paths depend on row order)."""
    d = decimal.Decimal
    rev = col("l_extendedprice") * (lit(d("1")) - col("l_discount"))
    return (customer.filter(col("c_mktsegment") == lit("BUILDING"))
            .join(orders.with_column("c_custkey", col("o_custkey")),
                  on=["c_custkey"], how="inner")
            .filter(col("o_orderdate") < 9204)
            .with_column("l_orderkey", col("o_orderkey"))
            .join(lineitem, on=["l_orderkey"], how="inner")
            .filter(col("l_shipdate") > 9204)
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(rev).alias("revenue")))


def test_q6_shape_parity_three_paths():
    def run(s):
        return _canon(_q6_shape(_tpch_frames(s)["lineitem"]).to_arrow())
    want = run(_host())
    assert run(_round()).equals(want)
    assert run(_fused()).equals(want)
    assert want.num_rows > 0


def test_q3_shape_parity_three_paths():
    def run(s):
        d = _tpch_frames(s)
        q = _q3_shape(d["customer"], d["orders"], d["lineitem"])
        tbl = _canon(q.to_arrow())
        return tbl, q
    want, _ = run(_host())
    got_r, _ = run(_round())
    assert got_r.equals(want)
    got_f, fq = run(_fused())
    assert got_f.equals(want)
    assert _msum(fq, "spmdStages") >= 1
    assert want.num_rows > 0


# ---------------------------------------------------------------------
# program counts: one compiled program per stage, warm rerun compiles 0
# ---------------------------------------------------------------------
def _distinct_groupby(s):
    # column names chosen to be unique to this test so the process-
    # global program cache cannot already hold the stage program
    rng = np.random.default_rng(31)
    n = 2048
    df = s.create_dataframe({
        "zz_spmd_key": pa.array(rng.integers(0, 64, n).astype(np.int64)),
        "zz_spmd_val": pa.array(rng.integers(0, 500, n).astype(np.int64)),
    })
    return df.group_by("zz_spmd_key").agg(
        F.sum("zz_spmd_val").alias("s"),
        F.max("zz_spmd_val").alias("m"))


def test_one_program_per_stage_and_zero_on_warm_rerun():
    s = _fused()
    drain_compile_events()
    out1 = _distinct_groupby(s).to_arrow()
    cold = drain_compile_events()
    spmd_cold = [e for e in cold
                 if e["program"].startswith("SpmdStageExec")]
    # exchange + final agg fused: exactly ONE program for the stage,
    # and the round-based per-round program was never built
    assert len(spmd_cold) == 1, cold
    assert not any(e["program"].startswith("MeshExchangeExec")
                   for e in cold), cold
    # warm rerun: fresh plan, same topology -> served from the
    # mesh-keyed cache without compiling anything
    out2 = _distinct_groupby(s).to_arrow()
    warm = [e for e in drain_compile_events()
            if e["program"].startswith("SpmdStageExec")]
    assert warm == [], warm
    assert _to_map(out2) == _to_map(out1)


def test_cache_misses_across_mesh_topologies():
    s8 = _fused()
    _distinct_groupby(s8).to_arrow()     # ensure the 8-device program
    drain_compile_events()
    s4 = st.TpuSession(_conf(**{"mesh.devices": 4}))
    out4 = _distinct_groupby(s4).to_arrow()
    ev = [e for e in drain_compile_events()
          if e["program"].startswith("SpmdStageExec")]
    # a different mesh shape is a different program-cache key: the
    # 4-device run cannot reuse the 8-device executable
    assert len(ev) >= 1, ev
    assert _to_map(out4) == _to_map(_distinct_groupby(_host()).to_arrow())


# ---------------------------------------------------------------------
# AQE mesh re-shard: on by default, off by conf
# ---------------------------------------------------------------------
def test_aqe_reshard_shrinks_active_axis_for_tiny_stage():
    from spark_rapids_tpu.plan.aqe import aqe_stats
    before = aqe_stats()["mesh_reshards"]
    data = {"k": pa.array(np.arange(64, dtype=np.int64)),
            "v": pa.array(np.arange(64, dtype=np.int64))}
    fq = _groupby(_fused(), data, [F.sum("v").alias("sv")])
    out = fq.to_arrow()
    assert aqe_stats()["mesh_reshards"] >= before + 1
    active = max(m.get("spmdActiveShards", 0)
                 for m in fq.last_metrics().values())
    assert 1 <= active < N_DEV
    assert _to_map(out) == {int(i): (int(i),) for i in range(64)}


def test_aqe_reshard_disabled_keeps_full_axis():
    from spark_rapids_tpu.plan.aqe import aqe_stats
    before = aqe_stats()["mesh_reshards"]
    data = {"k": pa.array(np.arange(64, dtype=np.int64)),
            "v": pa.array(np.arange(64, dtype=np.int64))}
    fq = _groupby(_fused(**{"mesh.spmdStage.reshard.enabled": "false"}),
                  data, [F.sum("v").alias("sv")])
    out = fq.to_arrow()
    assert aqe_stats()["mesh_reshards"] == before
    assert all("spmdActiveShards" not in m
               for m in fq.last_metrics().values())
    assert _to_map(out) == {int(i): (int(i),) for i in range(64)}


# ---------------------------------------------------------------------
# fault degradation: mesh.collective -> round-based, counted + parity
# ---------------------------------------------------------------------
def test_collective_fault_degrades_to_round_based_with_parity():
    rng = np.random.default_rng(41)
    n = 3000
    data = {"k": pa.array(rng.integers(0, 80, n).astype(np.int64)),
            "v": pa.array(rng.integers(0, 1000, n).astype(np.int64))}
    want = _to_map(_groupby(_host(), data).to_arrow())
    faults.clear_plan()
    faults.reset_recovery_stats()
    faults.install_plan(
        "mesh.collective:prob=1.0:times=1:bg=0:raise=FetchFailed")
    try:
        fq = _groupby(_fused(), data)
        got = _to_map(fq.to_arrow())
    finally:
        trace = faults.injection_trace()
        faults.clear_plan()
    assert got == want
    assert any(t["point"] == "mesh.collective" for t in trace), trace
    assert _msum(fq, "spmdDegraded") >= 1
    assert faults.recovery_stats().get("degradations", 0) >= 1


def test_stage_budget_overflow_degrades_with_parity():
    """A stage whose staged bytes exceed mesh.spmdStage.maxBytes must
    fall back to the bounded-memory round-based exchange."""
    rng = np.random.default_rng(42)
    n = 2048
    data = {"k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
            "v": pa.array(rng.integers(0, 100, n).astype(np.int64))}
    want = _to_map(_groupby(_host(), data).to_arrow())
    fq = _groupby(_fused(**{"mesh.spmdStage.maxBytes": 1}), data)
    assert _to_map(fq.to_arrow()) == want
    assert _msum(fq, "spmdDegraded") >= 1
    assert _msum(fq, "meshRounds") >= 1


# ---------------------------------------------------------------------
# cancellation mid-stage: permits/leases/handles back under the ledger
# ---------------------------------------------------------------------
def _dozy(pdf: pd.DataFrame) -> pd.DataFrame:
    time.sleep(0.4)
    return pdf


def test_cancel_mid_stage_releases_all_resources():
    from spark_rapids_tpu.memory.diagnostics import leak_report
    from spark_rapids_tpu.memory.host import host_manager, staging_pool
    from spark_rapids_tpu.runtime import ledger as _ledger
    from spark_rapids_tpu.service.query_manager import (QueryCancelled,
                                                        QueryState)
    s = _fused()
    rng = np.random.default_rng(43)
    n = 2048
    df = s.create_dataframe({
        "k": pa.array(rng.integers(0, 10, n).astype(np.int64)),
        "v": pa.array(rng.integers(0, 100, n).astype(np.int64))})

    def mk():
        # fresh tree per run: staged handles cache on the stage instance
        return (df.map_in_pandas(_dozy, [("k", dt.INT64),
                                         ("v", dt.INT64)])
                .group_by("k").agg(F.sum("v").alias("sv")))

    ref = mk().to_arrow()                # warm pools + programs
    assert ref.num_rows == 10
    base = {"leaks": leak_report(),
            "host_reserved": host_manager().reserved,
            "staging_held": staging_pool().held_bytes,
            "sem_available": s._semaphore._available}
    h = mk().submit()
    time.sleep(0.2)                      # mid map drain / staging
    assert h.cancel("spmd leak probe")
    with pytest.raises(QueryCancelled, match="spmd leak probe"):
        h.result(timeout=60)
    assert h.state == QueryState.CANCELLED
    after = leak_report()
    assert after["openHandles"] == base["leaks"]["openHandles"]
    assert after["deviceReservedBytes"] == \
        base["leaks"]["deviceReservedBytes"]
    assert host_manager().reserved == base["host_reserved"]
    assert staging_pool().held_bytes == base["staging_held"]
    sem = s._semaphore
    assert sem._available == base["sem_available"]
    assert sem._available == sem._permits
    lg = _ledger.ledger()
    assert lg is not None                # conftest arms SRTPU_LEDGER
    rep = lg.report()
    assert rep.get("balanceOk", True), rep


# ---------------------------------------------------------------------
# tpulint: shard_map programs in exec/ must key on mesh topology
# ---------------------------------------------------------------------
_LINT_BAD = """
import jax
from jax.experimental.shard_map import shard_map

def launch(mesh, fn, specs):
    step = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
    return jax.jit(step)
"""

_LINT_GOOD = """
import jax
from jax.experimental.shard_map import shard_map
from spark_rapids_tpu.runtime.program_cache import cached_program
from spark_rapids_tpu.parallel.mesh import mesh_topology_key

def launch(mesh, fn, specs, n, axis):
    step = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
    return cached_program(step, cls="X", tag="t",
                          key=(mesh_topology_key(n, axis),))
"""


def test_lint_mesh_program_key_fires_on_unkeyed_shard_map():
    from spark_rapids_tpu.analysis.lint_rules import lint_source
    rules = [v.rule for v in lint_source(_LINT_BAD, "exec/snippet.py")]
    assert "mesh-program-key" in rules


def test_lint_mesh_program_key_clean_when_topology_keyed():
    from spark_rapids_tpu.analysis.lint_rules import lint_source
    rules = [v.rule for v in lint_source(_LINT_GOOD, "exec/snippet.py")]
    assert "mesh-program-key" not in rules


def test_lint_mesh_program_key_scoped_to_exec():
    from spark_rapids_tpu.analysis.lint_rules import lint_source
    rules = [v.rule for v in lint_source(_LINT_BAD, "runtime/other.py")]
    assert "mesh-program-key" not in rules
