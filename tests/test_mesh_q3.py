"""TPC-H q3 over tables that cache() row-shards over a mesh
(mesh.devices > 1): placement, the answer against the benchmark's plain
reference, and that no program is compiled once a device — the steps of the
co-partitioned joins and of the map sides run as ONE program over the mesh
(parallel/mesh_program.py, exec/lockstep.py)."""
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import datagen  # noqa: E402
from benchmarks.harness import compare  # noqa: E402
from benchmarks.reference import q3 as reference_q3  # noqa: E402

import spark_rapids_tpu as st  # noqa: E402
from spark_rapids_tpu.profiler import xla_stats  # noqa: E402
from spark_rapids_tpu.runtime import program_cache  # noqa: E402
from spark_rapids_tpu.workloads import tpch  # noqa: E402

P = "spark.rapids.tpu."
SF = 0.02
TABLES = ("customer", "lineitem", "orders")
# programs a mesh plan may compile beyond the one-device plan's count: the
# five stage programs, their five cuts, and as many again of room
STAGE_PROGRAMS_AND_MARGIN = 20


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(list(TABLES), SF, 2910)


def _session(mesh, **extra):
    """Broadcast off (-1), so that both of q3's joins exchange their inputs
    at this scale as the planner's defaults make them at SF1."""
    conf = {P + "mesh.devices": mesh,
            P + "mesh.spmdStage.maxBytes": 4 << 30,
            P + "sql.autoBroadcastJoinThreshold": -1}
    conf.update(extra)
    return st.TpuSession(conf)


def _cached(session, tables):
    return {k: session.create_dataframe(v).cache() for k, v in tables.items()}


def _metric(df, key):
    return sum(int(m.get(key, 0)) for m in df.last_metrics().values())


def _nodes(node, seen=None):
    """Every operator once (a stage's members share its children)."""
    seen = set() if seen is None else seen
    if id(node) in seen:
        return
    seen.add(id(node))
    yield node
    for c in list(node.children) + list(getattr(node, "members", [])):
        yield from _nodes(c, seen)


@pytest.fixture(scope="module")
def one_device_answer(tables):
    return tpch.queries()[3](_cached(_session(0), tables)).to_arrow()


@pytest.mark.parametrize("mesh", [2, 4])
def test_q3_sharded_equals_reference_and_one_device(tables, mesh,
                                                    one_device_answer):
    program_cache.drain_compile_events()
    df = tpch.queries()[3](_cached(_session(mesh), tables))
    got = df.to_arrow()
    assert compare.mismatches(got, reference_q3.reference(tables)) == 0
    assert got.equals(one_device_answer)
    # both joins' inputs and the group-by crossed the mesh in fused stages
    assert _metric(df, "spmdStages") == 5
    assert _metric(df, "spmdDegraded") == 0
    assert _metric(df, "meshRounds") == 0
    assert _metric(df, "collectiveBytes") > 0
    for what in ("shardRowsReceived", "shardBytesReceived"):
        assert _metric(df, what + "Max") >= _metric(df, what + "Min") > 0
    kinds = [n.kind for n in _nodes(df._last_root)
             if type(n).__name__ == "SpmdStageExec"]
    assert sorted(kinds) == ["agg"] + ["exchange"] * 4
    # the joins ran in lockstep: their steps over the mesh, none a device
    compiled = {e["program"] for e in program_cache.drain_compile_events()}
    assert {"HashJoinExec.meshbuild", "HashJoinExec.meshprobe",
            "HashJoinExec.meshexpand"} <= compiled
    assert not compiled & {"HashJoinExec.buildsort", "HashJoinExec.probe",
                           "HashJoinExec.directbuild", "HashJoinExec.expand"}


def test_cache_shards_rows_evenly_over_the_mesh(tables):
    s = _session(4, **{P + "sql.batchSizeRows": 16384})
    devices = jax.devices()[:4]
    for name in TABLES:
        at = tables[name]
        df = s.create_dataframe(at).cache()
        assert df.cached_devices() == devices
        scan = df._plan
        assert scan.n_shards == 4
        per = len(scan.batches) // 4
        assert per * 4 == len(scan.batches)
        shares = []
        for shard, dev in enumerate(devices):
            mine = scan.batches[shard * per:(shard + 1) * per]
            for b in mine:
                assert b.row_mask.devices() == {dev}
                for leaf in jax.tree_util.tree_leaves(b.cvs()):
                    assert leaf.devices() == {dev}
            shares.append(sum(b.num_rows for b in mine))
            # the same capacities on every shard: one program signature
            assert ([jax.tree_util.tree_map(lambda x: x.shape, b.cvs())
                     for b in mine]
                    == [jax.tree_util.tree_map(lambda x: x.shape, b.cvs())
                        for b in scan.batches[:per]])
        assert sum(shares) == at.num_rows
        assert max(shares) - min(shares) <= 1
        # the shares are slices in row order: the table reads back as it was
        assert df.to_arrow().equals(at)


def test_pruned_views_of_a_sharded_table_copy_nothing(tables):
    s = _session(4)
    df = s.create_dataframe(tables["orders"]).cache()
    leaf = df._plan
    names = [f.name for f in leaf.schema.fields]
    view = leaf.pruned({"o_orderkey", "o_orderdate"})
    assert view is not leaf and view.n_shards == 4
    assert [f.name for f in view.schema.fields] == ["o_orderkey",
                                                    "o_orderdate"]
    for vb, lb in zip(view.batches, leaf.batches):
        assert vb.row_mask is lb.row_mask
        for vi, name in enumerate(["o_orderkey", "o_orderdate"]):
            vcv, lcv = vb.cvs()[vi], lb.cvs()[names.index(name)]
            assert vcv.data is lcv.data and vcv.validity is lcv.validity
            assert vcv.data.devices() == lb.row_mask.devices()
    # and the planned scan is the sharded one, a partition a device
    out = df.select("o_orderkey", "o_orderdate")
    assert out.to_arrow().equals(
        tables["orders"].select(["o_orderkey", "o_orderdate"]))
    scans = [n for n in _nodes(out._last_root)
             if type(n).__name__ == "CachedScanExec"]
    assert [n.n_shards for n in scans] == [4]
    assert "on 4 devices" in scans[0].describe()


@pytest.mark.parametrize("mesh", [0, 1])
def test_cache_without_a_mesh_places_as_before(tables, mesh):
    s = _session(mesh, **{P + "sql.batchSizeRows": 16384})
    at = tables["orders"]
    df = s.create_dataframe(at).cache()
    assert df._plan.n_shards == 0
    assert df.cached_devices() == [jax.devices()[0]]
    assert len(df._plan.batches) == -(-at.num_rows // 16384)
    assert s.create_dataframe(at).cached_devices() == []
    scan = next(n for n in _nodes(_planned(df))
                if type(n).__name__ == "CachedScanExec")
    assert scan.n_shards == 0 and "devices" not in scan.describe()


def _planned(df):
    df.to_arrow()
    return df._last_root


def _first_q3_compiles(mesh, tables):
    """Backend compiles (jax's own count, which sees a program compiled
    again for another device; program_cache's events do not) of the first
    q3 of a process that has compiled nothing, and of the second."""
    program_cache.clear()
    jax.clear_caches()
    dfs = _cached(_session(mesh), tables)
    counts = []
    for _ in range(2):
        before = xla_stats.snapshot()["compiles"]
        tpch.queries()[3](dfs).to_arrow()
        counts.append(xla_stats.snapshot()["compiles"] - before)
    return counts


def test_no_program_is_compiled_once_a_device(tables):
    first0, again0 = _first_q3_compiles(0, tables)
    first2, again2 = _first_q3_compiles(2, tables)
    first4, again4 = _first_q3_compiles(4, tables)
    assert (again0, again2, again4) == (0, 0, 0)
    # the count does not grow with the mesh
    assert first4 <= first2
    assert first4 <= first0 + STAGE_PROGRAMS_AND_MARGIN
