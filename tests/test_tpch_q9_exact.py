"""TPC-H q9 answered in exact decimals (PR 37): the program's q9 against the
benchmark's plain reference (benchmarks/reference/q9.py) through the
comparison that decides `correct`, the float32 control, the 128-bit
arithmetic against Python integers, the four generators of the eight-table
configuration against clause 4.2.3, the counters and the name that mark a
program of 128-bit decimal arithmetic, the two metrics that read the mark,
and the cell rehearsed in a process of its own."""
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow.compute as pc
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import datagen  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.datagen import common as gen  # noqa: E402
from benchmarks.datagen import lineitem as gen_lineitem  # noqa: E402
from benchmarks.datagen import part as gen_part  # noqa: E402
from benchmarks.harness import bytecount, compare, d128, manifest  # noqa: E402
from benchmarks.harness import tracereduce  # noqa: E402
from benchmarks.reference import q9 as reference_q9  # noqa: E402

import spark_rapids_tpu as st  # noqa: E402
from spark_rapids_tpu.columnar import dtypes as dt  # noqa: E402
from spark_rapids_tpu.expr.expressions import (Cast, Expression,  # noqa: E402
                                               d128_nodes)
from spark_rapids_tpu.ops import decimal128 as limbs  # noqa: E402
from spark_rapids_tpu.workloads import tpch  # noqa: E402

from test_decimal128 import pack, unpack  # noqa: E402

CELL = "tpch_sf1_hbm8.q9"
READS = ("lineitem", "nation", "orders", "part", "partsupp", "supplier")
# (scale factor, seed): a seed past 31 bits among them, as the driver's are
CASES = [(0.01, 3701), (0.02, 3702), (0.01, 3000370003)]
CASE_IDS = [f"sf{sf}-seed{seed}" for sf, seed in CASES]


@pytest.fixture(scope="module")
def tables():
    made = {}

    def get(sf, seed):
        if (sf, seed) not in made:
            made[sf, seed] = datagen.generate(list(READS), sf, seed)
        return made[sf, seed]
    return get


@pytest.fixture(scope="module")
def q9_session():
    """The configuration's own conf: nothing may fall to the host."""
    session = st.TpuSession(dict(manifest.cell(ROOT, CELL)["config"]["conf"]))
    yield session
    session.stop()


def _run_q9(session, t):
    df = tpch.queries()[9]({k: session.create_dataframe(v).cache()
                            for k, v in t.items()})
    return df, df.to_arrow()


# ---- the answer -------------------------------------------------------
@pytest.mark.parametrize("sf,seed", CASES, ids=CASE_IDS)
def test_q9_equals_the_plain_reference(q9_session, tables, sf, seed):
    t = tables(sf, seed)
    _, got = _run_q9(q9_session, t)
    want = reference_q9.reference(t)
    assert len(want.rows) > 100        # 25 nations by up to 7 years
    # rows, values, decimal(36,4) and ORDER BY n_name, o_year DESC
    assert compare.mismatches(got, want) == 0
    assert str(got.schema.field("sum_profit").type) == "decimal128(36, 4)"
    assert str(got.schema.field("o_year").type) == "int32"   # Spark's year()
    keys = list(zip(got["n_name"].to_pylist(),
                    [-y for y in got["o_year"].to_pylist()]))
    assert keys == sorted(keys)
    # a right answer out of order is a wrong answer
    assert compare.mismatches(got.slice(1).take(
        list(range(1, got.num_rows - 1)) + [0]), want) > 0


@pytest.mark.parametrize("sf,seed", CASES, ids=CASE_IDS)
def test_float32_control_reads_wrong_and_the_reference_right(tables, sf, seed):
    t = tables(sf, seed)
    want = reference_q9.reference(t)
    assert compare.mismatches(compare.to_table(want), want) == 0
    control = compare.to_table(reference_q9.control(t))
    assert compare.mismatches(control, want) > len(want.rows) // 2
    # a float64 answer rounded to the grid fails on the declared type alone
    doubles = compare.to_table(want).set_column(
        2, "sum_profit", pc.cast(compare.to_table(want)["sum_profit"],
                                 "float64"))
    assert compare.mismatches(doubles, want) == len(want.rows)


def _expressions(obj, seen):
    """Every Expression reachable from a logical plan node."""
    if id(obj) in seen:
        return
    seen.add(id(obj))
    if isinstance(obj, Expression):
        yield obj
        for child in obj.children:
            yield from _expressions(child, seen)
    elif isinstance(obj, (list, tuple)):
        for x in obj:
            yield from _expressions(x, seen)
    elif type(obj).__module__.startswith("spark_rapids_tpu.plan.logical"):
        for x in vars(obj).values():
            yield from _expressions(x, seen)


def _plan_nodes(node):
    yield node
    for child in list(node.children) + list(getattr(node, "members", [])):
        yield from _plan_nodes(child)


def test_q9_holds_no_float_cast_and_no_host_operator(q9_session, tables):
    df, _ = _run_q9(q9_session, tables(*CASES[0]))
    exprs = list(_expressions(df._plan, set()))
    assert any(type(e).__name__ == "Subtract" for e in exprs)
    floats = [e for e in exprs if isinstance(e, Cast) and isinstance(
        getattr(e, "to", getattr(e, "dtype", None)),
        (dt.DoubleType, dt.FloatType))]
    assert not floats
    names = [type(n).__name__ for n in _plan_nodes(df._last_root)]
    assert "HashJoinExec" in names
    assert not {"HostProjectExec", "HostFilterExec"} & set(names)
    assert sum(int(m.get("degradedToHost", 0))
               for m in df.last_metrics().values()) == 0


def test_counters_of_the_128_bit_arithmetic_and_the_two_key_join(
        q9_session, tables, monkeypatch):
    from spark_rapids_tpu.runtime.program_cache import CachedProgram
    launched = []
    call = CachedProgram.__call__

    def spy(prog, *args):
        launched.append(prog.base_key[1:3])
        return call(prog, *args)
    monkeypatch.setattr(CachedProgram, "__call__", spy)
    t = tables(*CASES[1])
    df, _ = _run_q9(q9_session, t)
    # the two-key join packs its pair into one word: no combined sort
    joins = {tag for cls, tag in launched if cls == "HashJoinExec"}
    assert "keyranges" in joins and "count" not in joins
    counted = list(df.last_metrics().values())
    wide = [m for m in counted if m.get("d128Exprs")]
    # ps_supplycost * l_quantity is decimal(25,4), the difference (26,4)
    assert [int(m["d128Exprs"]) for m in wide] == [2]
    assert int(wide[0]["d128Rows"]) == reference_q9.joined_rows(t)
    words = sorted(int(m["joinKeyWords"]) for m in counted
                   if "joinKeyWords" in m)
    assert words == [2, 2, 2, 2, 2]       # four int64 keys, one packed pair
    assert [int(m["joinPackedKeys"]) for m in counted
            if "joinPackedKeys" in m] == [2]
    rendered = df.explain("analyze")
    assert "d128Exprs=2" in rendered and "joinKeyWords=2" in rendered
    assert "joinPackedKeys=2" in rendered and "joinKeyWords=4" not in rendered
    # the program that holds the arithmetic says so in its name
    stage = next(n for n in _plan_nodes(df._last_root) if n.d128_exprs())
    assert type(stage).__name__ == "FusedStageExec"
    assert stage._jit.base_key[1:3] == ("FusedStageExec", "run_d128")
    plain = [n for n in _plan_nodes(df._last_root)
             if type(n).__name__ in ("ProjectExec", "FilterExec")]
    assert [n._jit.base_key[2] for n in plain if n.d128_exprs()] \
        == ["run_d128"]                       # the stage's member, unrun
    assert all(n._jit.base_key[2] == "run" for n in plain
               if not n.d128_exprs())


def test_d128_nodes_counts_operators_not_names_or_leaves():
    from spark_rapids_tpu.columnar.table import Field, Schema
    from spark_rapids_tpu.expr.expressions import col
    schema = Schema([Field("a", dt.DecimalType(12, 2)),
                     Field("b", dt.DecimalType(12, 2)),
                     Field("w", dt.DecimalType(25, 4))])
    narrow = (col("a") + col("b")).bind(schema)             # decimal(13,2)
    product = (col("a") * col("b")).alias("p").bind(schema)  # decimal(25,4)
    compared = (col("w") > col("w")).bind(schema)      # boolean of two wide
    assert d128_nodes([narrow]) == 0
    assert d128_nodes([product]) == 1
    assert d128_nodes([compared, col("w").bind(schema)]) == 1
    assert d128_nodes([(col("w") - col("a") * col("b")).bind(schema)]) == 2


# ---- the limb arithmetic against Python integers ----------------------
def _extremes(precision):
    top = 10 ** precision - 1
    return [0, 1, -1, top, -top, top // 2, -(top // 3), 100, -100]


def test_dec_mul_scaled_12_2_by_12_2_is_exact():
    """ps_supplycost * l_quantity: decimal(25,4), no rescale."""
    rng = np.random.default_rng(37)
    a = _extremes(12) + [int(x) for x in rng.integers(-10**12, 10**12, 200)]
    b = list(reversed(_extremes(12))) + [
        int(x) for x in rng.integers(-10**12, 10**12, 200)]
    got, overflow = limbs.dec_mul_scaled(pack(a), pack(b), 0, 25)
    assert unpack(got) == [x * y for x, y in zip(a, b)]
    assert not np.asarray(overflow).any()


def test_dec_sub_18_4_less_25_4_is_exact():
    """amount: decimal(18,4) - decimal(25,4) = decimal(26,4), negative
    amounts and both precisions' extremes included."""
    rng = np.random.default_rng(38)
    a = _extremes(18) + [int(x) for x in rng.integers(-10**18, 10**18, 200)]
    b = [x * 10**7 + 3 for x in reversed(_extremes(18))] + [
        int(x) * 9_999_999 for x in rng.integers(-10**18, 10**18, 200)]
    assert max(map(abs, b)) < 10**25
    got, overflow = limbs.dec_sub(pack(a), pack(b))
    want = [x - y for x, y in zip(a, b)]
    assert unpack(got) == want and min(want) < 0 < max(want)
    assert not np.asarray(overflow).any()
    assert np.asarray(limbs.fits_precision(limbs.to_limbs(got), 26)).all()


# ---- the generators against clause 4.2.3 ------------------------------
SF_GEN, SEED_GEN = 0.02, 3000370009


@pytest.fixture(scope="module")
def generated(tables):
    return tables(SF_GEN, SEED_GEN)


def test_partsupp_has_four_distinct_suppliers_a_part(generated):
    ps, parts = generated["partsupp"], generated["part"].num_rows
    assert ps.num_rows == 4 * parts == manifest.cardinality(
        manifest.cell(ROOT, CELL)["config"], SF_GEN)["partsupp"]
    pk, sk = ps["ps_partkey"].to_numpy(), ps["ps_suppkey"].to_numpy()
    assert (pk == np.repeat(np.arange(1, parts + 1), 4)).all()
    assert len(np.unique(pk * 10**6 + sk)) == len(pk)
    suppliers = generated["supplier"].num_rows
    assert sk.min() >= 1 and sk.max() <= suppliers
    cost = gen.unscaled(ps["ps_supplycost"])
    assert cost.min() >= 100 and cost.max() <= 100_000
    assert str(ps.schema.field("ps_supplycost").type) == "decimal128(12, 2)"
    lengths = pc.utf8_length(ps["ps_comment"]).to_numpy()
    assert lengths.min() >= 49 and lengths.max() <= 198


def test_every_lineitem_pair_is_in_partsupp_exactly_once(generated):
    ps, li = generated["partsupp"], generated["lineitem"]
    pairs = ps["ps_partkey"].to_numpy() * 10**6 + ps["ps_suppkey"].to_numpy()
    wanted = li["l_partkey"].to_numpy() * 10**6 + li["l_suppkey"].to_numpy()
    found = np.searchsorted(np.sort(pairs), wanted)
    assert (np.sort(pairs)[np.minimum(found, len(pairs) - 1)] == wanted).all()
    assert len(np.unique(pairs)) == len(pairs)
    # so the five inner joins drop no row the filter kept
    assert reference_q9.joined_rows(generated) == d128.amount_rows(
        generated, "green")


def test_part_names_prices_and_widths(generated):
    p = generated["part"]
    assert p["p_partkey"].to_pylist() == list(range(1, p.num_rows + 1))
    names = [n.split(" ") for n in p["p_name"].to_pylist()]
    assert all(len(n) == 5 and len(set(n)) == 5 for n in names)
    assert {w for n in names for w in n} == set(gen_part.COLOURS)
    assert len(gen_part.COLOURS) == len(set(gen_part.COLOURS)) == 92
    assert max(map(len, p["p_name"].to_pylist())) <= 55
    # 'green' is one word of 92 and a substring of no other
    assert [c for c in gen_part.COLOURS if "green" in c] == ["green"]
    share = pc.match_substring(p["p_name"], "green").to_numpy(
        zero_copy_only=False).mean()
    assert 0.04 < share < 0.07                      # 5 / 92 = 0.054
    assert (gen.unscaled(p["p_retailprice"])
            == gen_lineitem.retail_price(p["p_partkey"].to_numpy())).all()
    li = generated["lineitem"]
    qty = gen.unscaled(li["l_quantity"]) // 100
    assert (gen.unscaled(li["l_extendedprice"]) == qty * gen.unscaled(
        p["p_retailprice"])[li["l_partkey"].to_numpy() - 1]).all()
    assert set(p["p_type"].to_pylist()) <= set(gen_part.TYPES)
    assert set(p["p_container"].to_pylist()) <= set(gen_part.CONTAINERS)
    assert all(b[6] == m[-1] for b, m in zip(p["p_brand"].to_pylist(),
                                             p["p_mfgr"].to_pylist()))


def test_supplier_and_nation(generated):
    s, n = generated["supplier"], generated["nation"]
    assert s["s_suppkey"].to_pylist() == list(range(1, s.num_rows + 1))
    nations = s["s_nationkey"].to_numpy()
    assert nations.min() == 0 and nations.max() == 24
    assert s["s_name"][0].as_py() == "Supplier#000000001"
    assert n.num_rows == 25
    assert n["n_nationkey"].to_pylist() == list(range(25))
    names = n["n_name"].to_pylist()
    assert names[0] == "ALGERIA" and names[24] == "UNITED STATES"
    assert len(set(names)) == 25 and max(map(len, names)) <= 25
    assert sorted(set(n["n_regionkey"].to_pylist())) == [0, 1, 2, 3, 4]
    assert n["n_regionkey"].to_pylist().count(0) == 5


def test_same_seed_same_tables_and_every_column_declared(generated):
    again = datagen.generate(list(READS), SF_GEN, SEED_GEN)
    other = datagen.generate(["part", "partsupp", "supplier"], SF_GEN,
                             SEED_GEN + 1)
    schema = manifest.cell(ROOT, CELL)["config"]["schema"]
    rows = manifest.cardinality(manifest.cell(ROOT, CELL)["config"], SF_GEN)
    for name, table in generated.items():
        assert table.equals(again[name]), name
        assert table.column_names == list(schema[name]), name
        assert table.num_rows == rows[name], name
        for column, declared in schema[name].items():
            bytecount.width(declared, rows)       # a width it knows
    for name, table in other.items():
        assert not table.equals(generated[name]), name


# ---- the mark in a program's name, and the two metrics that read it ----
def _traced(modules):
    """Two executions, 0-100 and 100-200 ns, one chip."""
    trace = {"devices": {"/device:TPU:0": {
        "XLA Ops": [("fusion.1", 10, 30)], "XLA Modules": modules}},
        "host": [("bench.execution", 0, 100), ("bench.execution", 100, 200)]}
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def test_d128_device_ms_reads_the_marked_programs_only():
    run = _traced([("jit_FusedStageExec_run_d128(11)", 10, 30),
                   ("jit_ProjectExec_run_d128(12)", 120, 130),
                   ("jit_FusedStageExec_run(13)", 40, 60),
                   ("jit_d128_like(14)", 70, 80)])
    assert d128.marked("jit_FilterExec_run_d128(5)")
    assert not d128.marked("jit_FusedStageExec_run(5)")
    assert bench_run.read_metric("d128_device_ms", run) \
        == pytest.approx(30 / 1e6 / 2)
    none = _traced([("jit_FusedStageExec_run(13)", 40, 60)])
    for metric in ("d128_device_ms", "d128_roofline"):
        assert bench_run.read_metric(metric, dict(none, peaks=None)) is None
        assert bench_run.read_metric(
            metric, dict(run, trace=None, peaks=None)) is None


def test_d128_roofline_counts_from_the_generated_tables(generated,
                                                        monkeypatch):
    cell = manifest.cell(ROOT, CELL)
    rows = manifest.cardinality(cell["config"], SF_GEN)
    least = d128.least_bytes(9, cell["config"]["schema"], rows, generated)
    assert least == reference_q9.joined_rows(generated) * (8 + 4 + 8 + 8)
    assert d128.least_bytes(3, cell["config"]["schema"], rows, generated) \
        is None
    run = dict(_traced([("jit_FusedStageExec_run_d128(11)", 10, 30)]),
               config=cell["config"], cardinality=rows,
               queries=[{"query": 9}], peaks={"hbm_bytes_per_s": 819e9})
    # a process that was not started as a run of a cell: nothing to count
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert bench_run.read_metric("d128_roofline", run) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL, "--seed",
                                      str(SEED_GEN), "--trace", "1",
                                      "--rehearse", "--sf", str(SF_GEN)])
    again = d128.run_tables(run)
    assert again["part"].equals(generated["part"])
    assert again["lineitem"]["l_partkey"].equals(
        generated["lineitem"]["l_partkey"])
    assert bench_run.read_metric("d128_roofline", run) == pytest.approx(
        100.0 * least / 819e9 / (10 / 1e9))
    assert bench_run.read_metric(
        "d128_roofline", dict(run, queries=[{"query": 3}])) is None


# ---- the cell, rehearsed in a process of its own ----------------------
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsed_in_a_process_of_its_own(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000370021", "--seconds", "1",
         "--trace", str(trace), "--rehearse", "--sf", "0.01"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert result["correct"] is False            # off the chip
    assert result["rehearsal"] == {"correct_off_the_chip": True}
    assert all(c["value"] == 0 == c["limit"]
               for c in result["compared"].values())
    assert result["failed"] == 0 and result["attempted"] >= 1
    if not trace:
        assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
        bench = manifest.load(ROOT)
        assert {m["name"] for m in manifest.metrics_of(
            bench, CELL, "end_to_end")} == {"rows_per_s", "setup_s"}
        per_layer = {m["name"] for m in manifest.metrics_of(
            bench, CELL, "per_layer")}
        assert {"d128_device_ms", "d128_roofline"} <= per_layer
