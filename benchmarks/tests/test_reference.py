"""The plain references, the comparison, and the control that has to fail."""
import decimal
import importlib

import pyarrow as pa
import pytest

from benchmarks import datagen
from benchmarks.datagen import common as gen
from benchmarks.harness import compare
from benchmarks.reference import common

D = decimal.Decimal


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(["lineitem", "orders", "customer"], 0.02, 2**31 + 5)


def test_same_seed_same_tables_and_other_seed_other():
    a = datagen.generate(["lineitem"], 0.001, 2**31 + 9)["lineitem"]
    assert a.equals(datagen.generate(["lineitem"], 0.001, 2**31 + 9)["lineitem"])
    assert not a.equals(datagen.generate(["lineitem"], 0.001, 8)["lineitem"])


def test_unscaled_round_trip():
    col = gen.decimal_from_unscaled(
        pa.array([-5, 0, 123456]).to_numpy(), 12, 2)
    assert col.to_pylist() == [D("-0.05"), D("0.00"), D("1234.56")]
    assert list(datagen.unscaled(col)) == [-5, 0, 123456]


@pytest.mark.parametrize("total,count,p,s,want", [
    (100, 3, 12, 2, 333333),        # 1.00 / 3 = 0.333333
    (200, 3, 12, 2, 666667),        # 2.00 / 3 rounds up
    (1, 2, 4, 2, 5000),             # 0.01 / 2 = 0.005000
    (-200, 3, 12, 2, -666667),      # HALF_UP is away from zero
    (5, 1000000, 12, 2, 0),         # 0.00000005 -> 0.000000
    (50, 1000000, 12, 2, 1),        # 0.0000005 is a tie at scale 6: up
])
def test_spark_avg(total, count, p, s, want):
    assert common.spark_avg(total, count, p, s) == want


def test_q1_q6_against_python_decimals(tables):
    """A second, slower reckoning in Decimal objects, row by row."""
    li = tables["lineitem"].slice(0, 20000)
    rows = li.to_pylist()
    rev = sum(r["l_extendedprice"] * r["l_discount"] for r in rows
              if 8766 <= r["l_shipdate"] < 9131
              and D("0.05") <= r["l_discount"] <= D("0.07")
              and r["l_quantity"] < 24)
    q6 = importlib.import_module("benchmarks.reference.q6")
    assert q6.reference({"lineitem": li}).rows == [(int(rev.scaleb(4)),)]
    q1 = importlib.import_module("benchmarks.reference.q1")
    got = {r[:2]: r for r in q1.reference({"lineitem": li}).rows}
    keep = [r for r in rows if r["l_shipdate"] <= 10471
            and (r["l_returnflag"], r["l_linestatus"]) == ("N", "O")]
    assert keep
    charge = sum(r["l_extendedprice"] * (1 - r["l_discount"]) * (1 + r["l_tax"])
                 for r in keep)
    avg = (sum(r["l_extendedprice"] for r in keep) / len(keep)).quantize(
        D("0.000001"), decimal.ROUND_HALF_UP)
    row = got[("N", "O")]
    assert row[5] == int(charge.scaleb(6)) and row[7] == int(avg.scaleb(6))
    assert row[9] == len(keep)


@pytest.mark.parametrize("query", ["q1", "q3", "q6"])
def test_reference_passes_and_float32_control_fails(tables, query):
    ref = importlib.import_module("benchmarks.reference." + query)
    answer = ref.reference(tables)
    assert answer.rows and all(v is not None for r in answer.rows for v in r)
    assert compare.mismatches(compare.to_table(answer), answer) == 0
    assert compare.mismatches(compare.to_table(ref.control(tables)), answer) > 0


def test_compare_sees_order_scale_and_missing_rows(tables):
    q3 = importlib.import_module("benchmarks.reference.q3")
    answer = q3.reference(tables)
    table = compare.to_table(answer)
    assert compare.mismatches(table.slice(0, 9), answer) > 0
    assert compare.mismatches(table.take([1, 0] + list(range(2, 10))),
                              answer) > 0
    for wrong_type in (pa.decimal128(28, 6), pa.decimal128(38, 4)):
        other = table.set_column(3, "revenue",
                                 table["revenue"].cast(wrong_type))
        assert compare.mismatches(other, answer) > 0
    as_float = table.set_column(
        3, "revenue", table["revenue"].cast(pa.float64()))
    assert compare.mismatches(as_float, answer) > 0
    renamed = table.rename_columns(["a", "b", "c", "d"])
    assert compare.mismatches(renamed, answer) > 0


def test_rows_whose_order_by_keys_tie_may_swap():
    answer = common.Answer(["k", "v"], [None, None],
                           [(1, 5), (2, 5), (3, 4)], order_by=[(1, False)])
    swapped = pa.table({"k": [2, 1, 3], "v": [5, 5, 4]})
    assert compare.mismatches(swapped, answer) == 0
    assert compare.mismatches(pa.table({"k": [3, 1, 2], "v": [4, 5, 5]}),
                              answer) > 0


def test_a_tie_across_the_limit_admits_any_of_the_tied_rows():
    """LIMIT 2 where rows 2, 3 and 4 tie on the ORDER BY key."""
    answer = common.Answer(["k", "v"], [None, None],
                           [(1, 9), (2, 5), (3, 5), (4, 5)],
                           order_by=[(1, False)], limit=2)
    for second in (2, 3, 4):
        assert compare.mismatches(
            pa.table({"k": [1, second], "v": [9, 5]}), answer) == 0
    assert compare.mismatches(pa.table({"k": [1, 5], "v": [9, 5]}), answer) > 0
    assert compare.mismatches(pa.table({"k": [2, 3], "v": [5, 5]}), answer) > 0
    assert compare.mismatches(pa.table({"k": [1], "v": [9]}), answer) > 0
    assert compare.mismatches(
        pa.table({"k": [1, 2, 3], "v": [9, 5, 5]}), answer) > 0
    assert compare.to_table(answer).num_rows == 2


def test_q3_reference_keeps_the_rows_tied_with_the_tenth():
    q3 = importlib.import_module("benchmarks.reference.q3")
    import numpy as np
    key = np.arange(12)
    rows = q3._top10(key, np.full(12, 7), np.zeros(12, int),
                     np.r_[np.arange(100, 91, -1), 50, 50, 50], int)
    assert len(rows) == 12 and [r[3] for r in rows[9:]] == [50, 50, 50]


def test_generated_tables_follow_clause_4_2_3(tables):
    import numpy as np
    li, o, c = (tables[t] for t in ("lineitem", "orders", "customer"))
    assert li.num_rows == int(6_001_215 * 0.02) and o.num_rows == 30_000
    assert li.column_names[-1] == "l_comment" and "o_clerk" in o.column_names
    assert "c_comment" in c.column_names and "o_comment" in o.column_names
    okey = o["o_orderkey"].to_numpy()
    assert ((okey - 1) % 32 < 8).all() and (np.diff(okey) > 0).all()
    lkey = li["l_orderkey"].to_numpy()
    _, lines = np.unique(lkey, return_counts=True)
    assert lines.min() >= 1 and lines.max() <= 7 and len(lines) == o.num_rows
    at = np.searchsorted(okey, lkey)
    ship, odate = li["l_shipdate"].to_numpy(), o["o_orderdate"].to_numpy()[at]
    assert ((ship - odate >= 1) & (ship - odate <= 121)).all()
    receipt = li["l_receiptdate"].to_numpy()
    flag = np.array(li["l_returnflag"].to_pylist())
    assert ((flag == "N") == (receipt > gen.CURRENTDATE)).all()
    assert ((np.array(li["l_linestatus"].to_pylist()) == "O")
            == (ship > gen.CURRENTDATE)).all()
    assert (o["o_custkey"].to_numpy() % 3 != 0).all()
    assert (o["o_shippriority"].to_numpy() == 0).all()
    lens = np.array([len(x) for x in li["l_comment"].to_pylist()])
    assert lens.min() >= 10 and lens.max() <= 43 and 25 < lens.mean() < 28
