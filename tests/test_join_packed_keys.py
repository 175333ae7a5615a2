"""Equi-joins on several fixed-width keys packed into one 64-bit word
(exec/join.py:_pack_ranges, _key_word): every join type against a plain
Python reference, and which path ran, as `joinPackedKeys` says (the
number of keys packed, 0 where the generic combined sort ran)."""
import datetime
import random
from collections import Counter
from decimal import Decimal

import pyarrow as pa
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu.expr.expressions import col

HOWS = ("inner", "left", "left_semi", "left_anti", "right", "full")
DAY0 = datetime.date(1995, 1, 1)


@pytest.fixture(scope="module")
def session():
    # 128-row batches: the stream side probes the build side in several
    # batches, against one build sort
    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128})
    yield s
    s.stop()


def _draw(rng, draw, n, null_prob):
    return [None if rng.random() < null_prob else draw(rng)
            for _ in range(n)]


def _side(rng, types, draws, n, null_prob=0.1):
    keys = [_draw(rng, d, n, null_prob) for d in draws]
    vals = [rng.randint(0, 100) for _ in range(n)]
    return [pa.array(k, t) for k, t in zip(keys, types)] + \
        [pa.array(vals, pa.int64())]


def _ints(lo, hi):
    return lambda rng: rng.randint(lo, hi)


def _case(name, seed):
    """(key types, left columns, right columns, residual condition?,
    keys the packed path packs: 0 where it must not)."""
    rng = random.Random(seed)
    if name == "int32_int64":
        # negative keys; the int64 key spans 2e12, past the direct table
        types = [pa.int32(), pa.int64()]
        draws = [_ints(-6, 5), lambda r: r.choice(
            [-10 ** 12, -7, 0, 3, 10 ** 12])]
        return types, _side(rng, types, draws, 400), \
            _side(rng, types, draws, 60), False, 2
    if name == "date_int64":
        types = [pa.date32(), pa.int64()]
        draws = [lambda r: DAY0 + datetime.timedelta(r.randint(0, 9)),
                 _ints(-3, 20)]
        return types, _side(rng, types, draws, 400), \
            _side(rng, types, draws, 80), False, 2
    if name == "three_keys":
        types = [pa.int16(), pa.bool_(), pa.decimal128(12, 2)]
        draws = [_ints(-2, 4), lambda r: r.random() < 0.5,
                 lambda r: Decimal(r.randint(-300, 300)) / 100]
        left = _side(rng, types, draws, 400)
        right = _side(rng, types, draws, 120)
        return types, left, right, False, 3
    if name == "out_of_range_alias":
        # build (1, 9), (2, 0): a naive pack of stream (1, 10) reads
        # (2, 0)'s word; (0, 0) and (3, 5) lie outside the first key's
        types = [pa.int64(), pa.int64()]
        left = [pa.array([1, 1, 2, 2, 0, 3, 1, None], pa.int64()),
                pa.array([10, 9, 0, -1, 0, 5, None, 0], pa.int64()),
                pa.array(list(range(8)), pa.int64())]
        right = [pa.array([1, 2], pa.int64()), pa.array([9, 0], pa.int64()),
                 pa.array([50, 60], pa.int64())]
        return types, left, right, False, 2
    if name == "duplicate_build_pairs":
        types = [pa.int32(), pa.int32()]
        draws = [_ints(0, 3), _ints(0, 3)]
        return types, _side(rng, types, draws, 300), \
            _side(rng, types, draws, 40, 0.05), False, 2
    if name == "residual_condition":
        types = [pa.int64(), pa.int32()]
        draws = [_ints(0, 7), _ints(-4, 4)]
        return types, _side(rng, types, draws, 300), \
            _side(rng, types, draws, 70), True, 2
    if name == "empty_build":
        types = [pa.int64(), pa.int64()]
        left = _side(rng, types, [_ints(0, 5), _ints(0, 5)], 200)
        return types, left, [pa.array([], t) for t in types] + \
            [pa.array([], pa.int64())], False, 0
    if name == "spans_past_63_bits":
        # each span is 2^40 + 1: their product passes 2^63 - 1
        types = [pa.int64(), pa.int64()]
        draws = [lambda r: r.choice([0, 1, 1 << 40]),
                 lambda r: r.choice([-(1 << 39), 5, 1 << 39])]
        return types, _side(rng, types, draws, 300), \
            _side(rng, types, draws, 50), False, 0
    if name == "string_key":
        # a key wider than one int64 keeps the combined sort
        types = [pa.string(), pa.int64()]
        draws = [lambda r: r.choice(["a", "bb", "ccc"]), _ints(0, 4)]
        return types, _side(rng, types, draws, 200), \
            _side(rng, types, draws, 30), False, 0
    raise AssertionError(name)


CASES = ["int32_int64", "date_int64", "three_keys", "out_of_range_alias",
         "duplicate_build_pairs", "residual_condition", "empty_build",
         "spans_past_63_bits", "string_key"]


def _reference(lrows, rrows, how, nk, cond):
    def match(a, b):
        return all(a[i] is not None and a[i] == b[i] for i in range(nk)) \
            and (not cond or a[nk] < b[nk])
    width_l, width_r = nk + 1, nk + 1
    out, rmatched = [], [False] * len(rrows)
    for lr in lrows:
        hits = [j for j, rr in enumerate(rrows) if match(lr, rr)]
        for j in hits:
            rmatched[j] = True
            if how in ("inner", "left", "right", "full"):
                out.append(lr + rrows[j])
        if not hits and how in ("left", "full"):
            out.append(lr + (None,) * width_r)
        if (how == "left_semi") == bool(hits) and how in ("left_semi",
                                                           "left_anti"):
            out.append(lr)
    if how in ("right", "full"):
        out += [(None,) * width_l + rr
                for j, rr in enumerate(rrows) if not rmatched[j]]
    return Counter(out)


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case", CASES)
def test_packed_key_join_equals_reference(session, case, how):
    types, lcols, rcols, cond, packs = _case(case, CASES.index(case) + 71)
    nk = len(types)
    lnames = [f"k{i}" for i in range(nk)] + ["lv"]
    rnames = [f"r{i}" for i in range(nk)] + ["rv"]
    dl = session.create_dataframe(pa.table(lcols, names=lnames))
    dr = session.create_dataframe(pa.table(rcols, names=rnames))
    on = col("k0") == col("r0")
    for i in range(1, nk):
        on = on & (col(f"k{i}") == col(f"r{i}"))
    if cond:
        on = on & (col("lv") < col("rv"))
    df = dl.join(dr, on=on, how=how)
    out = df.to_arrow()
    got = Counter(zip(*[c.to_pylist() for c in out.columns]))
    rows = [list(zip(*[c.to_pylist() for c in cols]))
            for cols in (lcols, rcols)]
    assert got == _reference(*rows, how, nk, cond)
    joins = [m for m in df.last_metrics().values()
             if "joinPackedKeys" in m]
    assert [int(m["joinPackedKeys"]) for m in joins] == [packs]
    # a packed tuple is one 64-bit word of key: 2 words a row
    if packs:
        assert [int(m["joinKeyWords"]) for m in joins] == [2]
