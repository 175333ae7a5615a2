"""Aggregation correctness: ungrouped and grouped vs Python reference."""
import math
from collections import defaultdict

import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.expr.expressions import col

from asserts import assert_rows_equal, indexed_rows
from data_gen import (BooleanGen, DoubleGen, IntegerGen, LongGen, StringGen,
                      gen_df)


def _py_rows(at):
    cols = [at.column(i).to_pylist() for i in range(at.num_columns)]
    return list(zip(*cols))


def test_ungrouped_agg(session):
    df, at = gen_df(session, [("a", IntegerGen(lo=-10**6, hi=10**6)),
                              ("b", DoubleGen(no_special=True))],
                    n=5000, seed=10)
    out = df.agg(F.sum("a").alias("sa"), F.count("a").alias("ca"),
                 F.count("*").alias("n"), F.min("a").alias("mina"),
                 F.max("b").alias("maxb"), F.avg("a").alias("avga"))
    rows = _py_rows(at)
    avals = [r[0] for r in rows if r[0] is not None]
    bvals = [r[1] for r in rows if r[1] is not None]
    exp = [(sum(avals), len(avals), len(rows), min(avals), max(bvals),
            sum(avals) / len(avals))]
    assert_rows_equal(out.to_arrow(), exp)


def test_ungrouped_agg_all_null(session):
    df = session.create_dataframe(
        {"a": __import__("pyarrow").array([None, None], type=
                                          __import__("pyarrow").int32())})
    out = df.agg(F.sum("a").alias("s"), F.count("a").alias("c"),
                 F.min("a").alias("m")).to_arrow().to_pydict()
    assert out["s"] == [None]
    assert out["c"] == [0]
    assert out["m"] == [None]


def test_grouped_agg_int_keys(session):
    df, at = gen_df(session, [("k", IntegerGen(lo=0, hi=20)),
                              ("v", LongGen(lo=-10**9, hi=10**9))],
                    n=8000, seed=11)
    out = df.group_by("k").agg(F.sum("v").alias("s"),
                               F.count("v").alias("c"),
                               F.min("v").alias("mn"),
                               F.max("v").alias("mx"),
                               F.avg("v").alias("av")).to_arrow()
    groups = defaultdict(list)
    counts = defaultdict(int)
    for k, v in _py_rows(at):
        counts[k] += 0  # ensure key exists even if all v null
        if v is not None:
            groups[k].append(v)
        counts[k] += 1
    def wrap64(x):
        return ((x + 2**63) % 2**64) - 2**63  # Spark sum(long) wraps

    exp = []
    for k in counts:
        vs = groups.get(k, [])
        exp.append((k, wrap64(sum(vs)) if vs else None, len(vs),
                    min(vs) if vs else None, max(vs) if vs else None,
                    wrap64(sum(vs)) / len(vs) if vs else None))
    assert_rows_equal(out, exp)


def test_grouped_agg_string_keys(session):
    df, at = gen_df(session, [("k", StringGen(max_len=12)),
                              ("v", IntegerGen(lo=-1000, hi=1000))],
                    n=4000, seed=12)
    out = df.group_by("k").agg(F.sum("v").alias("s"),
                               F.count("*").alias("n")).to_arrow()
    groups = defaultdict(list)
    counts = defaultdict(int)
    for k, v in _py_rows(at):
        counts[k] += 1
        if v is not None:
            groups[k].append(v)
    exp = [(k, sum(groups[k]) if groups[k] else None, counts[k])
           for k in counts]
    assert_rows_equal(out, exp)


def test_grouped_agg_multi_keys_with_nulls(session):
    df, at = gen_df(session, [("k1", IntegerGen(lo=0, hi=3)),
                              ("k2", BooleanGen()),
                              ("v", IntegerGen(lo=0, hi=100))],
                    n=3000, seed=13)
    out = df.group_by("k1", "k2").agg(F.count("*").alias("n"),
                                      F.sum("v").alias("s")).to_arrow()
    counts = defaultdict(int)
    sums = defaultdict(lambda: None)
    for k1, k2, v in _py_rows(at):
        counts[(k1, k2)] += 1
        if v is not None:
            sums[(k1, k2)] = (sums[(k1, k2)] or 0) + v
    exp = [(k1, k2, counts[(k1, k2)], sums[(k1, k2)])
           for (k1, k2) in counts]
    assert_rows_equal(out, exp)


def test_grouped_agg_float_key_nan(session):
    import pyarrow as pa
    df = session.create_dataframe({"k": pa.array(
        [float("nan"), float("nan"), 1.0, 1.0, -0.0, 0.0, None],
        type=pa.float64()),
        "v": pa.array([1, 2, 3, 4, 5, 6, 7], type=pa.int64())})
    out = df.group_by("k").agg(F.sum("v").alias("s")).to_arrow()
    got = {}
    for k, s in zip(out.column(0).to_pylist(), out.column(1).to_pylist()):
        key = ("nan" if (k is not None and math.isnan(k)) else k)
        got[key] = s
    # Spark groups NaN together and -0.0 with 0.0; null its own group
    assert got["nan"] == 3
    assert got[1.0] == 7
    assert got[0.0] == 11
    assert got[None] == 7
    assert len(got) == 4


def test_agg_over_expression(session):
    df, at = gen_df(session, [("a", IntegerGen(lo=0, hi=100)),
                              ("b", IntegerGen(lo=0, hi=100))],
                    n=2000, seed=14)
    out = df.agg(F.sum(col("a") * col("b")).alias("dot")).to_arrow()

    def wrap32(x):  # int * int wraps in 32 bits (Java semantics)
        return ((x + 2**31) % 2**32) - 2**31

    exp_v = sum(wrap32(a * b) for a, b in _py_rows(at)
                if a is not None and b is not None)
    assert out.to_pydict()["dot"] == [exp_v]


def test_stddev_variance(session):
    import statistics
    df, at = gen_df(session, [("k", IntegerGen(lo=0, hi=4, nullable=False)),
                              ("v", IntegerGen(lo=0, hi=1000))],
                    n=2000, seed=130)
    out = (df.group_by("k").agg(F.stddev("v").alias("sd"),
                                F.variance("v").alias("vr")).to_arrow())
    groups = defaultdict(list)
    for k, v in zip(at.column(0).to_pylist(), at.column(1).to_pylist()):
        if v is not None:
            groups[k].append(v)
    got = {k: (sd, vr) for k, sd, vr in zip(
        *[out.column(i).to_pylist() for i in range(3)])}
    for k, vs in groups.items():
        sd, vr = got[k]
        assert abs(sd - statistics.stdev(vs)) < 1e-6 * max(statistics.stdev(vs), 1)
        assert abs(vr - statistics.variance(vs)) < 1e-6 * max(statistics.variance(vs), 1)
    # ungrouped + edge: single row -> null
    one = session.create_dataframe({"v": [5]})
    r = one.agg(F.stddev("v").alias("s")).to_arrow().to_pydict()
    assert r["s"] == [None]


def test_variance_no_catastrophic_cancellation(session):
    import pyarrow as pa
    n = 2000
    vals = [10**9 + (i % 2) for i in range(n)]
    df = session.create_dataframe({"v": pa.array(vals, pa.int64()),
                                   "k": pa.array([i % 3 for i in range(n)])})
    got = df.agg(F.variance("v").alias("v")).collect()[0][0]
    import statistics
    exp = statistics.variance(vals)
    assert abs(got - exp) < 1e-6, (got, exp)
    # grouped + multi-batch merge path
    s2 = __import__("spark_rapids_tpu").TpuSession(
        {"spark.rapids.tpu.sql.batchSizeRows": 128})
    df2 = s2.create_dataframe({"v": pa.array(vals, pa.int64()),
                               "k": pa.array([i % 3 for i in range(n)])})
    out = df2.group_by("k").agg(F.variance("v").alias("vr")).to_arrow()
    for k, vr in zip(out.column(0).to_pylist(), out.column(1).to_pylist()):
        gvals = [v for i, v in enumerate(vals) if i % 3 == k]
        assert abs(vr - statistics.variance(gvals)) < 1e-6


def test_grouped_first_last(session):
    import spark_rapids_tpu as st
    import pyarrow as pa
    # small batches force the merge path across partial states
    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 64})
    n = 500
    ks = [i % 5 for i in range(n)]
    vs = [None if i % 7 == 0 else i for i in range(n)]
    df = s.create_dataframe({"k": pa.array(ks, pa.int32()),
                             "v": pa.array(vs, pa.int64())})
    out = df.group_by("k").agg(
        F.first("v").alias("f"), F.last("v").alias("l"),
        F.first("v", ignorenulls=True).alias("fn")).to_arrow()
    got = {k: (f, l, fn) for k, f, l, fn in zip(
        *[out.column(i).to_pylist() for i in range(4)])}
    for k in range(5):
        vals = [v for kk, v in zip(ks, vs) if kk == k]
        nn = [v for v in vals if v is not None]
        assert got[k] == (vals[0], vals[-1], nn[0] if nn else None), \
            (k, got[k])


def test_cached_whole_input_agg(session):
    """HBM-cached small input takes the one-round-trip whole-input
    program (complete mode, optimistic group capacity) and matches the
    streaming path's results."""
    import pyarrow as pa
    from data_gen import IntegerGen, StringGen, gen_df
    df, at = gen_df(session, [("k", StringGen(max_len=4, charset="abc")),
                              ("g", IntegerGen(lo=0, hi=9)),
                              ("v", IntegerGen(lo=-1000, hi=1000))],
                    n=3000, seed=130)
    cached = df.cache()
    import spark_rapids_tpu.functions as F
    out = cached.group_by("k", "g").agg(
        F.sum("v").alias("s"), F.count("v").alias("c"),
        F.avg("v").alias("a")).to_arrow()
    from collections import defaultdict
    acc = defaultdict(lambda: [0, 0])
    for k, g, v in zip(at.column(0).to_pylist(),
                       at.column(1).to_pylist(),
                       at.column(2).to_pylist()):
        if v is not None:
            acc[(k, g)][0] += v
            acc[(k, g)][1] += 1
        else:
            acc[(k, g)]
    exp = []
    for (k, g), (sv, c) in acc.items():
        exp.append((k, g, sv if c else None, c,
                    sv / c if c else None))
    from asserts import assert_rows_equal, indexed_rows
    assert_rows_equal(out, exp)


def test_cached_whole_input_agg_overflow_falls_back(session):
    """More groups than the optimistic capacity: the overflow flag sends
    execution down the exact multi-pass path with identical results."""
    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu as st
    import spark_rapids_tpu.functions as F
    s2 = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 4096,
        "spark.rapids.tpu.sql.agg.optimisticGroups": 64,
    })
    rng = np.random.default_rng(131)
    n = 2000
    k = rng.integers(0, 500, n)   # 500 groups > 64
    v = rng.integers(0, 100, n)
    df = s2.create_dataframe({"k": pa.array(k),
                              "v": pa.array(v)}).cache()
    out = df.group_by("k").agg(F.sum("v").alias("s")).to_arrow()
    from collections import defaultdict
    acc = defaultdict(int)
    for ki, vi in zip(k, v):
        acc[ki] += vi
    got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
    assert got == {int(a): b for a, b in acc.items()}


def test_groupby_out_of_core_bucket_fallback(tmp_path, monkeypatch):
    """Distinct-key groupby whose group state exceeds the merge bound AND
    the device budget: partials park in the spill store, the final pass
    repartitions into hash buckets of disjoint keys, and the answer is
    exact (GpuAggregateExec.scala:863-894 repartition fallback analog)."""
    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu as st
    import spark_rapids_tpu.functions as F
    import spark_rapids_tpu.memory.device as dev_mod
    import spark_rapids_tpu.memory.spill as spill_mod

    dm = dev_mod.DeviceManager(budget_bytes=256 << 10)
    store = spill_mod.SpillStore(dm, spill_dir=str(tmp_path))
    monkeypatch.setattr(dev_mod, "_GLOBAL", dm)
    monkeypatch.setattr(spill_mod, "_STORE", store)

    n = 20000
    rng = np.random.default_rng(97)
    keys = rng.permutation(n).astype(np.int64)      # every key distinct
    vals = rng.integers(-100, 100, n).astype(np.int64)
    s = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 1024,
        "spark.rapids.tpu.sql.agg.maxMergeRows": 2048,
        "spark.rapids.tpu.sql.agg.optimisticGroups": 0,
    })
    out = s.create_dataframe({"k": pa.array(keys), "v": pa.array(vals)}) \
        .group_by("k").agg(F.sum("v").alias("sv"),
                           F.count("v").alias("c")).to_arrow()
    got = {out.column(0)[i].as_py(): (out.column(1)[i].as_py(),
                                      out.column(2)[i].as_py())
           for i in range(out.num_rows)}
    want = {int(k): (int(v), 1) for k, v in zip(keys, vals)}
    assert got == want
    assert store.metrics["spillToHost"] > 0, store.metrics


def test_groupby_out_of_core_string_keys(tmp_path, monkeypatch):
    """The bucket fallback with string keys: take_strings-based shrink
    paths and per-bucket merges keep exact contents."""
    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu as st
    import spark_rapids_tpu.functions as F

    n = 6000
    rng = np.random.default_rng(99)
    keys = [f"user-{i:05d}" for i in rng.permutation(n)]
    vals = rng.integers(0, 50, n).astype(np.int64)
    s = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 512,
        "spark.rapids.tpu.sql.agg.maxMergeRows": 1024,
        "spark.rapids.tpu.sql.agg.optimisticGroups": 0,
    })
    out = s.create_dataframe({"k": pa.array(keys), "v": pa.array(vals)}) \
        .group_by("k").agg(F.max("v").alias("mx")).to_arrow()
    got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
    want = {k: int(v) for k, v in zip(keys, vals)}
    assert got == want


# ---------------------------------------------------------------------
# the sort-segmented aggregate reduces runs by scan, not by scatter
# (PR 36): the run reducer against jax.ops.segment_* as the plain
# reference, the whole path against pyarrow, and the program's shape
# ---------------------------------------------------------------------
def _run_layout(name):
    """(sorted key, key validity, live) of a batch in key order, dead
    rows last, as `_reduce_runs` sees it after the ride."""
    import numpy as np
    rng = np.random.default_rng(len(name))
    cap = (1 << 20) if name == "mixed_1mi" else 128
    valid = np.ones(cap, bool)
    live = np.ones(cap, bool)
    if name == "runs_of_one":
        key = np.arange(cap)
    elif name == "one_run":
        key = np.zeros(cap, np.int64)
    elif name == "all_dead":
        key, live = rng.integers(0, 9, cap), np.zeros(cap, bool)
    else:
        key = np.sort(rng.integers(0, cap // 3, cap))
        if name == "dead_tail":
            live = np.arange(cap) < cap - 37
        if name == "null_keys":        # nulls sort first, one run
            valid = np.arange(cap) >= 11
            key = np.where(valid, key, rng.integers(0, 9, cap))
    return key.astype(np.int64), valid, live


def _reducer_case(name, cap, run_of):
    """(reducer, values) with sums that are exact in any order."""
    import numpy as np
    rng = np.random.default_rng(len(name) + cap)
    if name == "sum_int64_wraps":
        big = np.iinfo(np.int64).max
        return "sum", rng.choice(np.array([big, big - 1, 7, -big],
                                          np.int64), cap)
    if name == "sum_float32":
        return "sum", (rng.integers(0, 5, cap) / 4).astype(np.float32)
    if name == "sum_float64":
        # runs of 1e18 before runs of 2**-30: a difference of prefix
        # sums would lose every small run
        return "sum", np.where(run_of % 2 == 0, 1e18,
                               rng.integers(1, 9, cap) * 2.0 ** -30)
    if name in ("min_int64", "max_int64"):
        return name[:3], rng.integers(-2 ** 62, 2 ** 62, cap)
    if name in ("min_float64", "max_float64"):
        return name[:3], rng.normal(size=cap)
    if name == "max_int32":
        return "max", rng.integers(-2 ** 31, 2 ** 31, cap).astype(np.int32)
    assert name == "or"
    return "or", rng.integers(0, 4, cap) == 0


_REDUCER_CASES = ["sum_int64_wraps", "sum_d128_limbs", "sum_float32",
                  "sum_float64", "min_int64", "max_int64", "max_int32",
                  "min_float64", "max_float64", "or"]


@pytest.mark.parametrize("case,layout", [
    (c, lay) for c in _REDUCER_CASES
    for lay in ["runs_of_one", "one_run", "dead_tail", "null_keys",
                "all_dead", "mixed_128"]] + [
    (c, "mixed_1mi") for c in ["sum_int64_wraps", "sum_float64",
                               "min_float64", "or"]])
def test_run_reducer_equals_segment_reference(case, layout):
    """`RunGroups` (a segmented scan read at each run's last row, then
    one ride to slot k) against `ScatterGroups` (`jax.ops.segment_*`) on
    the same groups, through `_seg_reduce`, the seam both share: equal
    in every live slot, bit for bit, and the live slots are a prefix."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_reduce
    from spark_rapids_tpu.ops import decimal128 as d128
    from spark_rapids_tpu.ops import sortkeys as sk
    from spark_rapids_tpu.ops.groups import RunGroups, ScatterGroups
    key, valid, live = _run_layout(layout)
    cap = key.shape[0]
    order = [jnp.asarray(~live).astype(jnp.uint8),
             jnp.asarray(~valid).astype(jnp.uint8),
             jnp.asarray(np.where(valid, key, 0))]
    boundary = sk.group_boundaries(order)
    live = jnp.asarray(live)
    runs = RunGroups(order, live)
    ids = ScatterGroups(jnp.cumsum(boundary.astype(jnp.int32)) - 1, cap)
    if case == "sum_d128_limbs":
        rng = np.random.default_rng(cap)
        data = jnp.asarray(np.stack(
            [rng.integers(-2 ** 63, 2 ** 63 - 1, cap),
             rng.integers(-2 ** 40, 2 ** 40, cap)], axis=1))
        reducer, cols = "sum", d128.split_d128_limbs(data)
    else:
        reducer, x = _reducer_case(case, cap, np.asarray(ids.seg_ids))
        cols = [jnp.asarray(x)]
    got = runs.slots([_seg_reduce(reducer, c, live, runs) for c in cols])
    want = [_seg_reduce(reducer, c, live, ids) for c in cols]
    seg_live = np.asarray(ids.any(live))
    n_live = int(seg_live.sum())
    assert seg_live[:n_live].all()
    assert int(runs.count) == n_live
    np.testing.assert_array_equal(np.asarray(runs.slot_live), seg_live)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g)[:n_live],
                                      np.asarray(w)[:n_live])
        assert not np.asarray(g)[n_live:].any()
    if case == "sum_d128_limbs" and n_live:
        v, ovf = d128.combine_limb_sums(got, 38)
        vw, ovfw = d128.combine_limb_sums(want, 38)
        np.testing.assert_array_equal(np.asarray(v)[:n_live],
                                      np.asarray(vw)[:n_live])


def _oracle(at, keys, aggs):
    """pyarrow's group_by as {key tuple: value tuple}; NaN keys as
    'nan' and -0.0 as 0.0 (Spark groups them so)."""
    out = at.group_by(keys, use_threads=False).aggregate(aggs)
    return _as_groups(out, keys, [f"{a[0]}_{a[1]}" for a in aggs])


def _as_groups(tbl, keys, names):
    def norm(k):
        if isinstance(k, float):
            return "nan" if math.isnan(k) else k + 0.0
        return k
    cols = {n: tbl.column(n).to_pylist() for n in list(keys) + list(names)}
    return {tuple(norm(cols[k][i]) for k in keys):
            tuple(cols[n][i] for n in names)
            for i in range(tbl.num_rows)}


@pytest.mark.parametrize("shape", ["near_unique_decimal_sum", "string_key",
                                   "decimal128_key", "float64_key",
                                   "custom_reducers"])
def test_groupby_lost_hash_pass_sorted_update_merge_buckets(shape):
    """A near-unique key at a small `sql.agg.maxMergeRows`: the hash
    pass overflows and is thrown away, every batch goes through the
    sorted update, eager merges stop compacting and the bucket fallback
    splits the final pass. Against pyarrow, exact: q18's shape (sum of a
    decimal(12,2) into decimal(22,2), more groups than
    `_HASH_BUCKETS_MAX`), a string key beside a fixed-width key, a
    decimal128 key, a float64 key with NaN and -0.0, and custom
    reducers beside a sum."""
    from decimal import Decimal

    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import spark_rapids_tpu as st
    from spark_rapids_tpu.exec import aggregate as agg_mod
    big = shape == "near_unique_decimal_sum"
    # a batch of near-unique keys past the second round's buckets
    # (a quarter of its capacity) loses the hash pass
    n = 330_000 if big else 30_000
    batch = (1 << 16) if big else (1 << 13)
    rng = np.random.default_rng(36)
    s = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": batch,
        "spark.rapids.tpu.sql.agg.maxMergeRows": 2 * batch,
        "spark.rapids.tpu.sql.agg.optimisticGroups": 0,
    })
    ids = rng.permutation(n) * 10 // 11        # a few keys twice
    cents = rng.integers(-10 ** 11, 10 ** 11, n)
    v = pa.array([Decimal(int(c)) / 100 for c in cents],
                 pa.decimal128(12, 2))
    v = pc.if_else(pa.array(rng.integers(0, 50, n) == 0), None, v)
    keys, aggs = ["k"], [F.sum("v").alias("v_sum")]
    pa_aggs = [("v", "sum")]
    if big:
        assert len(set(ids.tolist())) > agg_mod._HASH_BUCKETS_MAX
        cols = {"k": pa.array(ids.astype(np.int64))}
    elif shape == "string_key":
        keys = ["k", "g"]
        cols = {"k": pa.array([None if i % 97 == 0 else f"user-{i:06d}"
                               for i in ids]),
                "g": pa.array((ids % 3).astype(np.int32))}
    elif shape == "decimal128_key":
        cols = {"k": pa.array([None if i % 89 == 0 else
                               Decimal(int(i) * 10 ** 20 + 7) / 1000
                               for i in ids], pa.decimal128(30, 3))}
    elif shape == "float64_key":
        special = {0: float("nan"), 1: -0.0, 2: 0.0, 3: None,
                   4: float("inf")}
        cols = {"k": pa.array([special.get(int(i) % 40, i / 8)
                               for i in ids], pa.float64())}
    else:
        cols = {"k": pa.array(ids.astype(np.int64)),
                "w": pa.array(rng.normal(size=n))}
        aggs += [F.first("w").alias("w_first"),
                 F.stddev("w").alias("w_stddev"),
                 F.min("w").alias("w_min"), F.count("v").alias("v_count")]
        pa_aggs += [("w", "first"), ("w", "stddev", pc.VarianceOptions(
            ddof=1)), ("w", "min"), ("v", "count")]
    at = pa.table({**cols, "v": v})
    q = s.create_dataframe(at).group_by(*keys).agg(*aggs)
    out = q.to_arrow()
    names = [f"{a[0]}_{a[1]}" for a in pa_aggs]
    got = _as_groups(out, keys, names)
    assert out.schema.field("v_sum").type == pa.decimal128(22, 2)
    if shape == "float64_key":
        # pyarrow keeps -0.0 and 0.0 apart; Spark groups them
        at = at.set_column(0, "k", pa.array(
            [None if x is None else x + 0.0
             for x in at.column("k").to_pylist()], pa.float64()))
    want = _oracle(at, keys, pa_aggs)
    assert len(got) == out.num_rows == len(want)
    if shape == "custom_reducers":
        for k, w in want.items():
            g = got[k]
            assert g[0] == w[0] and g[1] == w[1] and g[3:] == w[3:], k
            assert (g[2] is None and w[2] is None) or \
                math.isclose(g[2], w[2], rel_tol=1e-9, abs_tol=1e-12), k
    else:
        assert got == want
    ms = [m for m in q.last_metrics().values() if "aggSortWords" in m]
    words = sum(m["aggSortWords"] for m in ms)
    scattered = sum(m["aggScatteredColumns"] for m in ms)
    # every batch went through the sorted update and merges followed
    assert words > 12 * -(-n // batch)
    # only a custom reducer or a string key still scatters or gathers
    assert (scattered > 0) == (shape in ("custom_reducers", "string_key"))


def _planned_aggregate(keys, aggs, cols):
    """The planned HashAggregateExec of `group_by(keys).agg(aggs)` over
    a small table of `cols`."""
    import spark_rapids_tpu as st
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    s = st.TpuSession({"spark.rapids.tpu.sql.agg.optimisticGroups": 0})
    plan = s.create_dataframe(cols).group_by(*keys).agg(*aggs)
    root, _ = plan._execute()

    def walk(node):
        yield node
        for c in node.children:
            yield from walk(c)
    node = next(op for op in walk(root)
                if isinstance(op, HashAggregateExec))
    node._resolve_fusion()
    return node


@pytest.mark.parametrize("program", ["update", "merge"])
@pytest.mark.parametrize("keys", ["fixed", "with_string"])
def test_sorted_aggregate_program_holds_no_scatter_and_no_row_gather(
        keys, program):
    """Lower the sort-segmented update and merge on the CPU and read the
    StableHLO: with fixed-width keys (an int64, a decimal128, a float64)
    and standard reducers (sum into decimal128 limbs, min, max, avg,
    count) no scatter at all and no gather whose operand is as long as
    the batch; with a string key exactly the gathers and scatters of the
    string's own chunk words and `take`. (On the chip `lexsort` is a
    chain of two-operand sorts and its K-1 `w[perm]` are the only
    row-long gathers left: PERF.md, PR 36.)"""
    from decimal import Decimal

    import jax
    import jax.numpy as jnp
    import pyarrow as pa
    from spark_rapids_tpu.ops import sortkeys as sk
    from spark_rapids_tpu.ops.gather import take
    from spark_rapids_tpu.ops.kernel_utils import CV
    cap = 4096
    cols = {"k": pa.array([1, 2], pa.int64()),
            "d": pa.array([Decimal(1), None], pa.decimal128(30, 3)),
            "f": pa.array([0.5, None], pa.float64()),
            "s": pa.array(["a", "bb"]),
            "v": pa.array([Decimal(1), Decimal(2)], pa.decimal128(12, 2)),
            "w": pa.array([1.5, 2.5], pa.float64())}
    names = ["k", "d", "f"] + (["s"] if keys == "with_string" else [])
    node = _planned_aggregate(names, [
        F.sum("v").alias("a"), F.min("w").alias("b"), F.max("v").alias("c"),
        F.avg("w").alias("d_"), F.count("v").alias("e"),
        F.count("*").alias("n")], cols)
    nchunks = (0, 0, 0) + ((2,) if keys == "with_string" else ())

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def col(dtype, *tail):
        return CV(struct((cap,) + tail, dtype), struct((cap,), jnp.bool_))
    string = CV(struct((8 * cap,), jnp.uint8), struct((cap,), jnp.bool_),
                struct((cap + 1,), jnp.int32))
    def of(dtype):
        if dtype.is_variable_width:
            return string
        limbs = (2,) if getattr(dtype, "is_decimal128", False) else ()
        return col(dtype.np_dtype, *limbs)
    mask = struct((cap,), jnp.bool_)
    if program == "update":
        cvs = [of(f.dtype) for f in node._base.schema.fields]
        text = jax.jit(node._update_fn(nchunks)).lower(cvs, mask).as_text()
    else:
        states = [struct((cap,), d) for d in node._state_np_dtypes()]
        text = jax.jit(
            lambda a, b, c: node._merge_body(a, b, c, nchunks)).lower(
                [of(k.dtype) for k in node.keys], states, mask).as_text()
    gathers = indexed_rows(text, "gather")
    scatters = indexed_rows(text, "scatter")
    if keys == "fixed":
        assert scatters == [], scatters
        assert max(gathers, default=0) < cap, gathers
        return
    own = jax.jit(lambda cv, idx, inb: (
        sk.order_keys(cv, node.keys[3].dtype, 2),
        take(cv, idx, in_bounds=inb))).lower(
            string, struct((cap,), jnp.int32), mask).as_text()
    assert sorted(gathers) == sorted(indexed_rows(own, "gather"))
    assert sorted(scatters) == sorted(indexed_rows(own, "scatter"))
    assert scatters and gathers


def test_aggregate_counts_words_sorted_and_columns_scattered():
    """`aggSortWords` / `aggScatteredColumns` in `last_metrics()` and
    EXPLAIN ANALYZE read what the shapes say. q18's first aggregate
    (sum of a decimal(12,2) by an int64 key): an update launch lets 5
    words ride into key order (the key's two, the input's two, three
    flags in one) and 7 to the slots (the key's two, two int64 limb
    sums, two flags in one); a merge 7 and 7; nothing scattered. A
    string key and a `first` beside them: the chunk words and the row
    index ride, the key is gathered, `first`'s three columns scatter."""
    from decimal import Decimal

    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu as st
    n, batch = 20_000, 1 << 13
    launches = -(-n // batch)
    s = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": batch,
        "spark.rapids.tpu.sql.agg.optimisticGroups": 0})
    rng = np.random.default_rng(7)
    k = rng.permutation(n).astype(np.int64)
    at = pa.table({
        "k": pa.array(k), "s": pa.array([f"{i:07d}" for i in k]),
        "v": pa.array([Decimal(int(c)) / 100
                       for c in rng.integers(0, 10 ** 6, n)],
                      pa.decimal128(12, 2))})

    def agg_metrics(q):
        q.to_arrow()
        return [m for m in q.last_metrics().values()
                if "aggSortWords" in m]

    q = s.create_dataframe(at).group_by("k").agg(F.sum("v").alias("t"))
    partial, final = agg_metrics(q)
    assert partial["aggSortWords"] == (5 + 7) * launches
    assert final["aggSortWords"] == 7 + 7
    assert partial["aggScatteredColumns"] == 0
    assert final["aggScatteredColumns"] == 0
    plan = q.explain("ANALYZE")
    assert f"aggSortWords={(5 + 7) * launches} " in plan
    assert "aggSortWords=14 aggScatteredColumns=0" in plan

    q = s.create_dataframe(at).group_by("s", "k").agg(
        F.sum("v").alias("t"), F.first("v").alias("f"))
    partial, final = agg_metrics(q)
    # into key order: two chunk words, the int64 key, sum's and first's
    # inputs (two words each), six flags; to the slots: the int64 key,
    # the row index, two limb sums, two flags
    update = (2 + 2 + 2 + 2 + 1) + (2 + 1 + 4 + 1)
    merge = (2 + 2 + 4 + 2 + 1) + (2 + 1 + 4 + 1)
    assert partial["aggSortWords"] == update * launches
    assert final["aggSortWords"] == merge
    assert partial["aggScatteredColumns"] == (1 + 3) * launches
    assert final["aggScatteredColumns"] == 1 + 3
