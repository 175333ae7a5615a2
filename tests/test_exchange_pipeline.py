"""Parallel pipelined exchanges: multithreaded map side parity,
plan-level exchange reuse, async broadcast build, and the xxhash64 /
hive-hash device kernels (the jni Hash family's other algorithms).

Determinism contract: the parallel map side must be BYTE-IDENTICAL to
serial — workers fill mpid-keyed slots and the reduce side reads them
in sorted mpid order, so completion order never leaks into results.
"""
import numpy as np
import pyarrow as pa
import pytest

from asserts import indexed_rows

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.exec.exchange import map_partitions_executed
from spark_rapids_tpu.ops.kernel_utils import CV


def _mk_session(**extra):
    conf = {"spark.rapids.tpu.sql.batchSizeRows": 256,
            "spark.rapids.tpu.sql.shuffle.partitions": 4}
    conf.update(extra)
    return st.TpuSession(conf)


def _mixed_table(n=2000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array([None if i % 17 == 0 else int(x) for i, x in
                       enumerate(rng.integers(0, 12, n))],
                      type=pa.int64()),
        "v": pa.array(rng.normal(0, 1, n)),
        "s": pa.array([None if i % 23 == 0 else f"s{i % 41}"
                       for i in range(n)]),
    })


# =====================================================================
# multithreaded map side
# =====================================================================
def _shuffled(sess, at):
    # two chained exchanges: the second one's child has 6 map
    # partitions, so its map phase actually fans out across workers
    df = sess.create_dataframe(at)
    return (df.repartition(6)
              .repartition(5, F.col("k"))
              .to_arrow())


def test_parallel_map_byte_identical_to_serial():
    """Nulls, strings, and a multi-partition map side: mapThreads=1 vs
    mapThreads=4 produce the same table in the same order."""
    at = _mixed_table()
    serial = _shuffled(_mk_session(
        **{"spark.rapids.tpu.sql.exec.exchange.mapThreads": 1}), at)
    parallel = _shuffled(_mk_session(
        **{"spark.rapids.tpu.sql.exec.exchange.mapThreads": 4}), at)
    assert serial.schema == parallel.schema
    assert serial.equals(parallel)          # byte-identical, order too


def test_parallel_map_empty_partitions_parity():
    """Two distinct keys into 8 partitions: most reduce (and then map)
    partitions are empty — empty slots must not shift output."""
    at = pa.table({"k": pa.array([1, 2] * 300, type=pa.int64()),
                   "v": pa.array(range(600), type=pa.int64())})

    def run(threads):
        s = _mk_session(**{
            "spark.rapids.tpu.sql.exec.exchange.mapThreads": threads})
        return (s.create_dataframe(at)
                 .repartition(8, F.col("k"))
                 .repartition(3, F.col("k"))
                 .to_arrow())

    assert run(1).equals(run(4))


def test_parallel_map_agg_parity():
    at = _mixed_table(1500, seed=9)

    def run(threads):
        s = _mk_session(**{
            "spark.rapids.tpu.sql.exec.exchange.mapThreads": threads})
        df = s.create_dataframe(at).repartition(6)
        out = (df.group_by("k")
                 .agg(F.count(F.col("v")).alias("c"),
                      F.sum(F.col("v")).alias("sv"))
                 .collect())
        return sorted(((r[0], r[1], round(r[2], 9)) for r in out),
                      key=lambda t: (t[0] is None, t[0] or 0))

    assert run(1) == run(4)


def test_map_threads_conf_resolution():
    from spark_rapids_tpu.exec.exchange_pool import resolve_map_threads

    class _Ctx:
        def __init__(self, conf):
            self.conf = conf

    from spark_rapids_tpu.config import TpuConf
    ctx = _Ctx(TpuConf(
        {"spark.rapids.tpu.sql.exec.exchange.mapThreads": 3}))
    assert resolve_map_threads(ctx, 10) == 3
    assert resolve_map_threads(ctx, 2) == 2    # capped by nparts
    ctx0 = _Ctx(TpuConf({}))
    assert resolve_map_threads(ctx0, 64) >= 1  # auto


# =====================================================================
# plan-level exchange reuse
# =====================================================================
def _self_join_rows(reuse, how="inner"):
    s = _mk_session(**{
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.sql.exec.exchange.reuse.enabled": reuse})
    df = s.create_dataframe(
        {"k": [1, 2, 3, 4, 5, 6, 7, 8] * 10, "v": list(range(80))})
    m0 = map_partitions_executed()
    j = df.join(df, on="k", how=how)
    rows = sorted(map(tuple, j.collect()))
    return rows, map_partitions_executed() - m0, j


def test_exchange_reuse_self_join_halves_map_work():
    rows_on, maps_on, j = _self_join_rows(True)
    rows_off, maps_off, _ = _self_join_rows(False)
    assert rows_on == rows_off
    assert maps_on < maps_off       # one map phase per DISTINCT subtree
    plan = j.explain("ANALYZE")
    assert "ReusedExchange[loreId=" in plan
    assert "exchangeReuseHits=1" in plan


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_exchange_reuse_semi_anti_shapes(how):
    """The TPC-H q4/q21 shapes: semi/anti self-joins dedupe the build
    exchange while keeping exact row parity."""
    rows_on, maps_on, j = _self_join_rows(True, how=how)
    rows_off, maps_off, _ = _self_join_rows(False, how=how)
    assert rows_on == rows_off
    assert maps_on < maps_off
    hits = sum(int(m.get("exchangeReuseHits", 0))
               for m in j.last_metrics().values())
    assert hits >= 1


def test_exchange_reuse_disabled_by_conf():
    _, maps_off, j = _self_join_rows(False)
    plan = j.explain("ALL")
    assert "ReusedExchange" not in plan


def test_exchange_reuse_distinct_subtrees_not_merged():
    """Two different filters feed two exchanges: fingerprints differ,
    nothing merges, results stay correct."""
    s = _mk_session(**{
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    df = s.create_dataframe(
        {"k": [1, 2, 3, 4] * 20, "v": list(range(80))})
    a = df.filter(F.col("v") < 60)
    b = df.filter(F.col("v") < 40)
    j = a.join(b, on="k")
    rows = j.collect()
    assert len(rows) > 0
    assert "ReusedExchange" not in j.explain("ALL")


def test_reuse_fingerprint_name_blind():
    """node_fp must see through pure-rename projects and column-name
    labels — the Exchange(Project[k AS gensym](Scan)) self-join shape."""
    from spark_rapids_tpu.plan.planner import Planner
    from spark_rapids_tpu.plan.reuse import node_fp
    s = _mk_session(**{
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.sql.exec.exchange.reuse.enabled": False})
    df = s.create_dataframe({"k": [1, 2, 3], "v": [4, 5, 6]})
    j = df.join(df, on="k")
    root = Planner(s.conf).plan(j._plan)
    exs = []

    def walk(n):
        if type(n).__name__ == "ShuffleExchangeExec":
            exs.append(n)
        for c in n.children:
            walk(c)

    walk(root)
    assert len(exs) == 2
    fa, fb = node_fp(exs[0]), node_fp(exs[1])
    assert fa is not None and fa == fb


# =====================================================================
# async broadcast build
# =====================================================================
def _bcast_join(timeout_secs, async_on=True):
    s = _mk_session(**{
        "spark.rapids.tpu.sql.exec.exchange.broadcastTimeoutSecs":
            timeout_secs,
        "spark.rapids.tpu.sql.exec.exchange.asyncBroadcast.enabled":
            async_on})
    left = s.create_dataframe(
        {"k": list(range(200)) * 4, "v": list(range(800))})
    right = s.create_dataframe(
        {"k": list(range(200)), "w": [k * 10 for k in range(200)]})
    j = left.join(right, on="k")
    rows = sorted(map(tuple, j.collect()))
    return rows, j


def test_async_broadcast_parity_with_sync():
    rows_async, j = _bcast_join(300.0, async_on=True)
    rows_sync, _ = _bcast_join(300.0, async_on=False)
    assert rows_async == rows_sync
    assert len(rows_async) == 800
    overlap = [m.get("broadcastBuildOverlapMs")
               for m in j.last_metrics().values()
               if "broadcastBuildOverlapMs" in m]
    assert overlap                           # async path actually ran


def test_broadcast_timeout_degrades_to_sync(monkeypatch):
    """A microscopic timeout forces the fallback: results stay correct
    and the fallback is counted, never a hang. The build is slowed so
    it cannot finish during the stream-side prefetch window (a fast
    build that beats the await is legitimately not a fallback)."""
    import time as _time

    from spark_rapids_tpu.exec import broadcast as _bx

    orig = _bx.BroadcastExchangeExec._materialize

    def slow(self, ctx):
        _time.sleep(0.3)
        return orig(self, ctx)

    monkeypatch.setattr(_bx.BroadcastExchangeExec, "_materialize", slow)
    rows, j = _bcast_join(1e-9, async_on=True)
    ref, _ = _bcast_join(300.0, async_on=False)
    assert rows == ref
    fallbacks = sum(int(m.get("broadcastTimeoutFallbacks", 0))
                    for m in j.last_metrics().values())
    assert fallbacks >= 1


def test_async_broadcast_nested_builds_do_not_deadlock():
    """A broadcast join INSIDE the build side of another broadcast join
    (the TPC-H q2 shape): the nested build must materialize inline on
    the build-pool thread, not wait on a future queued behind itself on
    the same bounded pool — that cycle only the 300s timeout breaks."""
    import time as _time

    s = _mk_session(**{
        "spark.rapids.tpu.sql.exec.exchange.broadcastTimeoutSecs": 30.0})
    a = s.create_dataframe({"k": list(range(50)), "v": list(range(50))})
    b = s.create_dataframe(
        {"k": list(range(50)), "w": [k * 2 for k in range(50)]})
    c = s.create_dataframe(
        {"k": list(range(50)), "x": [k * 3 for k in range(50)]})
    j = a.join(b.join(c, on="k"), on="k")
    t0 = _time.perf_counter()
    rows = j.collect()
    assert _time.perf_counter() - t0 < 25.0   # not the timeout path
    assert len(rows) == 50
    fallbacks = sum(int(m.get("broadcastTimeoutFallbacks", 0))
                    for m in j.last_metrics().values())
    assert fallbacks == 0


# =====================================================================
# xxhash64 / hive-hash kernels (Spark's other two jni Hash algorithms)
# =====================================================================
_M64 = (1 << 64) - 1
_P1, _P2, _P3 = 0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F, \
    0x165667B19E3779F9
_P4, _P5 = 0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5


def _rotl(x, r):
    return ((x << r) | (x >> (64 - r))) & _M64


def _fmix(h):
    h ^= h >> 33
    h = (h * _P2) & _M64
    h ^= h >> 29
    h = (h * _P3) & _M64
    return h ^ (h >> 32)


def _ref_xxh_int(i, seed):
    h = (seed + _P5 + 4) & _M64
    h ^= ((i & 0xFFFFFFFF) * _P1) & _M64
    h = (_rotl(h, 23) * _P2 + _P3) & _M64
    return _fmix(h)


def _ref_xxh_long(l, seed):
    h = (seed + _P5 + 8) & _M64
    k1 = (_rotl((l & _M64) * _P2 & _M64, 31) * _P1) & _M64
    h = (_rotl(h ^ k1, 27) * _P1 + _P4) & _M64
    return _fmix(h)


def _ref_xxh_bytes(b, seed):
    h = (seed + _P5 + len(b)) & _M64
    i = 0
    while i + 8 <= len(b):
        w = int.from_bytes(b[i:i + 8], "little")
        k1 = (_rotl((w * _P2) & _M64, 31) * _P1) & _M64
        h = (_rotl(h ^ k1, 27) * _P1 + _P4) & _M64
        i += 8
    if i + 4 <= len(b):
        w = int.from_bytes(b[i:i + 4], "little")
        h = (_rotl(h ^ ((w * _P1) & _M64), 23) * _P2 + _P3) & _M64
        i += 4
    while i < len(b):
        h = (_rotl(h ^ ((b[i] * _P5) & _M64), 11) * _P1) & _M64
        i += 1
    return _fmix(h)


def _s64(u):
    return u - (1 << 64) if u >= (1 << 63) else u


def test_xxhash64_ints_match_spark_reference():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hash import xxhash64_row_hash
    xs = [1, -7, 0, 2 ** 31 - 1]
    cv = CV(jnp.asarray(np.array(xs, np.int32)), jnp.ones(4, bool))
    got = list(np.asarray(xxhash64_row_hash([cv], [dt.INT32])))
    assert got == [_s64(_ref_xxh_int(x & 0xFFFFFFFF, 42)) for x in xs]
    xs = [1, -7, 2 ** 40, -(2 ** 50)]
    cv = CV(jnp.asarray(np.array(xs, np.int64)), jnp.ones(4, bool))
    got = list(np.asarray(xxhash64_row_hash([cv], [dt.INT64])))
    assert got == [_s64(_ref_xxh_long(x & _M64, 42)) for x in xs]


def test_xxhash64_strings_match_reference_under_64_bytes():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hash import xxhash64_row_hash
    strs = [b"", b"abc", b"hello world!", b"0123456789abcdefGHIJKLMN",
            b"x" * 31, b"y" * 63]
    data = b"".join(strs)
    offs = np.zeros(len(strs) + 1, np.int32)
    for i, s in enumerate(strs):
        offs[i + 1] = offs[i] + len(s)
    cv = CV(jnp.asarray(np.frombuffer(data, np.uint8)),
            jnp.ones(len(strs), bool), offsets=jnp.asarray(offs))
    got = list(np.asarray(xxhash64_row_hash([cv], [dt.STRING])))
    assert got == [_s64(_ref_xxh_bytes(s, 42)) for s in strs]


def test_xxhash64_null_passes_seed_through():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hash import xxhash64_row_hash
    a = CV(jnp.asarray(np.array([5, 5], np.int32)),
           jnp.asarray([True, False]))
    b = CV(jnp.asarray(np.array([9, 9], np.int64)), jnp.ones(2, bool))
    got = list(np.asarray(
        xxhash64_row_hash([a, b], [dt.INT32, dt.INT64])))
    assert got == [_s64(_ref_xxh_long(9, _ref_xxh_int(5, 42))),
                   _s64(_ref_xxh_long(9, 42))]


def test_hive_hash_matches_java_semantics():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hash import hive_hash_row_hash

    def jstr(b):
        h = 0
        for x in b:
            x = x - 256 if x >= 128 else x
            h = (h * 31 + x) & 0xFFFFFFFF
        return h - (1 << 32) if h >= (1 << 31) else h

    def wrap(v):
        v &= 0xFFFFFFFF
        return v - (1 << 32) if v >= (1 << 31) else v

    cvi = CV(jnp.asarray(np.array([3, -4], np.int32)),
             jnp.ones(2, bool))
    strs = [b"abc", b"hive"]
    offs = np.array([0, 3, 7], np.int32)
    cvs = CV(jnp.asarray(np.frombuffer(b"".join(strs), np.uint8)),
             jnp.ones(2, bool), offsets=jnp.asarray(offs))
    got = list(np.asarray(
        hive_hash_row_hash([cvi, cvs], [dt.INT32, dt.STRING])))
    assert got == [wrap(wrap(3 * 31) + jstr(b"abc")),
                   wrap(wrap(-4 * 31) + jstr(b"hive"))]


def test_hash_functions_end_to_end():
    s = _mk_session()
    df = s.create_dataframe({"k": [1, 2, None], "v": ["a", "bb", "c"]})
    out = df.select(
        F.xxhash64(F.col("k"), F.col("v")).alias("x"),
        F.hive_hash(F.col("k"), F.col("v")).alias("h")).collect()
    assert len(out) == 3
    # null k row: xxhash64 folds only v; hive contributes 0 for k
    assert all(isinstance(r[0], int) and isinstance(r[1], int)
               for r in out)


# =====================================================================
# the map program places rows where ops/hash.py:partition_ids says
# =====================================================================
@pytest.mark.parametrize("key_types", [
    (dt.INT32,), (dt.DATE,), (dt.INT64,), (dt.INT32, dt.INT64)],
    ids=["int32", "date", "int64", "two_keys"])
def test_map_places_rows_by_partition_ids(key_types):
    """At a capacity that is a multiple of 1,024, with dead rows and
    null keys: partition p's slice of the map output holds exactly the
    live rows whose murmur3 pmod is p, in their original order."""
    import jax.numpy as jnp

    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.expr.expressions import BoundRef
    from spark_rapids_tpu.ops.hash import partition_ids

    cap, n = 2048, 16
    rng = np.random.default_rng(11)
    mask = rng.random(cap) < 0.9
    key_cvs = [CV(jnp.asarray(rng.integers(-2**31, 2**31, cap).astype(
                      np.int64 if t is dt.INT64 else np.int32)),
                  jnp.asarray(rng.random(cap) < 0.95)) for t in key_types]
    row_id = CV(jnp.arange(cap, dtype=jnp.int64), jnp.ones(cap, bool))
    keys = [BoundRef(i, t) for i, t in enumerate(key_types)]

    out, counts = ShuffleExchangeExec._build_map_fn(n, keys)(
        key_cvs + [row_id], jnp.asarray(mask))

    want = np.asarray(partition_ids(key_cvs, list(key_types), n))
    counts = np.asarray(counts)
    assert counts.sum() == mask.sum()
    got_rows = np.asarray(out[-1].data)
    ends = np.cumsum(counts)
    for p in range(n):
        assert got_rows[ends[p] - counts[p]:ends[p]].tolist() == \
            np.flatnonzero(mask & (want == p)).tolist()


# =====================================================================
# the map program's contract: stable target order by a sort the payload
# rides (ops/partition.py), held to a plain numpy reference and, leaf
# for leaf, to the argsort-and-take body it replaced
# =====================================================================
def _argsort_and_take_map(cvs, mask, pids, n):
    """The map tail as it was before PR 32 (the reference the reduce
    side's bytes are held to): argsort by target, gather every column
    by the order, bincount."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.gather import take
    eff = jnp.where(mask, pids, n)
    order = jnp.argsort(eff, stable=True)
    live_sorted = mask[order]
    counts = jnp.bincount(eff, length=n + 1)[:n]
    return [take(cv, order, in_bounds=live_sorted) for cv in cvs], counts


def _str_cv(strs, bcap=None):
    """A string CV: bytes packed from the front of a power-of-two
    buffer. None is a null (no bytes)."""
    import jax.numpy as jnp
    d = b"".join(b or b"" for b in strs)
    bcap = bcap or 1 << max(len(d) - 1, 127).bit_length()
    buf = np.zeros(bcap, np.uint8)
    buf[:len(d)] = np.frombuffer(d, np.uint8)
    off = np.zeros(len(strs) + 1, np.int32)
    np.cumsum([len(b or b"") for b in strs], out=off[1:])
    return CV(jnp.asarray(buf),
              jnp.asarray(np.array([b is not None for b in strs])),
              jnp.asarray(off))


def _fixed_cv(vals, valid=None):
    import jax.numpy as jnp
    if valid is None:
        valid = np.ones(len(vals), np.bool_)
    return CV(jnp.asarray(vals), jnp.asarray(valid))


def _some_strings(rng, n, null_share=0.0, empty_share=0.0):
    out = []
    for i in range(n):
        u = rng.random()
        if u < null_share:
            out.append(None)
        elif u < null_share + empty_share:
            out.append(b"")
        else:
            out.append(f"s{i}-".encode() + b"x" * int(rng.integers(0, 9)))
    return out


def _py_rows(cv, k):
    """The first k rows of a (nested) CV as plain python values: None
    for a null, bytes for a string, a list for a list row, a tuple for a
    struct row, a tuple of limbs for a decimal128, its bits for a float."""
    valid = np.asarray(cv.validity)
    if cv.offsets is not None:
        off = np.asarray(cv.offsets)
        if cv.children:
            kids = _py_rows(cv.child, int(off[k]))
            return [kids[off[r]:off[r + 1]] if valid[r] else None
                    for r in range(k)]
        data = np.asarray(cv.data)
        return [bytes(data[off[r]:off[r + 1]]) if valid[r] else None
                for r in range(k)]
    if cv.children:
        kids = [_py_rows(ch, k) for ch in cv.children]
        return [tuple(kid[r] for kid in kids) if valid[r] else None
                for r in range(k)]
    data = np.asarray(cv.data)
    if data.dtype.kind == "f":   # by bits: NaN equals itself, -0.0 not 0.0
        data = data.view(f"i{data.dtype.itemsize}")
    return [(tuple(data[r].tolist()) if data.ndim > 1 else data[r].item())
            if valid[r] else None for r in range(k)]


def _dead_tail_is_null(cv, k):
    """Validity is false past the k live rows, at every struct level;
    variable-width offsets are dense: they stand still past row k and
    the bytes past the last live one are zero."""
    assert not np.asarray(cv.validity)[k:].any()
    if cv.offsets is not None:
        off = np.asarray(cv.offsets)
        assert off[0] == 0 and (np.diff(off) >= 0).all()
        assert (off[k:] == off[k]).all()
        if not cv.children:
            assert not np.asarray(cv.data)[off[k]:].any()
        return
    for ch in cv.children:
        _dead_tail_is_null(ch, k)


def _map_case(name):
    """(cvs, mask, pids, n) of one case of the map program's contract."""
    import jax.numpy as jnp
    cap, n = 512, 8
    if name == "capacity_128":
        cap = 128
    elif name == "n_200":
        n = 200
    rng = np.random.default_rng(sum(map(ord, name)))
    mask = rng.random(cap) < 0.8
    pids = rng.integers(0, n, cap)
    ints = rng.integers(-(1 << 40), 1 << 40, cap).astype(np.int64)
    nulls = rng.random(cap) < 0.9
    cvs = [_fixed_cv(ints, nulls)]
    if name == "int32_date":
        cvs = [_fixed_cv(rng.integers(-50, 50, cap).astype(np.int32), nulls),
               _fixed_cv(rng.integers(-9, 9, cap).astype(np.int8)),
               _fixed_cv(rng.integers(-9, 9, cap).astype(np.int16), nulls)]
    elif name == "bool":
        cvs = [_fixed_cv(rng.random(cap) < 0.5, nulls)]
    elif name == "decimal128_limbs":
        cvs = [_fixed_cv(rng.integers(-(1 << 62), 1 << 62, (cap, 2))
                         .astype(np.int64), nulls)]
    elif name == "mixed_widths":
        cvs = [_fixed_cv(ints, nulls), _fixed_cv(rng.random(cap) < 0.5),
               _fixed_cv(rng.integers(0, 9, cap).astype(np.int32)),
               _fixed_cv(rng.normal(0, 1, cap).astype(np.float32), nulls),
               _fixed_cv(np.where(rng.random(cap) < 0.1,
                                  rng.choice([np.nan, -0.0, np.inf], cap),
                                  rng.normal(0, 1e9, cap)), nulls),
               _fixed_cv(rng.integers(0, 1 << 62, (cap, 2))
                         .astype(np.int64))]
        # 40 validity arrays: the bit-packed flags spill into a second word
        cvs += [_fixed_cv(rng.integers(0, 9, cap).astype(np.int32),
                          rng.random(cap) < 0.5) for _ in range(34)]
    elif name == "strings_nulls_and_empties":
        cvs = [_fixed_cv(ints, nulls),
               _str_cv(_some_strings(rng, cap, 0.2, 0.2))]
    elif name == "two_string_columns":
        cvs = [_str_cv(_some_strings(rng, cap, 0.1)), _fixed_cv(ints, nulls),
               _str_cv(_some_strings(rng, cap, 0.0, 0.5))]
    elif name == "list_column":
        lens = rng.integers(0, 4, cap)
        off = np.zeros(cap + 1, np.int32)
        np.cumsum(lens, out=off[1:])
        ecap = 2048
        elems = _fixed_cv(rng.integers(0, 99, ecap).astype(np.int64),
                          rng.random(ecap) < 0.9)
        cvs = [CV(jnp.zeros(0, jnp.int8), jnp.asarray(nulls),
                  jnp.asarray(off), (elems,)), _fixed_cv(ints, nulls)]
    elif name == "struct_column":
        inner = CV(jnp.zeros(0, jnp.int8),
                   jnp.asarray(rng.random(cap) < 0.7), None,
                   (_fixed_cv(rng.random(cap) < 0.5, nulls),))
        cvs = [CV(jnp.zeros(0, jnp.int8),
                  jnp.asarray(rng.random(cap) < 0.8), None,
                  (_fixed_cv(ints, nulls),
                   _str_cv(_some_strings(rng, cap, 0.2, 0.1)), inner)),
               CV(jnp.zeros(0, jnp.int8), jnp.asarray(nulls), None,
                  (_fixed_cv(rng.integers(0, 9, cap).astype(np.int32)),))]
    elif name == "every_row_to_one_partition":
        mask = np.ones(cap, np.bool_)
        pids = np.full(cap, 3)
    elif name == "all_rows_dead":
        mask = np.zeros(cap, np.bool_)
        cvs.append(_str_cv(_some_strings(rng, cap, 0.1, 0.1)))
    elif name == "no_row_for_some_partitions":
        pids = rng.integers(0, 2, cap) * 5
    else:
        assert name in ("int64", "capacity_128", "n_200"), name
    return cvs, mask, pids.astype(np.int32), n


def _hold_map_to_contract(out, counts, cvs, mask, pids, n):
    """Columns in stable order by partition id, the live rows of
    partition p at [starts[p], starts[p+1]), dead rows after every live
    row with validity false, counts[n], offsets dense."""
    want = [i for p in range(n) for i in np.flatnonzero(mask & (pids == p))]
    k = len(want)
    counts = np.asarray(counts)
    assert counts.shape == (n,)
    assert counts.tolist() == np.bincount(pids[mask], minlength=n).tolist()
    for cv_in, cv_out in zip(cvs, out):
        rows = _py_rows(cv_in, mask.shape[0])
        assert _py_rows(cv_out, k) == [rows[i] for i in want]
        assert cv_out.validity.shape == cv_in.validity.shape
        _dead_tail_is_null(cv_out, k)


def _same_leaves(got, want):
    import jax
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("name", [
    "int64", "int32_date", "bool", "decimal128_limbs", "mixed_widths",
    "strings_nulls_and_empties", "two_string_columns", "list_column",
    "struct_column", "every_row_to_one_partition", "all_rows_dead",
    "no_row_for_some_partitions", "capacity_128", "n_200"])
def test_map_contract(name):
    """What `_finish_map` promises the reduce side, against a plain
    numpy reference; and every leaf of its output, dead rows' slots
    included, equals the argsort-and-take body's byte for byte."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.exec.exchange import _finish_map
    cvs, mask, pids, n = _map_case(name)
    args = (cvs, jnp.asarray(mask), jnp.asarray(pids))
    out, counts = jax.jit(_finish_map, static_argnums=3)(*args, n)
    _hold_map_to_contract(out, counts, cvs, mask, pids, n)
    ref, ref_counts = jax.jit(_argsort_and_take_map,
                              static_argnums=3)(*args, n)
    _same_leaves(out, ref)
    assert np.asarray(counts).tolist() == np.asarray(ref_counts).tolist()


def test_range_map_contract_null_keys():
    """The range exchange's map program: null keys go to partition 0,
    the others where the bounds say, rows in stable order."""
    import jax.numpy as jnp

    from spark_rapids_tpu.exec.exchange import RangeShuffleExchangeExec
    from spark_rapids_tpu.expr.expressions import BoundRef
    cap, n = 512, 4
    rng = np.random.default_rng(32)
    mask = rng.random(cap) < 0.85
    keys = rng.integers(0, 1000, cap).astype(np.int64)
    kvalid = rng.random(cap) < 0.8
    cvs = [_fixed_cv(keys, kvalid),
           _str_cv(_some_strings(rng, cap, 0.1, 0.1))]
    bounds = np.array([250, 500, 750], np.int64)
    out, counts = RangeShuffleExchangeExec._build_map_fn(
        n, [BoundRef(0, dt.INT64)])(cvs, jnp.asarray(mask),
                                    jnp.asarray(bounds))
    pids = np.where(kvalid, np.searchsorted(bounds, keys, side="right"), 0)
    assert (pids[~kvalid] == 0).all() and (~kvalid & mask).any()
    _hold_map_to_contract(out, counts, cvs, mask, pids.astype(np.int32), n)


def _reduce_side_leaves(plan_of):
    """Every reduce-side batch of every shuffle exchange under a planned
    query, as host leaves: (rows, mask bytes, leaf bytes...) a batch."""
    import jax

    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.plan.planner import Planner
    s = _mk_session(**{
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.sql.exec.exchange.reuse.enabled": False})
    root = Planner(s.conf).plan(plan_of(s)._plan)
    ctx = ExecContext(s.conf, s)
    stack, got = [root], []
    while stack:
        node = stack.pop()
        stack.extend(node.children)
        if not isinstance(node, ShuffleExchangeExec):
            continue
        for pid in range(node.n):
            for b in node.execute_partition(ctx, pid):
                got.append((b.num_rows, [
                    (np.asarray(x).dtype.str, np.asarray(x).tobytes())
                    for x in jax.tree.leaves((b.cvs(), b.row_mask))]))
        node.release()
    return got


def test_reduce_side_bytes_equal_argsort_and_take(monkeypatch):
    """A shuffled join over nulls, strings and a decimal128: what the
    reduce side reads from each exchange equals, byte for byte, what it
    read when the map ordered rows by argsort and take."""
    import decimal

    from spark_rapids_tpu.exec import exchange
    from spark_rapids_tpu.runtime import program_cache
    at = _mixed_table(900, seed=5).append_column(
        "d", pa.array([decimal.Decimal(i * 7 - 300) for i in range(900)],
                      pa.decimal128(25, 0)))
    right = pa.table({"k": pa.array(list(range(12)) + [None], pa.int64()),
                      "w": pa.array([f"w{i}" for i in range(13)])})

    def plan_of(s):
        return s.create_dataframe(at).join(s.create_dataframe(right),
                                           on="k")

    program_cache.clear()
    new = _reduce_side_leaves(plan_of)
    assert len(new) >= 2 and sum(rows for rows, _ in new) > 800
    monkeypatch.setattr(exchange, "_finish_map", _argsort_and_take_map)
    program_cache.clear()
    try:
        old = _reduce_side_leaves(plan_of)
    finally:
        program_cache.clear()
    assert new == old


# ---------------------------------------------------------------------
# the map program holds one sort and nothing that indexes the batch's
# rows: the gain of PR 32, held off the chip
# ---------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["hash", "roundrobin", "range",
                                  "with_float64", "with_string"])
def test_map_program_is_one_sort_and_no_gather_over_rows(mode):
    """Lower the map program on the CPU and read the StableHLO: exactly
    one sort (the payload rides it a word a turn of a loop), no scatter
    and no gather whose operand is as long as the batch. A float64
    column cannot become words on the chip and rides a second sort of
    its own type, still ungathered. With a string column the row index
    rides too and `take_strings`' own gathers and scatter stay (the
    reader is not blind)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.exec.exchange import (RangeShuffleExchangeExec,
                                                ShuffleExchangeExec)
    from spark_rapids_tpu.expr.expressions import BoundRef
    cap, n = 4096, 8

    def col(dtype, *tail):
        return CV(jax.ShapeDtypeStruct((cap,) + tail, dtype),
                  jax.ShapeDtypeStruct((cap,), jnp.bool_))

    cvs = [col(jnp.int64), col(jnp.int32), col(jnp.int64, 2),
           col(jnp.bool_), col(jnp.float32)]
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    keys = [BoundRef(0, dt.INT64)]
    if mode == "range":
        fn = RangeShuffleExchangeExec._build_map_fn(n, keys)
        args = (cvs, mask, jax.ShapeDtypeStruct((n - 1,), jnp.int64))
    else:
        if mode == "with_float64":
            cvs += [col(jnp.float64), col(jnp.float64)]
        if mode == "with_string":
            cvs.append(CV(jax.ShapeDtypeStruct((8 * cap,), jnp.uint8),
                          jax.ShapeDtypeStruct((cap,), jnp.bool_),
                          jax.ShapeDtypeStruct((cap + 1,), jnp.int32)))
        fn = ShuffleExchangeExec._build_map_fn(
            n, None if mode == "roundrobin" else keys)
        args = (cvs, mask)
    text = jax.jit(fn).lower(*args).as_text()
    assert text.count('"stablehlo.sort"') == 1 + (mode == "with_float64")
    gathers = indexed_rows(text, "gather")
    scatters = indexed_rows(text, "scatter")
    if mode == "with_string":
        assert scatters and gathers
    else:
        assert scatters == [], scatters
        assert max(gathers, default=0) < cap, gathers


def test_map_counts_words_sorted_and_columns_gathered():
    """`mapSortWords` / `mapGatheredColumns` in `last_metrics()` and in
    EXPLAIN ANALYZE: three int64 columns are six words and their three
    validities share a seventh, nothing gathered; a string column adds
    the row index as a word and is the one column gathered."""
    s = _mk_session(**{
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    n = 1000
    fixed = {c: pa.array(np.arange(n, dtype=np.int64) % m)
             for c, m in (("k", 13), ("a", 7), ("b", 5))}

    def exchange_metrics(q):
        q.to_arrow()
        return [m for m in q.last_metrics().values()
                if "mapSortWords" in m]

    q = s.create_dataframe(fixed).repartition(4, F.col("k"))
    (m,) = exchange_metrics(q)
    passes = -(-n // 256)
    assert m["mapSortWords"] == 7 * passes
    assert m["mapGatheredColumns"] == 0
    plan = q.explain("ANALYZE")
    assert f"mapSortWords={7 * passes}" in plan
    assert "mapGatheredColumns=0" in plan

    with_s = dict(fixed, s=pa.array([f"s{i % 9}" for i in range(n)]))
    q = s.create_dataframe(with_s).repartition(4, F.col("k"))
    (m,) = exchange_metrics(q)
    assert m["mapSortWords"] == 8 * passes       # + the row index
    assert m["mapGatheredColumns"] == passes


def test_shuffle_counts_bytes_at_each_host_boundary():
    """`shuffleRawBytes`, `shuffleD2HBytes`, `shuffleH2DBytes` and
    `shuffleBlocksWritten` in `last_metrics()` and in EXPLAIN ANALYZE's
    `shuffle=`: uncompressed, the file holds the raw blocks and an 8-byte
    length prefix each; the codec changes what is written and nothing
    before it; both copies move at least the live rows' bytes."""
    n = 1000
    table = {c: pa.array(np.arange(n, dtype=np.int64) % m)
             for c, m in (("k", 13), ("a", 7), ("b", 5))}
    live = n * 3 * (8 + 1)                    # int64 data + a validity byte
    seen = {}
    for codec in ("none", "zstd"):
        s = _mk_session(**{
            "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
            "spark.rapids.tpu.shuffle.compression.codec": codec})
        q = s.create_dataframe(table).repartition(4, F.col("k"))
        q.to_arrow()
        (m,) = [m for m in q.last_metrics().values()
                if "shuffleRawBytes" in m]
        seen[codec] = m
        assert m["shuffleBlocksWritten"] > 0
        assert m["shuffleD2HBytes"] >= live
        assert m["shuffleH2DBytes"] >= live
        plan = q.explain("ANALYZE")
        assert f"blocks:{m['shuffleBlocksWritten']}" in plan
        assert "{raw:" in plan and "d2h:" in plan and "h2d:" in plan
    plain, zstd = seen["none"], seen["zstd"]
    assert plain["shuffleBytesWritten"] == (
        plain["shuffleRawBytes"] + 8 * plain["shuffleBlocksWritten"])
    for key in ("shuffleRawBytes", "shuffleBlocksWritten",
                "shuffleD2HBytes", "shuffleH2DBytes"):
        assert zstd[key] == plain[key], key
    assert zstd["shuffleBytesWritten"] != plain["shuffleBytesWritten"]
