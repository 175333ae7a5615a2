"""Cross-query result & fragment cache (runtime/result_cache.py):
hit/miss correctness, write invalidation, LRU budget + host-pressure
eviction, service fast path, and byte-identity vs fresh execution."""
import os
import threading

import pyarrow as pa
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.runtime import result_cache


CACHE_ON = {"spark.rapids.tpu.sql.cache.enabled": True}


@pytest.fixture(autouse=True)
def _fresh_cache():
    result_cache.clear()
    yield
    result_cache.clear()


def _session(extra=None):
    conf = dict(CACHE_ON)
    if extra:
        conf.update(extra)
    return st.TpuSession(conf)


def _table(n=64, seed=0):
    return pa.table({"k": [(i + seed) % 7 for i in range(n)],
                     "v": [float(i * 3 + seed) for i in range(n)]})


# ---------------------------------------------------------------------
# query tier

def test_query_tier_hit_is_byte_identical():
    s = _session()
    df = s.create_dataframe(_table())
    q = lambda: df.group_by("k").agg(total=F.sum("v")).to_arrow()
    r1 = q()
    st1 = result_cache.stats()
    assert st1["result_cache_stores"] == 1
    assert st1["result_cache_misses"] == 1
    r2 = q()
    st2 = result_cache.stats()
    assert st2["result_cache_hits"] == 1
    assert r1.equals(r2)          # byte-identical, not just value-equal


def test_hit_reports_metrics_and_fast_path_counter():
    s = _session()
    df = s.create_dataframe(_table())
    q = df.group_by("k").agg(total=F.sum("v"))
    q.to_arrow()
    base_fp = s.query_manager().stats["cache_fast_path"]
    q2 = df.group_by("k").agg(total=F.sum("v"))
    q2.to_arrow()
    assert s.query_manager().stats["cache_fast_path"] == base_fp + 1
    m = q2.last_metrics()
    assert m.get("ResultCache", {}).get("resultCacheHits") == 1


def test_disabled_by_default_never_stores(session):
    df = session.create_dataframe(_table())
    df.group_by("k").agg(total=F.sum("v")).to_arrow()
    stc = result_cache.stats()
    assert stc["result_cache_stores"] == 0
    assert stc["result_cache_misses"] == 0


def test_different_conf_is_a_different_key():
    s1 = _session()
    s2 = _session({"spark.rapids.tpu.sql.batchSizeRows": 4096})
    t = _table()
    s1.create_dataframe(t).group_by("k").agg(x=F.sum("v")).to_arrow()
    s2.create_dataframe(t).group_by("k").agg(x=F.sum("v")).to_arrow()
    # second session's conf differs -> its lookup must not hit
    assert result_cache.stats()["result_cache_hits"] == 0


# ---------------------------------------------------------------------
# invalidation: external writes, engine writes, uncache()

def test_parquet_overwrite_invalidates(tmp_path):
    s = _session()
    p = str(tmp_path / "t")
    s.create_dataframe(_table(seed=1)).write_parquet(p)
    q = lambda: s.read.parquet(p).agg(total=F.sum("v")).to_arrow()
    r1 = q()
    assert q().equals(r1)
    assert result_cache.stats()["result_cache_hits"] == 1
    s.create_dataframe(pa.table({"k": [0], "v": [41.5]})).write_parquet(
        p, mode="overwrite")
    stc = result_cache.stats()
    assert stc["result_cache_invalidations"] >= 1
    r2 = q()
    assert r2.column("total").to_pylist() == [41.5]
    assert result_cache.stats()["result_cache_hits"] == 1  # no new hit


def test_external_overwrite_detected_by_snapshot(tmp_path):
    """No engine write hook fires here: the parquet file is replaced
    behind the engine's back; the bind-time snapshot must catch it."""
    import pyarrow.parquet as pq
    s = _session()
    p = str(tmp_path / "ext")
    os.makedirs(p)
    f = os.path.join(p, "part-00000.parquet")
    pq.write_table(pa.table({"v": [1.0, 2.0]}), f)
    q = lambda: s.read.parquet(f).agg(total=F.sum("v")).to_arrow()
    assert q().column("total").to_pylist() == [3.0]
    assert q().column("total").to_pylist() == [3.0]
    assert result_cache.stats()["result_cache_hits"] == 1
    os.remove(f)
    pq.write_table(pa.table({"v": [10.0, 20.0]}), f)
    assert q().column("total").to_pylist() == [30.0]


def test_snapshot_refresh_without_cache(tmp_path):
    """The snapshot satellite holds with the cache OFF: a bound
    DataFrame re-executed after an overwrite serves the NEW data."""
    s = st.TpuSession()
    p = str(tmp_path / "t")
    s.create_dataframe(pa.table({"v": [1.0, 2.0]})).write_parquet(p)
    df = s.read.parquet(p).agg(total=F.sum("v"))
    assert df.to_arrow().column("total").to_pylist() == [3.0]
    s.create_dataframe(pa.table({"v": [7.0]})).write_parquet(
        p, mode="overwrite")
    assert df.to_arrow().column("total").to_pylist() == [7.0]


def test_delta_append_and_optimize_invalidate(tmp_path):
    s = _session()
    p = str(tmp_path / "d")
    s.create_dataframe(pa.table({"v": [1.0, 2.0]})).write_delta(p)
    q = lambda: s.read.delta(p).agg(total=F.sum("v")).to_arrow()
    assert q().column("total").to_pylist() == [3.0]
    assert q().column("total").to_pylist() == [3.0]
    assert result_cache.stats()["result_cache_hits"] == 1
    s.create_dataframe(pa.table({"v": [4.0]})).write_delta(
        p, mode="append")
    assert q().column("total").to_pylist() == [7.0]
    assert result_cache.stats()["result_cache_hits"] == 1
    # OPTIMIZE rewrites files without changing data: entries over the
    # old files drop, and the post-OPTIMIZE read stays correct
    from spark_rapids_tpu.io.delta import optimize_delta
    s.create_dataframe(pa.table({"v": [5.0]})).write_delta(
        p, mode="append")
    optimize_delta(s, p, min_files=2)
    assert q().column("total").to_pylist() == [12.0]


def test_uncache_drops_plan_entries():
    s = _session()
    df = s.create_dataframe(_table()).cache()
    df.to_arrow()
    df.to_arrow()
    assert result_cache.stats()["result_cache_hits"] == 1
    df.uncache()
    assert result_cache.stats()["result_cache_invalidations"] >= 1
    df2 = s.create_dataframe(_table())
    r = df2.to_arrow()
    assert r.num_rows == 64


# ---------------------------------------------------------------------
# memory discipline

def test_lru_eviction_under_byte_cap():
    s = _session({"spark.rapids.tpu.sql.cache.maxBytes": 4096,
                  "spark.rapids.tpu.sql.cache.maxEntryBytes": 4096})
    df = s.create_dataframe(_table(n=256))
    for i in range(8):
        # each full-width projection result is ~2KB: 8 of them overflow
        # the 4KB cap and must age out the oldest entries
        df.select((F.col("v") + float(i)).alias("x")).to_arrow()
    stc = result_cache.stats()
    assert stc["result_cache_bytes"] <= 4096
    assert stc["result_cache_evictions"] > 0


def test_oversize_entry_rejected():
    s = _session({"spark.rapids.tpu.sql.cache.maxEntryBytes": 8})
    df = s.create_dataframe(_table(n=256))
    r = df.group_by("k").agg(x=F.sum("v")).to_arrow()
    assert r.num_rows > 0
    stc = result_cache.stats()
    assert stc["result_cache_rejected"] >= 1
    assert stc["result_cache_entries"] == 0


def test_host_pressure_evicts_cache_first():
    from spark_rapids_tpu.memory.host import HostMemoryManager
    mgr = HostMemoryManager(budget_bytes=1 << 20)
    result_cache.set_host_manager(mgr)
    s = _session()
    df = s.create_dataframe(_table(n=512))
    df.group_by("k").agg(x=F.sum("v")).to_arrow()
    assert result_cache.stats()["result_cache_entries"] == 1
    assert mgr.reserved > 0
    # another consumer takes the whole budget: the cache's pressure
    # hook must evict its entries to make room (cache spills first)
    mgr.reserve(1 << 20)
    stc = result_cache.stats()
    assert stc["result_cache_entries"] == 0
    assert stc["result_cache_evictions"] >= 1
    mgr.release(1 << 20)


# ---------------------------------------------------------------------
# concurrency

def test_concurrent_hit_miss_hammer():
    s = _session()
    df = s.create_dataframe(_table(n=128))
    builds = [lambda i=i: df.group_by("k").agg(
        x=F.sum(F.col("v") * float(i + 1))) for i in range(3)]
    refs = [b().to_arrow() for b in builds]   # warm: 3 stores
    base = result_cache.stats()
    errors = []

    def worker(wid):
        try:
            for j in range(6):
                r = builds[(wid + j) % 3]().to_arrow()
                if not r.equals(refs[(wid + j) % 3]):
                    errors.append(f"w{wid} iter{j}: result mismatch")
        except Exception as e:  # noqa: BLE001
            errors.append(f"w{wid}: {e!r}")

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]
    stc = result_cache.stats()
    hits = stc["result_cache_hits"] - base["result_cache_hits"]
    misses = stc["result_cache_misses"] - base["result_cache_misses"]
    # every one of the 48 lookups resolved to exactly a hit or a miss
    assert hits + misses == 8 * 6
    assert hits > 0


def test_fast_path_bypasses_admission():
    s = _session({"spark.rapids.tpu.sql.service.maxConcurrentQueries": 1})
    df = s.create_dataframe(_table())
    q = df.group_by("k").agg(x=F.sum("v"))
    r1 = q.to_arrow()                       # populate
    mgr = s.query_manager()
    # occupy the single admission slot with an open query...
    blocker = mgr.open_query(plan=None, conf=s.conf, action="blocker")
    try:
        done = []

        def cached_run():
            done.append(df.group_by("k").agg(x=F.sum("v")).to_arrow())

        t = threading.Thread(target=cached_run)
        t.start()
        t.join(timeout=30)
        # ...the cached query must complete anyway: a hit takes the
        # fast path and never waits on the scheduler
        assert not t.is_alive(), \
            "cached query blocked behind a full admission queue"
        assert done and done[0].equals(r1)
    finally:
        mgr.close_query(blocker, result=None)


# ---------------------------------------------------------------------
# fragment tier

def test_fragment_tier_hit_and_explain_annotation():
    s = _session({"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    left = s.create_dataframe(pa.table(
        {"a": [i % 5 for i in range(400)],
         "b": [float(i) for i in range(400)]}))
    right = s.create_dataframe(pa.table(
        {"a": [0, 1, 2, 3], "c": [10.0, 20.0, 30.0, 40.0]}))
    q1 = left.join(right, on="a").agg(n=F.count(F.lit(1)))
    q1.to_arrow()
    assert result_cache.stats()["result_cache_fragment_stores"] >= 1
    # different downstream agg over the SAME join: the exchange map
    # output must come from the fragment tier
    q2 = left.join(right, on="a").agg(sb=F.sum("b"))
    r2 = q2.to_arrow()
    stc = result_cache.stats()
    assert stc["result_cache_fragment_hits"] >= 1
    assert "CachedFragmentExec" in q2._last_root.tree_string()
    # and the result matches a cache-free session
    s2 = st.TpuSession(
        {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    l2 = s2.create_dataframe(pa.table(
        {"a": [i % 5 for i in range(400)],
         "b": [float(i) for i in range(400)]}))
    r2b = l2.join(s2.create_dataframe(pa.table(
        {"a": [0, 1, 2, 3], "c": [10.0, 20.0, 30.0, 40.0]})),
        on="a").agg(sb=F.sum("b")).to_arrow()
    assert r2.equals(r2b)


def test_fragment_hit_after_invalidating_side_write(tmp_path):
    """The `fragment_hits: 0` regression scenario, done right. A
    repeat-heavy zipfian run once showed zero fragment hits not because
    fragment keying was broken but because its streams were served from
    the whole-query tier (no replanning => substitute_fragments never
    ran) and its only replanned query had no shuffle exchange. This
    test forces the real workflow the fragment tier exists for: a
    two-table shuffle join, a write that invalidates ONE side, and a
    re-run that must reuse the surviving side's exchange fragment."""
    import pyarrow.parquet as pq
    s = _session({
        # force a distributed shuffle join with real exchanges: no
        # broadcast, small batches, 2 shuffle partitions, multi-file
        # scans so the planner keeps >1 input partition
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
        "spark.rapids.tpu.sql.batchSizeRows": 64,
        "spark.rapids.tpu.sql.shuffle.partitions": 2})
    left_dir, right_dir = str(tmp_path / "L"), str(tmp_path / "R")
    os.makedirs(left_dir), os.makedirs(right_dir)
    for i in range(3):
        pq.write_table(pa.table(
            {"a": [(j + i * 50) % 7 for j in range(50)],
             "b": [float(j + i) for j in range(50)]}),
            os.path.join(left_dir, f"p{i}.parquet"))
        pq.write_table(pa.table(
            {"a": [(j + i * 50) % 7 for j in range(50)],
             "c": [float(j * 2 + i) for j in range(50)]}),
            os.path.join(right_dir, f"p{i}.parquet"))

    def q():
        l = s.read.parquet(left_dir)
        r = s.read.parquet(right_dir)
        return l.join(r, on="a").agg(n=F.count(F.lit(1)),
                                     sb=F.sum("b")).to_arrow()

    r1 = q()
    assert result_cache.stats()["result_cache_fragment_stores"] >= 2
    # overwrite the RIGHT table: its scan snapshot changes, its
    # fragments die, the whole-query entry dies — but the LEFT side's
    # exchange fragment survives and must be reused on the re-run
    pq.write_table(pa.table({"a": [0, 1, 2], "c": [9.0, 9.0, 9.0]}),
                   os.path.join(right_dir, "p0.parquet"))
    h0 = result_cache.stats()["result_cache_fragment_hits"]
    r2 = q()
    stc = result_cache.stats()
    assert stc["result_cache_fragment_hits"] > h0, \
        "surviving side's fragment must hit after the side write"
    # and correctness: a cache-free session on the new files agrees
    s2 = st.TpuSession({
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
        "spark.rapids.tpu.sql.batchSizeRows": 64,
        "spark.rapids.tpu.sql.shuffle.partitions": 2})
    fresh = s2.read.parquet(left_dir).join(
        s2.read.parquet(right_dir), on="a").agg(
        n=F.count(F.lit(1)), sb=F.sum("b")).to_arrow()
    assert r2.equals(fresh)
    assert not r2.equals(r1)


def test_fragments_disabled_conf():
    s = _session({"spark.rapids.tpu.sql.cache.fragments.enabled": False,
                  "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    left = s.create_dataframe(pa.table(
        {"a": [i % 3 for i in range(200)],
         "b": [float(i) for i in range(200)]}))
    right = s.create_dataframe(pa.table({"a": [0, 1], "c": [1.0, 2.0]}))
    left.join(right, on="a").agg(n=F.count(F.lit(1))).to_arrow()
    assert result_cache.stats()["result_cache_fragment_stores"] == 0


# ---------------------------------------------------------------------
# byte identity against fresh execution, TPC-H shapes

def _tpch_identity(qids, sf):
    from spark_rapids_tpu.workloads import tpch
    tabs = tpch.gen_all(sf=sf, seed=11)
    reg = tpch.queries()
    s_fresh = st.TpuSession()
    dfs_fresh = {k: s_fresh.create_dataframe(v) for k, v in tabs.items()}
    s_cache = _session()
    dfs_cache = {k: s_cache.create_dataframe(v) for k, v in tabs.items()}
    for qn in qids:
        fresh = reg[qn](dfs_fresh).to_arrow()
        first = reg[qn](dfs_cache).to_arrow()
        served = reg[qn](dfs_cache).to_arrow()
        assert first.equals(fresh), f"q{qn}: fresh vs first run diverge"
        assert served.equals(fresh), f"q{qn}: cached result diverges"
    assert result_cache.stats()["result_cache_hits"] >= len(qids)


def test_tpch_cached_byte_identity_subset():
    _tpch_identity((1, 3, 6, 12, 14, 19), sf=0.004)


@pytest.mark.slow
def test_tpch_cached_byte_identity_all22():
    from spark_rapids_tpu.workloads import tpch
    _tpch_identity(sorted(tpch.queries()), sf=0.004)
