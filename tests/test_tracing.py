"""Distributed tracing (profiler/tracing.py + critical_path.py): span
API and parenting, deterministic sampling, wire/conf propagation,
single-trace assembly across the distributed runner's executor
processes, critical-path attribution of an injected slow fetch (the
fault-harness cross-check), and the EventLogWriter concurrency/crash
contract the trace records ride on."""
import json
import os
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.cluster.driver import ClusterManager
from spark_rapids_tpu.cluster.query import DistributedRunner
from spark_rapids_tpu.config import (TRACE_ENABLED, TRACE_SAMPLE_RATE,
                                     TpuConf)
from spark_rapids_tpu.expr.expressions import col
from spark_rapids_tpu.profiler import critical_path, tracing
from spark_rapids_tpu.profiler.event_log import (EventLogWriter,
                                                 read_event_log)
from spark_rapids_tpu.workloads import tpch, tpch_cluster

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))
import profile_report  # noqa: E402


# ----------------------------------------------------------------------
# span API
# ----------------------------------------------------------------------
def test_span_nesting_and_parenting():
    tc = tracing.start_trace("unit-q1", TpuConf({}))
    assert tc is not None and tc.trace_id == "unit-q1"
    root = tracing.open_span("query", "query", tc)
    try:
        with tracing.use(tracing.TraceContext("unit-q1", root.span_id,
                                              True)):
            with tracing.span("plan", "plan") as p:
                p.set("nodes", 7)
                with tracing.span("compile.sync", "compile"):
                    pass
            # after the with-block the TLS context is restored
            assert tracing.current().span_id == root.span_id
    finally:
        root.end()
    spans = tracing.drain_trace("unit-q1")
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"query", "plan", "compile.sync"}
    assert by_name["query"]["parent_id"] is None
    assert by_name["plan"]["parent_id"] == root.span_id
    assert by_name["compile.sync"]["parent_id"] \
        == by_name["plan"]["span_id"]
    assert by_name["plan"]["attrs"] == {"nodes": 7}
    for s in spans:
        assert s["end_ns"] >= s["start_ns"] and s["dur_ms"] >= 0
        assert s["proc"] == os.getpid()
        assert json.loads(json.dumps(s)) == s
    # drained: a second drain is empty, and stragglers are dropped
    assert tracing.drain_trace("unit-q1") == []
    d0 = tracing.dropped_spans()
    tracing.open_span("late", "compile", tc).end()
    assert tracing.drain_trace("unit-q1") == []
    assert tracing.dropped_spans() == d0 + 1


def test_off_trace_is_noop():
    with tracing.use(None):
        assert tracing.current() is None
        sp = tracing.open_span("x", "compile")
        sp.set("a", 1)
        sp.end()                         # no-op span: nothing recorded
        with tracing.span("y", "plan") as sp2:
            sp2.set("b", 2)
        tracing.record_wait_span("w", "queue", 50.0)


def test_sampling_deterministic():
    off = TpuConf({TRACE_ENABLED.key: False})
    assert tracing.start_trace("q", off) is None
    zero = TpuConf({TRACE_SAMPLE_RATE.key: 0.0})
    assert tracing.start_trace("q", zero) is None
    half = TpuConf({TRACE_SAMPLE_RATE.key: 0.5})
    ids = [f"query-{i}" for i in range(400)]
    first = [tracing.start_trace(q, half) is not None for q in ids]
    second = [tracing.start_trace(q, half) is not None for q in ids]
    # deterministic per query id: a retried query (and its executor
    # fragments) agree on the decision with no coordination
    assert first == second
    frac = sum(first) / len(first)
    assert 0.35 < frac < 0.65


def test_wire_and_conf_propagation():
    tc = tracing.TraceContext("qid-7", "abc.1", True)
    back = tracing.from_wire(tracing.to_wire(tc))
    assert (back.trace_id, back.span_id) == ("qid-7", "abc.1")
    assert tracing.from_wire(None) is None
    assert tracing.from_wire("garbage") is None

    settings = {"spark.rapids.tpu.sql.batchSizeRows": 64}
    out = tracing.inject_into_conf(settings, tc)
    assert out is not settings
    assert out[tracing.TRACE_CONF_KEY] == "qid-7|abc.1"
    # off-trace: identity, no copy, no key
    assert tracing.inject_into_conf(settings, None) is settings
    adopted = tracing.adopt_from_conf(out)
    assert (adopted.trace_id, adopted.span_id) == ("qid-7", "abc.1")
    assert tracing.adopt_from_conf(settings) is None
    assert tracing.adopt_from_conf(TpuConf(out)).trace_id == "qid-7"


def test_record_wait_span_is_backdated():
    tc = tracing.TraceContext("unit-wait", None, True)
    tracing.record_wait_span("admission.queue", "queue", 125.0, ctx=tc,
                             pool="etl")
    (s,) = tracing.drain_trace("unit-wait")
    assert s["kind"] == "queue" and s["dur_ms"] == 125.0
    assert s["end_ns"] - s["start_ns"] == int(125.0 * 1e6)
    assert s["end_ns"] <= time.time_ns()
    assert s["attrs"] == {"pool": "etl"}
    # zero/negative waits record nothing
    tracing.record_wait_span("w", "queue", 0.0, ctx=tc)
    assert tracing.drain_trace("unit-wait") == []


# ----------------------------------------------------------------------
# critical-path decomposition
# ----------------------------------------------------------------------
def _sp(name, kind, a_ms, b_ms, span_id, parent=None):
    return {"trace_id": "t", "span_id": span_id, "parent_id": parent,
            "name": name, "kind": kind, "start_ns": int(a_ms * 1e6),
            "end_ns": int(b_ms * 1e6), "dur_ms": b_ms - a_ms, "proc": 1}


def test_summarize_attributes_shares_to_deepest_edge():
    spans = [_sp("query", "query", 0, 100, "r"),
             _sp("fetch", "fetch", 0, 60, "f", "r"),
             _sp("compile", "compile", 60, 80, "c", "r")]
    summ = critical_path.summarize(spans)
    assert summ["total_ms"] == pytest.approx(100.0)
    assert summ["shares"]["shuffle_fetch"] == pytest.approx(60.0)
    assert summ["shares"]["compile"] == pytest.approx(20.0)
    assert summ["shares"]["compute"] == pytest.approx(20.0)
    assert summ["dominant"] == "shuffle_fetch"
    assert summ["dominant_pct"] == pytest.approx(60.0)
    assert sum(summ["shares"].values()) == pytest.approx(
        summ["total_ms"])


def test_summarize_depth_beats_breadth():
    """A nested non-compute span blames its instants, not its
    ancestor: the deepest covering span is the most specific cause."""
    spans = [_sp("query", "query", 0, 100, "r"),
             _sp("task", "task", 0, 100, "t", "r"),
             _sp("spill", "spill_write", 30, 90, "s", "t")]
    summ = critical_path.summarize(spans)
    assert summ["shares"]["spill"] == pytest.approx(60.0)
    assert summ["shares"]["compute"] == pytest.approx(40.0)
    assert summ["dominant"] == "spill"


def test_summarize_dominant_floor_and_wall_rescale():
    # a 2ms blip on a 100ms query is noise, not the critical path
    spans = [_sp("query", "query", 0, 100, "r"),
             _sp("fetch", "fetch", 10, 12, "f", "r")]
    summ = critical_path.summarize(spans)
    assert summ["dominant"] == "compute"
    # true wall > span hull: the missing slivers count as compute
    summ2 = critical_path.summarize(spans, wall_s=0.2)
    assert summ2["total_ms"] == pytest.approx(200.0)
    assert summ2["shares"]["compute"] == pytest.approx(198.0)
    assert critical_path.summarize([]) is None


def test_dominant_of_pct_mirrors_summarize_rule():
    assert critical_path.dominant_of_pct(
        {"compute": 40.0, "compile": 35.0, "queue": 25.0}) == "compile"
    assert critical_path.dominant_of_pct(
        {"compute": 98.0, "compile": 2.0}) == "compute"


# ----------------------------------------------------------------------
# local end-to-end: one trace per query in the event log
# ----------------------------------------------------------------------
def _session(tmp_path, **extra):
    return st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 4096,
        "spark.rapids.tpu.sql.eventLog.enabled": True,
        "spark.rapids.tpu.sql.eventLog.dir": str(tmp_path / "events"),
        **extra})


def _run_small_query(s):
    df = s.create_dataframe({
        "k": list(range(500)),
        "v": [float(i % 13) for i in range(500)]})
    return (df.filter(col("v") > 2.0).group_by("k")
            .agg(F.sum(col("v")).alias("sv")).to_arrow())


def test_local_trace_assembles_in_event_log(tmp_path):
    s = _session(tmp_path)
    out = _run_small_query(s)
    assert out.num_rows > 0
    evs = read_event_log(s.last_event_log)
    qid = evs[0]["query_id"]
    spans = [e for e in evs if e["event"] == "trace_span"]
    assert spans, "tracing is on by default: spans must be emitted"
    # ONE trace per query: trace_id == query_id on every span
    assert {sp["trace_id"] for sp in spans} == {qid}
    kinds = {sp["kind"] for sp in spans}
    assert "query" in kinds and "plan" in kinds and "queue" in kinds
    roots = [sp for sp in spans if sp["kind"] == "query"]
    assert len(roots) == 1 and roots[0]["parent_id"] is None
    # ONE rooted tree: every other span (plan and the back-dated
    # admission wait included) parents inside the trace, not beside it
    assert all(sp["parent_id"] is not None
               for sp in spans if sp is not roots[0])
    # the critical-path summary rides the log too, and is consistent
    (summ,) = [e for e in evs if e["event"] == "trace_summary"]
    assert summ["span_count"] == len(spans)
    assert summ["dominant"] in critical_path.CATEGORIES
    assert sum(summ["shares"].values()) \
        == pytest.approx(summ["total_ms"], rel=1e-3)
    wall = next(e for e in evs if e["event"] == "query_end")["wall_s"]
    assert summ["total_ms"] >= wall * 1e3 * 0.99


def test_trace_conf_gates(tmp_path):
    s = _session(tmp_path, **{
        "spark.rapids.tpu.sql.trace.enabled": False})
    _run_small_query(s)
    evs = read_event_log(s.last_event_log)
    assert not [e for e in evs if e["event"] == "trace_span"]
    s2 = _session(tmp_path, **{
        "spark.rapids.tpu.sql.trace.sampleRate": 0.0})
    _run_small_query(s2)
    evs2 = read_event_log(s2.last_event_log)
    assert not [e for e in evs2 if e["event"] == "trace_span"]


def test_cli_trace_report(tmp_path, capsys):
    s = _session(tmp_path)
    _run_small_query(s)
    assert profile_report.main(["--trace", s.last_event_log]) == 0
    out = capsys.readouterr().out
    assert "== trace " in out
    assert "critical path:" in out
    assert "[query@" in out              # the waterfall's root row


# ----------------------------------------------------------------------
# distributed: executor spans come home and assemble into one trace
# ----------------------------------------------------------------------
def _write_splits(tmp_path, n_splits, sf=0.01):
    li = tpch.gen_lineitem(sf=sf, seed=7)
    cust = tpch.gen_customer(sf=sf, seed=7)
    orders = tpch.gen_orders(sf=sf, seed=7)
    cust_p = str(tmp_path / "customer.parquet")
    ord_p = str(tmp_path / "orders.parquet")
    pq.write_table(cust, cust_p)
    pq.write_table(orders, ord_p)
    n = li.num_rows
    splits = []
    for i in range(n_splits):
        sl = li.slice(i * n // n_splits,
                      (i + 1) * n // n_splits - i * n // n_splits)
        p = str(tmp_path / f"lineitem-{i}.parquet")
        pq.write_table(sl, p)
        splits.append({"lineitem": p, "customer": cust_p,
                       "orders": ord_p})
    return splits


def _dist_conf(tmp_path):
    return {"spark.rapids.tpu.sql.batchSizeRows": 8192,
            "spark.rapids.tpu.sql.eventLog.enabled": True,
            "spark.rapids.tpu.sql.eventLog.dir":
                str(tmp_path / "events")}


def test_distributed_trace_and_fetch_delay_blame(tmp_path,
                                                 monkeypatch, capsys):
    """Two runs on one cluster.

    Run 1 (cold): executor-side task spans ride the task-metric side
    channel home and parent under the driver's stage spans — one trace.
    Run 2 (same executors, compile caches warm from run 1): the
    fault-harness cross-check — an injected block.fetch delay must make
    shuffle_fetch the dominant critical-path edge, both in the
    trace_summary record and in profile_report --trace.  The warm
    second run makes the dominance deterministic: on a cold cluster the
    XLA compile edge can rival the injected delay."""
    from spark_rapids_tpu.runtime import faults
    monkeypatch.setenv("SRTPU_FAULTS", "block.fetch:delay=1500")
    splits = _write_splits(tmp_path, n_splits=2)
    cm = ClusterManager(2)
    cm.start()
    try:
        runner = DistributedRunner(cm, _dist_conf(tmp_path))
        runner.run(splits, tpch_cluster.q6_map, part_keys=["g"],
                   reduce_fn=tpch_cluster.q6_reduce, n_reduce=1)
        log1 = runner.last_event_log
        qid1 = runner.last_profile["query_id"]
        ea1 = runner.explain_analyze()
        runner.run(splits, tpch_cluster.q6_map, part_keys=["g"],
                   reduce_fn=tpch_cluster.q6_reduce, n_reduce=1)
        log2 = runner.last_event_log
    finally:
        cm.shutdown()
        faults.clear_plan()

    # -- run 1: cross-process assembly ---------------------------------
    evs = read_event_log(log1)
    spans = [e for e in evs if e["event"] == "trace_span"]
    assert spans
    assert {sp["trace_id"] for sp in spans} == {qid1}
    # spans from more than one process: the driver plus executors
    procs = {sp["proc"] for sp in spans}
    assert os.getpid() in procs and len(procs) >= 2
    by_id = {sp["span_id"]: sp for sp in spans}
    stage_ids = {sp["span_id"] for sp in spans if sp["kind"] == "stage"}
    tasks = [sp for sp in spans if sp["kind"] == "task"]
    assert tasks and stage_ids
    for t in tasks:
        assert t["proc"] != os.getpid()
        assert t["parent_id"] in stage_ids     # driver-stage parenting
    # executor fetch spans parent under their executor task span
    fetches = [sp for sp in spans if sp["kind"] == "fetch"]
    assert fetches
    for fsp in fetches:
        assert by_id[fsp["parent_id"]]["kind"] == "task"
    (summ1,) = [e for e in evs if e["event"] == "trace_summary"]
    assert summ1["span_count"] == len(spans)
    # the EXPLAIN ANALYZE root annotation names the same edge
    assert ea1.splitlines()[0].startswith(
        f"criticalPath={summ1['dominant']}")

    # -- run 2: injected delay owns the critical path ------------------
    evs2 = read_event_log(log2)
    (summ2,) = [e for e in evs2 if e["event"] == "trace_summary"]
    assert summ2["dominant"] == "shuffle_fetch", summ2
    assert summ2["shares"]["shuffle_fetch"] >= 1500.0
    assert profile_report.main(["--trace", log2]) == 0
    out = capsys.readouterr().out
    assert "critical path: shuffle_fetch" in out
    assert "shuffle.fetch_blocks" in out


# ----------------------------------------------------------------------
# overhead gate: tracing ON stays within budget on a q6-shaped query
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_q6_tracing_overhead_under_three_percent():
    at = pa.table({
        "k": pa.array(np.arange(60_000) % 50, type=pa.int64()),
        "v": pa.array(np.random.default_rng(6).normal(0, 1, 60_000)),
    })

    def best_of(extra, n=5):
        sess = st.TpuSession({
            "spark.rapids.tpu.sql.batchSizeRows": 8192, **extra})
        df = sess.create_dataframe(at)
        q = (df.filter(col("v") > 0.0).group_by("k")
             .agg(F.sum(col("v")).alias("sv")))
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            q.to_arrow()
            best = min(best, time.perf_counter() - t0)
        return best

    off = best_of({"spark.rapids.tpu.sql.trace.enabled": False})
    on = best_of({"spark.rapids.tpu.sql.trace.enabled": True})
    # 2x the 3% budget + a constant slack so loaded CI machines do not
    # flake (the same headroom pattern as the ledger overhead gate)
    assert on <= off * 1.06 + 0.05, (on, off)


# ----------------------------------------------------------------------
# EventLogWriter: the concurrency/crash contract trace records ride on
# ----------------------------------------------------------------------
def test_event_log_writer_concurrent_emit(tmp_path):
    """Racing emitters (query thread + pool workers + absorb) produce
    whole lines — no interleaved/torn records."""
    p = str(tmp_path / "races.jsonl")
    w = EventLogWriter(p, "q-races")
    n_threads, per = 8, 250

    def emitter(t):
        for i in range(per):
            w.emit("tick", thread=t, i=i, pad="x" * 64)

    ts = [threading.Thread(target=emitter, args=(t,))
          for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    w.close()
    evs = read_event_log(p)
    assert len(evs) == n_threads * per   # read_event_log skips torn
    seen = {(e["thread"], e["i"]) for e in evs}
    assert len(seen) == n_threads * per


def test_event_log_writer_survives_dead_volume(tmp_path):
    """An OSError mid-query (full/yanked log volume) silently disables
    the writer instead of failing the query; the prefix stays
    readable."""
    p = str(tmp_path / "dead.jsonl")
    w = EventLogWriter(p, "q-dead")
    w.emit("alpha")
    os.close(w._f.fileno())              # yank the volume
    w.emit("beta")                       # must not raise
    w.emit("gamma")
    w.close()                            # idempotent, still quiet
    evs = read_event_log(p)
    assert [e["event"] for e in evs] == ["alpha"]


# ----------------------------------------------------------------------
# the second sink: every span is a TraceAnnotation on the profiler's
# clock ("srt." + name), whether or not a dict record is kept
# ----------------------------------------------------------------------
def _profiled(tmp_path, body):
    """Run `body()` under the jax profiler and return its `srt.` host
    events as [(thread line index, name, start_ns, end_ns, stats)]."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(
        tmp_path / "prof" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend(
                (i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for e in line.events
                if e.name.startswith(tracing.PREFIX))
    return out


def _inside(events, inner, outer):
    """Every `inner` event lies within some `outer` event of its own
    thread (annotations nest per thread)."""
    outers = [e for e in events if e[1] == outer]
    inners = [e for e in events if e[1] == inner]
    return bool(inners) and all(
        any(o[0] == i[0] and o[2] <= i[2] and i[3] <= o[3]
            for o in outers) for i in inners)


@pytest.mark.parametrize("trace_on", [True, False])
def test_spans_land_on_the_profilers_clock(tmp_path, trace_on):
    at = pa.table({
        "k": pa.array(np.arange(20_000) % 50, type=pa.int64()),
        "v": pa.array(np.random.default_rng(6).normal(0, 1, 20_000))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(at, path, compression="snappy", row_group_size=5_000)
    s = _session(tmp_path, **{
        "spark.rapids.tpu.sql.trace.enabled": trace_on,
        # set explicitly, so the device decode runs on the CPU backend
        "spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled": True})
    df = s.create_dataframe(at)

    def queries():
        (df.filter(col("v") > 0.0).group_by("k")
         .agg(F.sum(col("v")).alias("sv")).to_arrow())
        (s.read.parquet(path).filter(col("v") > 0.0)
         .select((col("v") * 2).alias("w")).to_arrow())

    queries()                           # compile outside the profile
    events = _profiled(tmp_path, queries)
    names = {e[1] for e in events}
    assert {"srt.query", "srt.admit", "srt.plan", "srt.prewarm",
            "srt.collect", "srt.export", "srt.fetch",
            "srt.HashAggregateExec.opTime",
            "srt.launch.HashAggregateExec.hash_update",
            "srt.launch.FusedStageExec.run", "srt.cache.key",
            "srt.exchange.map",
            "srt.io.read", "srt.io.upload", "srt.io.decode"} <= names
    # nested as docs/observability.md says, thread by thread
    assert _inside(events, "srt.plan", "srt.query")
    assert _inside(events, "srt.prewarm", "srt.query")
    assert _inside(events, "srt.collect", "srt.query")
    assert _inside(events, "srt.export", "srt.collect")
    assert _inside(events, "srt.launch.FusedStageExec.run",
                   "srt.FusedStageExec.opTime")
    assert _inside(events, "srt.launch.HashAggregateExec.hash_update",
                   "srt.HashAggregateExec.opTime")
    assert _inside(events, "srt.io.upload", "srt.io.decode")
    assert _inside(events, "srt.io.read", "srt.ParquetScanExec.scanTime")
    # admission is beside the query, not inside it
    assert not _inside(events, "srt.admit", "srt.query")
    # on worker threads too: the exchange map pool and the scan's
    # prefetch thread launch, fetch and read off the query's thread
    main = {e[0] for e in events if e[1] == "srt.query"}
    assert len(main) == 1
    for name in ("srt.launch.HashAggregateExec.hash_update", "srt.fetch",
                 "srt.io.read"):
        assert {e[0] for e in events if e[1] == name} - main, name
    # stats, not names, carry the ids
    op = next(e for e in events if e[1] == "srt.FusedStageExec.opTime")
    assert op[4]["op"].startswith("FusedStageExec@")
    root = next(e for e in events if e[1] == "srt.query")
    assert ("query" in root[4]) == trace_on
    # the dict sink keeps to the conf, and to its own vocabulary
    recorded = [e for e in read_event_log(s.last_event_log)
                if e["event"] == "trace_span"]
    assert bool(recorded) == trace_on
    assert not {sp["kind"] for sp in recorded} & tracing.PROFILER_ONLY


SHUFFLE_WRITE = ("srt.shuffle.slice", "srt.shuffle.serialize",
                 "srt.shuffle.compress", "srt.shuffle.write")
SHUFFLE_READ = ("srt.shuffle.read", "srt.shuffle.decompress",
                "srt.shuffle.decode", "srt.shuffle.assemble",
                "srt.shuffle.upload")


@pytest.mark.parametrize("codec", ["zstd", "none"])
def test_shuffle_and_agg_phases_land_on_the_profilers_clock(tmp_path, codec):
    """The one-chip exchange's host phases and the whole-input aggregate's
    argument building are spans on the profiler's clock, on the thread
    that does the work, and never dict records."""
    at = pa.table({
        "k": pa.array(np.arange(20_000) % 50, type=pa.int64()),
        "v": pa.array(np.random.default_rng(7).normal(0, 1, 20_000))})
    s = _session(tmp_path, **{
        "spark.rapids.tpu.sql.trace.enabled": True,
        "spark.rapids.tpu.shuffle.compression.codec": codec})
    df = s.create_dataframe(at)
    cached = s.create_dataframe(at).cache()

    def queries():
        (df.filter(col("v") > 0.0).group_by("k")
         .agg(F.sum(col("v")).alias("sv")).to_arrow())
        cached.filter(col("v") > 0.0).agg(F.sum(col("v")).alias("s")) \
            .to_arrow()

    queries()                           # compile outside the profile
    events = _profiled(tmp_path, queries)
    names = {e[1] for e in events}
    codec_spans = {"srt.shuffle.compress", "srt.shuffle.decompress"}
    phases = set(SHUFFLE_WRITE + SHUFFLE_READ)
    if codec == "none":
        assert not codec_spans & names
        phases -= codec_spans
    assert phases | {"srt.agg.whole_args", "srt.agg.pad"} <= names
    main = {e[0] for e in events if e[1] == "srt.query"}
    write_time = [e for e in events
                  if e[1] == "srt.ShuffleExchangeExec.writeTime"]
    # the map side lies inside writeTime or on a worker thread
    for ev in (e for e in events if e[1] in SHUFFLE_WRITE):
        assert ev[0] not in main or any(
            w[0] == ev[0] and w[2] <= ev[2] and ev[3] <= w[3]
            for w in write_time), ev
    assert _inside(events, "srt.shuffle.write",
                   "srt.ShuffleExchangeExec.writeTime")
    assert _inside(events, "srt.shuffle.assemble",
                   "srt.ShuffleExchangeExec.fetchAndMergeTime")
    assert _inside(events, "srt.shuffle.upload",
                   "srt.ShuffleExchangeExec.fetchAndMergeTime")
    if codec != "none":
        assert _inside(events, "srt.shuffle.compress",
                       "srt.shuffle.serialize")
        assert _inside(events, "srt.shuffle.decompress",
                       "srt.shuffle.decode")
    assert _inside(events, "srt.agg.whole_args", "srt.collect")
    assert _inside(events, "srt.agg.pad", "srt.collect")
    # sizes ride as stats, not in the name
    ser = next(e for e in events if e[1] == "srt.shuffle.serialize")
    assert ser[4]["rows"] > 0
    # the dict sink records none of them
    recorded = [e for e in read_event_log(s.last_event_log)
                if e["event"] == "trace_span"]
    assert recorded
    assert not [sp for sp in recorded
                if sp["name"].startswith(("shuffle.", "agg."))]


@pytest.mark.parametrize("kind", sorted(tracing.PROFILER_ONLY))
def test_profiler_only_kinds_never_reach_drain_trace(kind):
    tc = tracing.TraceContext("unit-only-" + kind, None, True)
    with tracing.use(tc):
        with tracing.span("x", kind, tc, op="FooExec@1"):
            # no dict record, so no new parent either
            assert tracing.current() is tc
        sp = tracing.open_span("y", kind, tc)
        sp.set("a", 1)
        sp.end()
        sp.end()                        # idempotent, like Span.end
        with tracing.span("z", "plan", tc):
            pass
    assert [s["name"] for s in tracing.drain_trace(tc.trace_id)] == ["z"]


def test_cached_program_is_named_by_call_site():
    import jax.numpy as jnp
    from spark_rapids_tpu.runtime.program_cache import cached_program

    def fn(x):                          # what builders are called today
        return x + 1

    prog = cached_program(fn, cls="FooExec", tag="bar", key=("unit",))
    jitted = prog._jit()
    assert jitted.__name__ == "FooExec_bar"
    assert "jit_FooExec_bar" in jitted.lower(jnp.ones(4)).as_text()
    assert prog._launch == "launch.FooExec.bar"
    np.testing.assert_array_equal(np.asarray(prog(jnp.ones(4))),
                                  np.full(4, 2.0, np.float32))


def test_metric_timer_is_a_span_only_for_an_operators_set(tmp_path):
    from spark_rapids_tpu.utils.metrics import MetricSet

    def timed():
        with MetricSet(op_id="FooExec@1f").timer("opTime"):
            pass
        with MetricSet().timer("opTime"):   # no operator: no span
            pass

    events = _profiled(tmp_path, timed)
    assert [(e[1], e[4]) for e in events] == [
        ("srt.FooExec.opTime", {"op": "FooExec@1f"})]
