"""The readers of the program's spans (harness/spans.py and the seven
metrics/<name>.py that call it): on a made-up run worked by hand, and on a
trace recorded on a TPU v5e from the tree that first opened the spans (one
execution of tpch_sf1_parquet.q6, my chip run, PR 26)."""
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import tracereduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "pq6_1exec_spans.xplane.pb")
SPAN_METRICS = ("plan_ms", "session_self_ms", "host_syncs_per_query",
                "host_sync_ms", "io_host_ms", "idle_unattributed_pct")


def _made_up():
    """Two executions, 0-100 and 100-200 ns. The query's thread, a worker
    whose fetch (80-92) overlaps the query thread's (72-86), a fetch and a
    program that began before the window (cut to it, not counted)."""
    host = [
        ("bench.execution", 0, 100), ("bench.execution", 100, 200),
        ("bench.to_arrow", 2, 100), ("Execute", 30, 38),
        # the query's thread, first execution
        ("srt.admit", 0, 4), ("srt.query", 4, 96), ("srt.plan", 6, 16),
        ("srt.prewarm", 16, 18), ("srt.collect", 20, 90),
        ("srt.export", 70, 88), ("srt.fetch", 72, 86), ("srt.admit", 96, 100),
        ("srt.fetch", -10, 2),
        # a worker thread
        ("srt.FusedStageExec.opTime", 24, 60),
        ("srt.launch.FusedStageExec.run", 26, 30), ("srt.fetch", 80, 92),
        ("srt.io.read", 21, 23), ("srt.io.upload", 22, 25),
        ("srt.io.decode", 22, 40),
        # second execution
        ("srt.query", 104, 196), ("srt.plan", 106, 126),
        ("srt.collect", 130, 190), ("srt.fetch", 150, 160)]
    ops = [("fusion", -5, 1), ("fusion", 28, 40), ("copy", 50, 58),
           ("fusion", 140, 170)]
    modules = [("jit_w", -5, 1), ("jit_FusedStageExec_run", 28, 40),
               ("jit_x", 50, 58), ("jit_y", 140, 170)]
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                           "XLA Modules": modules}},
             "host": host}
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def _per_execution_ms(ns):
    return pytest.approx(ns / 1e6 / 2)


def test_times_are_unions_cut_to_the_window_per_execution():
    run = _made_up()
    assert run["reduced"]["executions"] == 2
    assert bench_run.read_metric("plan_ms", run) == _per_execution_ms(10 + 20)
    # 0-2 (cut), 72-92 (two threads overlap: once), 150-160
    assert bench_run.read_metric("host_sync_ms", run) \
        == _per_execution_ms(2 + 20 + 10)
    # read 21-23 and upload 22-25; decode is the device's side
    assert bench_run.read_metric("io_host_ms", run) == _per_execution_ms(4)


def test_counts_take_what_starts_inside_the_window():
    run = _made_up()
    assert bench_run.read_metric("host_syncs_per_query", run) == 3 / 2
    assert bench_run.read_metric("launches_per_query", run) == 3 / 2


def test_session_self_time_is_less_every_other_span_on_any_thread():
    # query and admit cover 0-100 and 104-196 = 192; the other spans cover
    # 0-2, 6-18, 20-92 (the worker's fetch runs past the collect), 106-126
    # and 130-190 = 166 of it
    assert bench_run.read_metric("session_self_ms", _made_up()) \
        == _per_execution_ms(192 - 166)


def test_idle_time_no_finer_span_covers_is_unattributed():
    # busy 0-1, 28-40, 50-58, 140-170; gaps 1-28 (middle 14, in plan),
    # 40-50 (45, in opTime), 58-140 (99: only query/admit) and 170-200
    # (185: only query and collect)
    assert bench_run.read_metric("idle_unattributed_pct", _made_up()) \
        == pytest.approx(100.0 * (82 + 30) / (27 + 10 + 82 + 30))


def test_a_program_without_spans_gives_nothing_and_does_not_raise():
    run = _made_up()
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if not ev[0].startswith("srt.")]
    assert [bench_run.read_metric(m, run) for m in SPAN_METRICS] \
        == [None] * len(SPAN_METRICS)
    # the device's own line needs no span of the program
    assert bench_run.read_metric("launches_per_query", run) == 3 / 2


@pytest.mark.parametrize("gone", ["trace", "reduced"])
def test_no_trace_or_no_device_operation_gives_nothing(gone):
    run = dict(_made_up(), **{gone: None})
    assert [bench_run.read_metric(m, run)
            for m in SPAN_METRICS + ("launches_per_query",)] == [None] * 7


@pytest.fixture(scope="module")
def recorded():
    trace = tracereduce.load(RECORDED)
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def test_recorded_trace_carries_the_programs_spans(recorded):
    names = {n for n, _, _ in recorded["trace"]["host"]
             if n.startswith("srt.")}
    assert {"srt.query", "srt.admit", "srt.plan", "srt.prewarm",
            "srt.collect", "srt.export", "srt.fetch", "srt.cache.key",
            "srt.ParquetScanExec.scanTime", "srt.io.read", "srt.io.upload",
            "srt.io.decode", "srt.launch.UngroupedAggExec.update_merge",
            } <= names
    r = recorded["reduced"]
    assert r["executions"] == 1
    # the cached program is named by its call site, the idle gaps by spans
    assert any(n.startswith("jit_UngroupedAggExec_update_merge(")
               for n, _, _ in
               recorded["trace"]["devices"]["/device:TPU:0"]["XLA Modules"])
    assert r["idle_gaps"][0][0] == "bench.to_arrow / srt.io.read"
    assert "bench.to_arrow" not in dict(r["idle_gaps"])


@pytest.mark.parametrize("metric,value", [
    ("plan_ms", 3.35018), ("session_self_ms", 1.154391),
    ("launches_per_query", 261.0), ("host_syncs_per_query", 1.0),
    ("host_sync_ms", 0.67338), ("io_host_ms", 71.749688),
    ("idle_unattributed_pct", 6.317322207767124)])
def test_readers_on_the_recorded_trace(recorded, metric, value):
    assert bench_run.read_metric(metric, recorded) == pytest.approx(value)
