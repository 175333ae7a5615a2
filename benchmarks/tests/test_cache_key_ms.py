"""metrics/cache_key_ms.py, the reader of the program's `srt.cache.key`
spans (PR 27): on a made-up run worked by hand, on a program that opens no
such span, and on the trace recorded on a TPU v5e (one execution of
tpch_sf1_parquet.q6, my chip run, PR 26), beside test_span_metrics.py."""
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest, tracereduce
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_span_metrics import RECORDED, _made_up


def _keyed():
    """test_span_metrics' two executions (0-100, 100-200 ns) with the keys of
    three launches: one begun before the window (cut to it), one on the
    query's thread and a worker's that overlaps it (counted once), one in
    the second execution."""
    run = _made_up()
    run["trace"]["host"] += [
        ("srt.cache.key", -3, 1), ("srt.cache.key", 24, 27),
        ("srt.cache.key", 26, 30), ("srt.cache.key", 131, 139)]
    return run


def test_key_time_is_the_union_cut_to_the_window_per_execution():
    # 0-1, 24-30, 131-139
    assert bench_run.read_metric("cache_key_ms", _keyed()) \
        == pytest.approx((1 + 6 + 8) / 1e6 / 2)


def test_a_program_without_spans_gives_nothing_and_does_not_raise():
    run = _keyed()
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if not ev[0].startswith("srt.")]
    assert bench_run.read_metric("cache_key_ms", run) is None
    # spans, and no launch of a cached program among them: no time keyed
    assert bench_run.read_metric("cache_key_ms", _made_up()) == 0.0


@pytest.mark.parametrize("gone", ["trace", "reduced"])
def test_no_trace_or_no_device_operation_gives_nothing(gone):
    assert bench_run.read_metric(
        "cache_key_ms", dict(_keyed(), **{gone: None})) is None


def test_reader_on_the_recorded_trace():
    trace = tracereduce.load(RECORDED)
    run = {"trace": trace, "reduced": tracereduce.reduce(trace)}
    # seven launches of cached programs in the one execution
    assert sum(n == "srt.cache.key" for n, _, _ in trace["host"]) == 7
    assert bench_run.read_metric("cache_key_ms", run) \
        == pytest.approx(0.76458)


def test_every_cell_that_reports_rows_per_s_reports_it():
    bench = manifest.load(ROOT)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "cache_key_ms"]
    assert entry == {"name": "cache_key_ms", "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "program cache / compile",
                     "moves": "rows_per_s"}
    assert bench["per_layer"][-1] is entry
    assert manifest.cells_of(bench, entry) == [
        w["name"] for w in bench["workloads"]]
