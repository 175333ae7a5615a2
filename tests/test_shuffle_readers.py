"""The four readers of the one-chip exchange's host phases
(benchmarks/metrics/shuffle_*.py, benchmarks/harness/shuffle.py) on a
recorded trace made by hand: the union of the phases over threads per
execution, the codec alone, the device's idle share whose gap's middle lies
in a shuffle span, and silence where the trace holds no shuffle span."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import tracereduce  # noqa: E402

READERS = ("shuffle_write_ms", "shuffle_read_ms", "shuffle_codec_ms",
           "shuffle_idle_pct")
# two executions, 0-100 and 100-200 ns; the device busy 10-30 and 120-130,
# so idle 0-10 (middle 5), 30-120 (middle 75) and 130-200 (middle 165)
MAP_SIDE = [("srt.shuffle.slice", 40, 50), ("srt.shuffle.serialize", 45, 60),
            ("srt.shuffle.compress", 50, 55), ("srt.shuffle.write", 60, 80)]
REDUCE_SIDE = [("srt.shuffle.read", 140, 150),
               ("srt.shuffle.decode", 150, 170),
               ("srt.shuffle.decompress", 155, 160),
               ("srt.shuffle.assemble", 170, 175),
               ("srt.shuffle.upload", 175, 180)]


def _run(host):
    trace = {"devices": {"/device:TPU:0": {
        "XLA Ops": [("fusion.1", 10, 30), ("fusion.2", 120, 130)],
        "XLA Modules": [("jit_a(1)", 10, 30), ("jit_b(2)", 120, 130)]}},
        "host": [("bench.execution", 0, 100), ("bench.execution", 100, 200),
                 ("srt.query", 0, 200)] + host}
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def test_readers_take_the_union_of_the_phases_per_execution():
    run = _run(MAP_SIDE + REDUCE_SIDE)
    ns = 1e-6                                  # one nanosecond in ms
    assert bench_run.read_metric("shuffle_write_ms", run) \
        == pytest.approx(40 * ns / 2)          # 40-80
    assert bench_run.read_metric("shuffle_read_ms", run) \
        == pytest.approx(40 * ns / 2)          # 140-180
    assert bench_run.read_metric("shuffle_codec_ms", run) \
        == pytest.approx(10 * ns / 2)
    # the gaps at 75 and 165 lie in shuffle spans, the one at 5 does not
    assert bench_run.read_metric("shuffle_idle_pct", run) \
        == pytest.approx(100 * 160 / 170)


def test_uncompressed_blocks_read_no_codec_time():
    plain = [ev for ev in MAP_SIDE + REDUCE_SIDE
             if ev[0] not in ("srt.shuffle.compress", "srt.shuffle.decompress")]
    run = _run(plain)
    assert bench_run.read_metric("shuffle_codec_ms", run) == 0
    assert bench_run.read_metric("shuffle_write_ms", run) > 0


@pytest.mark.parametrize("reader", READERS)
def test_readers_are_silent_without_a_shuffle_span(reader):
    # a cell whose plan crosses no one-chip exchange, a trace with no
    # program span at all, and a run that was not traced
    assert bench_run.read_metric(reader, _run([("srt.plan", 1, 5)])) is None
    assert bench_run.read_metric(reader, _run([])) is None
    run = _run(MAP_SIDE)
    assert bench_run.read_metric(reader, dict(run, trace=None)) is None
    assert bench_run.read_metric(reader, dict(run, reduced=None)) is None
