"""metrics/io_host_decode_ms.py, decode_device_ms.py and decode_roofline.py,
the readers of the device Parquet decode (harness/decode.py): on a made-up
trace worked by hand, on runs that hold nothing to read, and on a trace
recorded on a TPU v5e (one execution of tpch_sf1_parquet.q6), whose decode
programs predate the `pq_` name and whose scan opened no host-decode
span."""
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest, tracereduce
from benchmarks.tests.conftest import ROOT
from benchmarks.tests.test_span_metrics import RECORDED

HBM = 819e9


def _made_up(host_decode=True, io=True):
    """Two executions, 0-100 and 100-200 ns, two chips. Chip 0: decode
    programs 10-30 and 25-40 (overlap: 10-40 once), 120-130, and one begun
    before the window (-6 to 4: cut to 0-4); chip 1: 10-20. Programs of
    other names are no one's. Host: a read 5-9, host decodes 50-60 on two
    threads over 55-70 (50-70 once)."""
    chip0 = [("jit_pq_fixed(1)", 10, 30), ("jit_pq_unpack(2)", 25, 40),
             ("jit_pq_fixed(1)", 120, 130), ("jit_pq_zeros(3)", -6, 4),
             ("jit_HashJoinExec_probe(4)", 40, 90), ("jit_pqx(5)", 60, 70)]
    chip1 = [("jit_pq_strings(6)", 10, 20)]
    ops = [("fusion.1", 10, 40)]
    host = [("bench.execution", 0, 100), ("bench.execution", 100, 200)]
    if io:
        host.append(("srt.io.read", 5, 9))
    if host_decode:
        host += [("srt.io.host_decode", 50, 60),
                 ("srt.io.host_decode", 55, 70)]
    trace = {"devices": {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": chip0},
        "/device:TPU:1": {"XLA Ops": ops, "XLA Modules": chip1}},
        "host": host}
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def test_decode_device_time_is_a_union_a_plane_averaged_per_execution():
    run = _made_up()
    # chip 0: 4 + 30 + 10 = 44; chip 1: 10; mean 27, two executions
    assert bench_run.read_metric("decode_device_ms", run) \
        == pytest.approx(27 / 1e6 / 2)


def test_host_decode_is_the_union_of_its_spans():
    assert bench_run.read_metric("io_host_decode_ms", _made_up()) \
        == pytest.approx(20 / 1e6 / 2)
    # the scan read a file and decoded nothing on the host: 0.0, not None
    assert bench_run.read_metric(
        "io_host_decode_ms", _made_up(host_decode=False)) == 0.0
    # no `srt.io.` span at all (tables cached in HBM): nothing to read
    assert bench_run.read_metric(
        "io_host_decode_ms", _made_up(host_decode=False, io=False)) is None


@pytest.mark.parametrize("metric", ["io_host_decode_ms", "decode_device_ms",
                                    "decode_roofline"])
def test_silent_where_there_is_no_trace(metric):
    for gone in ("trace", "reduced"):
        run = dict(_made_up(), **{gone: None}, peaks={"hbm_bytes_per_s": HBM})
        assert bench_run.read_metric(metric, run) is None


def _cell_run(cell, peaks=True):
    c = manifest.cell(ROOT, cell)
    run = dict(_made_up(), config=c["config"],
               cardinality=manifest.cardinality(c["config"], 1.0),
               peaks={"hbm_bytes_per_s": HBM} if peaks else None)
    return run


@pytest.mark.parametrize("cell,row_bytes", [
    # l_shipdate 4, l_discount 4 (decimal(4,2)), l_quantity 8,
    # l_extendedprice 8: over lineitem's rows
    ("tpch_sf1_parquet.q6", {"lineitem": 24}),
    # c_custkey 4, c_mktsegment 16 (char(10)); o_orderkey 4, o_custkey 4,
    # o_orderdate 4, o_shippriority 4; l_orderkey 4, l_shipdate 4,
    # l_extendedprice 8, l_discount 4
    ("tpch_sf1_parquet3.q3", {"customer": 20, "orders": 16,
                              "lineitem": 20})])
def test_roofline_reads_the_declared_widths_of_the_columns_read(
        monkeypatch, cell, row_bytes):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell,
                                      "--seed", "7"])
    run = _cell_run(cell)
    rows = run["cardinality"]
    least = sum(w * rows[t] for t, w in row_bytes.items())
    ms = 27 / 1e6 / 2
    assert bench_run.read_metric("decode_roofline", run) \
        == pytest.approx(100.0 * least / HBM / (ms / 1e3))


def test_roofline_silent_off_the_chip_and_outside_a_run(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "tpch_sf1_parquet.q6"])
    assert bench_run.read_metric(
        "decode_roofline", _cell_run("tpch_sf1_parquet.q6", peaks=False)) \
        is None
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert bench_run.read_metric(
        "decode_roofline", _cell_run("tpch_sf1_parquet.q6")) is None


def test_readers_on_the_recorded_trace():
    trace = tracereduce.load(RECORDED)
    run = {"trace": trace, "reduced": tracereduce.reduce(trace),
           "peaks": {"hbm_bytes_per_s": HBM}}
    # programs from before the name: `jit_expand_hybrid` and friends
    assert bench_run.read_metric("decode_device_ms", run) is None
    assert bench_run.read_metric("decode_roofline", run) is None
    # its scan opened `srt.io.` spans, and no host-decode span yet
    assert bench_run.read_metric("io_host_decode_ms", run) == 0.0
