"""What the cell tpch_sf1_mesh4.q3 brought (PR 29): the six readers of a mesh
run (metrics/collective_ms, mesh_stage_ms, collective_device_ms,
spmd_degrades_per_query, chip_busy_skew_pct, collective_roofline) on a
made-up trace with two device planes worked by hand, harness/meshbytes.py
against a hand count, and storage/hbm_cache_mesh.py raising where one device
holds everything."""
import json
import os
from fractions import Fraction

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest, meshbytes, tracereduce
from benchmarks.storage import hbm_cache_mesh
from benchmarks.tests.conftest import ROOT

CELL = "tpch_sf1_mesh4.q3"
MESH_METRICS = ("collective_ms", "mesh_stage_ms", "collective_device_ms",
                "spmd_degrades_per_query", "chip_busy_skew_pct",
                "collective_roofline")


def _made_up():
    """Two executions, 0-100 and 100-200 ns, on two chips. Chip 0 is busy
    10-50 and 110-140 (70), 10-20 and 110-116 of it in all-to-all (16); chip
    1 is busy 12-40 and 110-130 (48), 12-30 and 110-120 of it in all-to-all
    (28). One all-to-all began before the window (cut to it)."""
    host = [
        ("bench.execution", 0, 100), ("bench.execution", 100, 200),
        ("srt.query", 2, 98), ("srt.collect", 4, 96),
        # staging 6-30 with its timer inside, the collective 30-60 with the
        # exchange timer inside; a pool thread's map task overlaps staging
        ("srt.spmd.stage", 6, 30), ("srt.SpmdStageExec.partitionTime", 6, 30),
        ("srt.spmd.map", 8, 20),
        ("srt.spmd.collective", 30, 60),
        ("srt.SpmdStageExec.exchangeTime", 34, 64),
        ("srt.launch.SpmdStageExec.exchange", 35, 37),
        ("srt.mesh.gather", 80, 84),
        # second execution: two stages, the second degraded
        ("srt.query", 102, 198), ("srt.spmd.stage", 104, 110),
        ("srt.spmd.collective", 110, 130), ("srt.spmd.collective", 125, 150),
        ("srt.spmd.degrade", 150, 152),
        # a collective that began before the window
        ("srt.spmd.collective", -20, 0)]
    chip0 = [("all-to-all.3", -4, -1), ("all-to-all.3", 10, 20),
             ("fusion.1", 20, 50), ("all-to-all-start.9", 110, 116),
             ("sort.2", 116, 140)]
    chip1 = [("all-to-all.3", 12, 30), ("fusion.1", 30, 40),
             ("all-to-all-start.9", 110, 120), ("sort.2", 120, 130)]
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": chip0},
                         "/device:TPU:1": {"XLA Ops": chip1}},
             "host": host}
    return {"trace": trace, "reduced": tracereduce.reduce(trace)}


def _per_execution_ms(ns):
    return pytest.approx(ns / 1e6 / 2)


def test_host_side_of_the_stages_is_a_union_per_execution():
    run = _made_up()
    assert run["reduced"]["executions"] == 2
    # 30-60, 110-150 (two overlap: once); the one before the window is cut
    assert bench_run.read_metric("collective_ms", run) \
        == _per_execution_ms(30 + 40)
    # srt.spmd.stage and srt.SpmdStageExec.*: 6-30, 34-64, 104-110; the pool
    # task and the launch are other layers' spans
    assert bench_run.read_metric("mesh_stage_ms", run) \
        == _per_execution_ms(24 + 30 + 6)
    assert bench_run.read_metric("spmd_degrades_per_query", run) == 1 / 2


def test_device_side_is_read_a_plane_and_averaged():
    run = _made_up()
    # all-to-all: chip 0 has 10 + 6, chip 1 has 18 + 10
    assert bench_run.read_metric("collective_device_ms", run) \
        == _per_execution_ms((16 + 28) / 2)
    # busy 70 and 48, mean 59
    assert bench_run.read_metric("chip_busy_skew_pct", run) \
        == pytest.approx(100.0 * (70 - 48) / 59)


def test_roofline_is_the_least_bytes_over_the_peak_over_the_time():
    run = _made_up()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch_sf1_mesh4.json")) as f:
        config = json.load(f)
    # two planes in the trace: the least bytes of a two-chip mesh
    least = meshbytes.q3_least_bytes_per_chip(
        config["schema"], manifest.cardinality(config, 1.0), 2)
    seconds = (16 + 28) / 2 / 1e9 / 2
    assert bench_run.read_metric("collective_roofline", run) \
        == pytest.approx(100.0 * (least / 200e9) / seconds)


def test_one_chip_or_no_collective_reads_nothing():
    run = _made_up()
    del run["trace"]["devices"]["/device:TPU:1"]
    assert bench_run.read_metric("chip_busy_skew_pct", run) is None
    assert bench_run.read_metric("collective_roofline", run) is None
    run = _made_up()
    for lines in run["trace"]["devices"].values():
        lines["XLA Ops"] = [ev for ev in lines["XLA Ops"]
                            if "all-to-all" not in ev[0]]
    assert bench_run.read_metric("collective_device_ms", run) is None
    assert bench_run.read_metric("collective_roofline", run) is None
    assert bench_run.read_metric("chip_busy_skew_pct", run) is not None


@pytest.mark.parametrize("name", MESH_METRICS)
def test_a_program_without_the_spans_or_a_run_without_a_trace(name):
    """The parent opens no such span and runs no mesh: nothing, no raise."""
    run = _made_up()
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if not ev[0].startswith("srt.")]
    if name in ("collective_ms", "mesh_stage_ms", "spmd_degrades_per_query"):
        assert bench_run.read_metric(name, run) is None
    else:   # the device's lines need no span of the program
        assert bench_run.read_metric(name, run) is not None
    for gone in ("trace", "reduced"):
        assert bench_run.read_metric(
            name, dict(_made_up(), **{gone: None})) is None


def test_spans_present_and_no_stage_reads_zero():
    run = _made_up()
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if "spmd" not in ev[0].lower()]
    assert bench_run.read_metric("collective_ms", run) == 0.0
    assert bench_run.read_metric("mesh_stage_ms", run) == 0.0
    assert bench_run.read_metric("spmd_degrades_per_query", run) == 0.0


@pytest.mark.parametrize("name", MESH_METRICS)
def test_the_readers_are_listed_for_the_mesh_cell_only(name):
    bench = manifest.load(ROOT)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "rows_per_s"
    assert name in [m["name"] for m in
                    manifest.metrics_of(bench, CELL, "per_layer")]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "q3"


def test_least_bytes_against_a_hand_count():
    """Q3 at SF1 on four chips. Order dates are uniform over the 2,406 days
    from 1992-01-01 to 1998-08-02 and 1,169 of them lie before 1995-03-15; a
    line ships 1..121 days after its order, so it ships after the date with
    probability (121 x (2,406 - 1,170) + (1 + ... + 121)) / (121 x 2,406)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch_sf1_mesh4.json")) as f:
        config = json.load(f)
    card = manifest.cardinality(config, 1.0)
    sel = meshbytes.q3_selectivities()
    assert sel["customer"] == sel["orders_of_segment"] == pytest.approx(1 / 5)
    assert sel["orders"] == pytest.approx(1169 / 2406)
    assert sel["lineitem"] == pytest.approx(
        (121 * 1236 + 121 * 122 // 2) / (121 * 2406))
    orders = Fraction(1_500_000 * 1169, 2406)
    lines = Fraction(6_001_215 * 156_937, 291_126)
    by_hand = (30_000 * 4                   # c_custkey
               + orders * (4 + 4 + 4 + 4)   # custkey, orderkey, date, prio
               + orders / 5 * (4 + 4 + 4)   # orderkey, date, prio
               + lines * (4 + 8 + 4))       # orderkey, price, discount
    got = meshbytes.q3_least_bytes_per_chip(config["schema"], card, 4)
    assert got == pytest.approx(float(by_hand) / 4 * 3 / 4)
    assert 12.2e6 < got < 12.3e6
    # one chip exchanges nothing; two send half of their half
    assert meshbytes.q3_least_bytes_per_chip(config["schema"], card, 1) == 0
    assert meshbytes.q3_least_bytes_per_chip(config["schema"], card, 2) \
        == pytest.approx(float(by_hand) / 2 / 2)


class _Frame:
    def __init__(self, devices):
        self._devices = devices

    def cache(self):
        return self

    def cached_devices(self):
        return list(self._devices)


class _NoAccessor:
    def cache(self):
        return self


class _Session:
    def __init__(self, make):
        self.make = make

    def create_dataframe(self, table):
        return self.make(table)


def test_storage_takes_tables_that_every_chip_holds():
    store = hbm_cache_mesh.Storage(
        _Session(lambda t: _Frame(["d0", "d1", "d2", "d3"])),
        {"kind": "hbm_cache_mesh", "chips": 4}, None)
    store.load({"orders": object(), "customer": object()})
    assert sorted(store.scans()) == ["customer", "orders"]
    store.close()
    assert store.scans() == {}


def test_storage_raises_when_one_device_holds_everything():
    store = hbm_cache_mesh.Storage(
        _Session(lambda t: _Frame(["d0"])),
        {"kind": "hbm_cache_mesh", "chips": 4}, None)
    with pytest.raises(RuntimeError, match=r"rows on 1 device\(s\)"):
        store.load({"orders": object()})
    # one table on three of four chips is as wrong
    shares = {"a": ["d0", "d1", "d2", "d3"], "b": ["d0", "d1", "d2"]}
    store = hbm_cache_mesh.Storage(
        _Session(lambda t: _Frame(shares[t])),
        {"kind": "hbm_cache_mesh", "chips": 4}, None)
    with pytest.raises(RuntimeError, match="b: rows on 3 device"):
        store.load({"a": "a", "b": "b"})


def test_storage_raises_on_a_program_that_cannot_say_where_rows_are():
    """The parent commit: no cached_devices() on a DataFrame."""
    store = hbm_cache_mesh.Storage(
        _Session(lambda t: _NoAccessor()),
        {"kind": "hbm_cache_mesh", "chips": 4}, None)
    with pytest.raises(RuntimeError, match="no cached_devices"):
        store.load({"orders": object()})
