"""BENCHMARK.json against the contract's rules that an added entry can
break, and the files it names."""
import json
import os
import re

import pytest

from benchmarks.harness import manifest
from benchmarks.tests.conftest import ROOT

BENCH = manifest.load(ROOT)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    names = CELLS + [c["name"] for c in BENCH["configs"]] + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names) and all(NAME.match(n) for n in names)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"layer", "moves"} if "moves" in metric else {"bound"}
    assert set(metric) <= allowed
    assert set(metric.get("workloads", [])) <= set(CELLS)
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "metrics", metric["name"] + ".py"))
    if "moves" in metric:   # every cell that reads it reports what it moves
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(manifest.cells_of(BENCH, metric)) <= set(
            manifest.cells_of(BENCH, moved))
        assert len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    else:
        assert 0 < metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(conf):
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    assert config["name"] == conf["name"]
    assert config["source"] == conf["source"] and len(conf["source"]) <= 200
    assert config["reduced"] == conf["reduced"]
    assert ("scale_factor" in conf["reduced"]) == (
        config["scale_factor"] != config["source_scale_factor"])
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "storage", config["storage"]["kind"] + ".py"))
    for key in ("spark.rapids.tpu.sql.exec.degradeToHost.enabled",
                "spark.rapids.tpu.sql.allowCpuFallback",
                "spark.rapids.tpu.sql.cache.enabled"):
        assert config["conf"][key] == "false"
    assert len(config["conf"]) == 3   # every other conf at its default


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_reads_its_files(cell):
    got = manifest.cell(ROOT, cell["name"])
    assert got["traffic"]["clients"] == 1
    assert got["traffic"]["loop"] == "closed"
    assert got["traffic"]["fresh_tree"] is True
    for query in got["traffic"]["queries"]:
        assert set(query["reads"]) <= set(got["config"]["schema"])
        for table in query["reads"]:   # a generator and a reference by name
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", "datagen", table + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "reference", query["reference"] + ".py"))
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    assert len(manifest.metrics_of(BENCH, cell["name"], "end_to_end")) >= 2
    assert manifest.metrics_of(BENCH, cell["name"], "per_layer")
