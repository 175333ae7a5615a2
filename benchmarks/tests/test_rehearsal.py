"""A whole run off the chip at a tiny scale: the harness's look for a chip is
skipped (run_cell, not main) and the rest is driven as on the chip. Sound
runs come out correct; with the timed path broken underneath, not."""
import json
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest
from benchmarks.harness.engine import System
from benchmarks.tests.conftest import ROOT

SF = 0.01


def _run(cell, factory=None, trace=0):
    return bench_run.run_cell(manifest.cell(ROOT, cell), 2**31 + 17, 0.3,
                              trace, sf=SF, system_factory=factory)


class Broken(System):
    """Sound through set-up, broken once the window is open."""
    calls = 0

    def in_window(self):
        self.calls += 1
        return self.calls > 1 + self.traffic["warm_executions"]


class AlteredAnswer(Broken):
    """An answer altered where it is produced: the first row dropped."""
    def execute(self, which=0):
        out = super().execute(which)
        return out.slice(1) if self.in_window() else out


class HalfTheRows(System):
    """Half of the rows left out of what the engine is given."""
    def load(self, tables):
        super().load({k: v.slice(0, v.num_rows // 2)
                      for k, v in tables.items()})


class Raises(Broken):
    """Every other execution of the window never gives its answer."""
    def execute(self, which=0):
        if self.in_window() and self.calls % 2:
            raise RuntimeError("refused")
        return super().execute(which)


class DegradesOnce(Broken):
    """One early execution of the window degrades to the host; the last
    ones do not."""
    def execute(self, which=0):
        out = super().execute(which)
        first = 2 + self.traffic["warm_executions"]
        if self.in_window() and self.calls == first:
            self.hidden["degraded_to_host"] += 1   # the window's first
        return out


@pytest.mark.parametrize("cell,metrics", [
    ("tpch_sf10_hbm.q6", {"rows_per_s", "query_p95_ms", "setup_s"}),
    ("tpch_sf1_hbm.q3", {"rows_per_s", "setup_s"}),
    ("tpch_sf1_parquet.q6", {"rows_per_s", "setup_s"})])
def test_sound_run_is_correct(cell, metrics):
    r = _run(cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == metrics
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "compared"
    assert all(c == {"value": 0, "limit": 0} for c in r["compared"].values())


def test_traced_run_reports_per_layer_metrics_and_stays_silent_off_chip():
    r = _run("tpch_sf10_hbm.q6", trace=1)
    assert r["correct"] is True
    assert {"first_answer_s", "first_answer_compiles",
            "window_compiles", "dispatches_per_query"} == set(r["metrics"])
    # no device plane in a CPU trace: the roofline is left out, never 0
    assert not any(m.startswith("query_roofline") for m in r["metrics"])
    assert not any(m.startswith("device_idle_pct") for m in r["metrics"])


@pytest.mark.parametrize("factory,number", [
    (AlteredAnswer, "wrong_answers"), (HalfTheRows, "wrong_answers"),
    (Raises, "missing_answers"), (DegradesOnce, "degraded_to_host")])
def test_broken_timed_path_is_not_correct(factory, number):
    r = _run("tpch_sf10_hbm.q6", factory)
    assert r["correct"] is False
    assert r["compared"][number]["value"] > 0
    assert r["failed"] > 0 or number == "degraded_to_host"


def test_a_mix_of_two_queries_runs_by_its_data_file_alone():
    """A traffic mix of q6 and q3 in turn (what `power22` would be): each
    answer is held to its own query's reference, rows are counted by query."""
    cell = manifest.cell(ROOT, "tpch_sf1_hbm.q3")
    q6 = manifest.cell(ROOT, "tpch_sf10_hbm.q6")["traffic"]["queries"]
    cell["traffic"] = dict(cell["traffic"],
                           queries=q6 + cell["traffic"]["queries"])
    r = bench_run.run_cell(cell, 2**31 + 19, 0.3, 0, sf=SF)
    assert r["correct"] is True and r["attempted"] >= 2
    assert r["metrics"]["rows_per_s"]["value"] > 0


def test_command_refuses_to_measure_off_the_chip_and_rehearses():
    cmd = [sys.executable, "benchmarks/run.py", "--workload",
           "tpch_sf10_hbm.q6", "--seed", "3", "--seconds", "0.2",
           "--trace", "0"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
    no_chip = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                             text=True, timeout=300)
    assert no_chip.returncode != 0 and no_chip.stdout.strip() == ""
    rehearsal = subprocess.run(cmd + ["--rehearse", "--sf", str(SF)], cwd=ROOT,
                               env=env, capture_output=True, text=True,
                               timeout=300)
    assert rehearsal.returncode == 0
    last = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["rehearsal"]["correct_off_the_chip"] is True
    assert rehearsal.stderr.strip().splitlines()[-1].startswith("compared ")
