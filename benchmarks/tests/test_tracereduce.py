"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on a made-up trace worked by hand, and on a small trace recorded
on a TPU v5e (two executions of tpch_sf1_hbm.q6, my chip run, PR 25)."""
import os

import pytest

from benchmarks.harness import tracereduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "q6_hbm_2exec.xplane.pb")


def _made_up():
    """Two executions, 0-100 and 100-200 ns; ops 10-30, 20-40 (overlap),
    120-150 and one before the window, -20-5, cut to 0-5."""
    host = [("bench.execution", 0, 100), ("bench.execution", 100, 200),
            ("bench.build_tree", 0, 8), ("bench.to_arrow", 8, 100),
            ("bench.build_tree", 100, 110), ("bench.to_arrow", 110, 200),
            ("Execute", 40, 90), ("ReadSyncFlag", 60, 80),
            ("other thread", 150, 200)]
    ops = [("a", 10, 30), ("b", 20, 40), ("c", 120, 150), ("d", -20, 5)]
    modules = [("jit_x", 10, 40), ("jit_y", 120, 150), ("jit_z", -20, 5)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "host": host}


def test_busy_is_the_union_cut_to_the_window():
    r = tracereduce.reduce(_made_up())
    assert r["executions"] == 2
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((5 + 30 + 30) * 1e-9)
    assert r["each_execution"] == [
        [pytest.approx(100e-9), pytest.approx(35e-9)],
        [pytest.approx(100e-9), pytest.approx(30e-9)]]
    assert r["device_ops"][:2] == [["jit_x", pytest.approx(30e-9)],
                                   ["jit_y", pytest.approx(30e-9)]]
    assert ["jit_z", pytest.approx(5e-9)] in r["device_ops"]


def test_idle_gaps_are_labelled_by_the_innermost_host_event():
    gaps = dict(tracereduce.reduce(_made_up())["idle_gaps"])
    # idle 5-10 (middle 7: build_tree), 40-120 (middle 80: to_arrow, inside
    # Execute 40-90; ReadSyncFlag 60-80 has ended), 150-200 (middle 175:
    # to_arrow, with an event of another thread)
    assert gaps["bench.build_tree"] == pytest.approx(5e-9)
    assert gaps["bench.to_arrow / Execute"] == pytest.approx(80e-9)
    assert gaps["bench.to_arrow / other thread"] == pytest.approx(50e-9)
    assert sum(gaps.values()) == pytest.approx((200 - 65) * 1e-9)


def test_modules_stand_in_where_a_trace_has_no_op_line():
    t = _made_up()
    del t["devices"]["/device:TPU:0"]["XLA Ops"]
    assert tracereduce.reduce(t)["busy_s"] == pytest.approx(65e-9)


@pytest.mark.parametrize("drop", ["devices", "executions", "ops in window"])
def test_nothing_to_read_gives_nothing(drop):
    t = _made_up()
    if drop == "devices":
        t["devices"] = {}
    elif drop == "executions":
        t["host"] = [e for e in t["host"] if e[0] != "bench.execution"]
    else:
        t["devices"]["/device:TPU:0"] = {"XLA Ops": [("a", 300, 400)]}
    assert tracereduce.reduce(t) is None


def test_recorded_v5e_trace():
    assert os.path.getsize(RECORDED) < 1 << 20
    t = tracereduce.load(RECORDED)
    assert list(t["devices"]) == ["/device:TPU:0"]
    r = tracereduce.reduce(t)
    # the numbers the run itself printed on the chip for this trace
    assert r["executions"] == 2
    assert r["busy_s"] == pytest.approx(0.000767917, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.01826829, rel=1e-9)
    assert r["device_ops"][0][0].startswith("jit_run(")
    assert r["device_ops"][0][1] == pytest.approx(0.000773588, rel=1e-9)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    assert r["idle_gaps"][0][0] == "bench.to_arrow"
    assert 0 < r["busy_s"] < r["window_s"]


def test_find_xplane(tmp_path):
    assert tracereduce.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "2026_09_30"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert tracereduce.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
