"""Correctness soaks over concurrent TPC-H streams (slow-marked):

    JAX_PLATFORMS=cpu python -m pytest -m slow tests/test_soak.py -q

Wider than their tier-1 miniatures
(test_fault_recovery.py::test_chaos_smoke_q3_q6_distributed,
test_racedep.py::test_perturbed_queries_byte_identical): every point of
`faults.POINTS` is armed at once, over q1, q3, q6, q12 and q14 from
HBM-cached tables, with several streams interleaving. Each soak is a
function of its `seed` parameter alone: the same seed derives the same
fault plan, the same stream orders and the same yield decisions. The
lockdep, ledger and racedep witnesses are the suite's (conftest.py).
"""
import random
import threading

import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu.runtime import faults, ledger, lockdep, racedep
from spark_rapids_tpu.workloads import tpch

pytestmark = pytest.mark.slow

SF = 0.2
STREAMS = 4
QIDS = (1, 3, 6, 12, 14)
MAX_RETRIES = 8


@pytest.fixture(scope="module")
def soak():
    """(query registry, cached frames, fault-free serial answers). The
    serial pass also warms the program cache, so the soaks exercise
    recovery and interleaving rather than compiles."""
    s = st.TpuSession({
        # the result cache would serve the reference bytes back and
        # mask every fault point downstream of it
        "spark.rapids.tpu.sql.resultCache.enabled": "false",
        "spark.rapids.tpu.sql.service.maxQueryRetries": str(MAX_RETRIES),
    })
    dfs = {k: s.create_dataframe(v).cache()
           for k, v in tpch.gen_all(sf=SF, seed=7).items()}
    reg = tpch.queries()
    faults.clear_plan()
    serial = {qn: reg[qn](dfs).to_arrow() for qn in QIDS}
    yield reg, dfs, serial
    for df in dfs.values():
        df.uncache()


def _run_streams(soak, seed, qids):
    """STREAMS threads, each a seeded shuffle of `qids` through the sync
    path (so service-level retry, degradation and OOM retry are live).
    Returns (queries whose bytes differ from the serial answer, errors)."""
    reg, dfs, serial = soak
    mismatched, errors = [], []

    def stream(i):
        order = list(qids)
        random.Random(seed * 1000 + i).shuffle(order)
        for qn in order:
            try:
                if not reg[qn](dfs).to_arrow().equals(serial[qn]):
                    mismatched.append(qn)
            except Exception as e:  # noqa: BLE001 — asserted empty below
                errors.append(f"stream{i} q{qn}: {e!r}")

    threads = [threading.Thread(target=stream, args=(i,),
                                name=f"soak-stream-{i}")
               for i in range(STREAMS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(set(mismatched)), errors


def _ledger_state():
    """(findings so far, strict-kind handles outstanding now)."""
    lg = ledger.ledger()
    assert lg is not None, "conftest.py arms the resource ledger"
    rep = lg.report()
    return rep["findings"], sum(v["outstanding"]
                                for k, v in rep["kinds"].items()
                                if k in ledger.STRICT_KINDS)


def _fault_plan(seed):
    """Every named point armed with a seeded low-probability transient
    raise. Kill and delay actions are left out: a kill would take the
    test process with it, and a delay exercises no recovery path."""
    rng = random.Random(seed)
    raises = ["FetchFailed", "RESOURCE_EXHAUSTED", "ChaosError"]
    return ";".join(
        f"{point}:prob={round(rng.uniform(0.05, 0.12), 3)}"
        f":seed={rng.randrange(1 << 16)}:raise={rng.choice(raises)}"
        for point in sorted(faults.POINTS))


@pytest.mark.parametrize("seed", [7, 42])
def test_chaos_soak(soak, seed):
    """Under a seeded fault plan every answer is byte-identical to the
    fault-free serial run, the resource ledger balances, lockdep finds
    nothing and the transparent retries stay inside their budget."""
    lw = lockdep.witness()
    assert lw is not None, "conftest.py arms lockdep"
    led_before, lock_before = _ledger_state()[0], len(lw.findings)
    faults.reset_recovery_stats()
    faults.install_plan(_fault_plan(seed))
    try:
        mismatched, errors = _run_streams(soak, seed, QIDS)
    finally:
        # clear_plan() wipes the injection counters with the rules
        counts = faults.injection_counts()
        faults.clear_plan()
    assert errors == []
    assert mismatched == []
    assert counts.get("injected", 0) >= 1, "the plan never fired"
    retries = faults.recovery_stats().get("query_retries", 0)
    assert retries <= len(QIDS) * STREAMS * MAX_RETRIES
    assert _ledger_state() == (led_before, 0)
    assert len(lw.findings) == lock_before, lw.findings[lock_before:]


@pytest.mark.parametrize("seed", [7, 42])
def test_schedule_perturbation(soak, seed):
    """No fault armed: a microsecond bytecode switch interval and seeded
    yields at the instrumented shared-structure accesses. Byte-identical
    answers, no lockset collapse witnessed and a balanced ledger show
    the pools' sharing discipline rather than retry luck."""
    rw = racedep.witness()
    assert rw is not None, "conftest.py arms racedep"
    led_before, race_before = _ledger_state()[0], len(rw.findings)
    racedep.perturb(seed, yield_prob=0.2)
    try:
        mismatched, errors = _run_streams(soak, seed, (3, 6))
    finally:
        racedep.restore()
    assert errors == []
    assert mismatched == []
    assert len(rw.findings) == race_before, rw.findings[race_before:]
    assert _ledger_state() == (led_before, 0)
