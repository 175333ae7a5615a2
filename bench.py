#!/usr/bin/env python
"""Benchmark: TPC-H Q6/Q1/Q3 on the TPU engine vs vectorized single-core
numpy CPU baselines (the CPU-Spark stand-in, BASELINE.json configs), plus a
COLD Q6 run (parquet decode + H2D + compute, nothing cached).

Scale factors: Q6 runs at BENCH_SF (default 10 — the fixed dispatch and
fetch round-trip amortizes over 60M rows), Q1 at
BENCH_SF_AGG (default 2), Q3 at BENCH_SF_JOIN (default 1, bounded by the
single-core numpy join baseline's runtime).

Hot runs use HBM-cached columnar tables (GpuInMemoryTableScan analog) so
the engine — not the host<->device copy — is measured; the cold run
measures the full parquet->result path. Headline timings are FRESH
executions: a new query tree is built (and re-planned) per timed
iteration, so resident operator state cannot flatter the numbers; the
old same-object reruns are reported as *_resident_replay_* for
comparison. First-ever shapes pay XLA compiles once per process — the
process-global program cache (runtime/program_cache.py) makes every
later same-shaped query, fresh or not, compile-free — and the
persistent compilation cache (spark_rapids_tpu/__init__.py) makes
subsequent processes start warm.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

`--smoke` (or BENCH_SMOKE=1) is the CI profile: tiny scale factors,
2 iterations, scan profile skipped — same JSON shape in ~a minute.

`--concurrent N` is the TPC-H *throughput* mode (the service PR's
acceptance surface): N client streams submit shuffled query mixes
through the session's QueryManager, reporting makespan, per-query
p50/p99 latency, queue-wait share, and service counters
(admitted/queued_peak/cancelled); every stream result is asserted
byte-identical to a serial reference run, and a forced mid-stream
cancel must leave zero resource leaks. Under --smoke the standard
bench also runs a 2-stream variant and embeds it in `extra`.
"""
import contextlib
import json
import os
import signal
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

# ---- time budgets (BENCH_r05 exited rc=124: the runner's global timeout
# killed the process before any JSON was emitted). The bench now enforces
# its OWN deadline, shorter than any plausible runner timeout, and always
# flushes a parseable artifact: per-query SIGALRM budgets inside the
# sweep, per-section budgets before it, and a partial-result flush when
# the global budget runs out mid-way. r05 showed 780s was NOT inside the
# runner's timeout — the partial flush never won the race — so the
# defaults now leave real headroom (600s global, 45s/query).
#
# --smoke (or BENCH_SMOKE=1): CI profile — tiny scale factors, 2 iters,
# no scan profile; exercises every code path including a 2-stream
# concurrent-service pass (330s budget: the sweep drains to its ~30s
# floor, and the concurrent tail section needs room after it).
_SMOKE = ("--smoke" in sys.argv[1:]
          or os.environ.get("BENCH_SMOKE", "") == "1")
if _SMOKE:
    # smoke doubles as the lockdep soak: witness every engine lock for
    # the whole run (must be in the env BEFORE the engine imports) and
    # record the order-graph stats in extra.lockdep
    os.environ.setdefault("SRTPU_LOCKDEP", "1")
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S",
                                 "330" if _SMOKE else "600"))
_QUERY_BUDGET_S = float(os.environ.get("BENCH_QUERY_BUDGET_S",
                                       "20" if _SMOKE else "45"))
_T0 = time.monotonic()

# --profile: embed the per-query top-5 operator breakdown (from the
# engine's per-op MetricSets) in the emitted JSON, so the bench
# trajectory carries attribution, not just totals
_PROFILE = ("--profile" in sys.argv[1:]
            or os.environ.get("BENCH_PROFILE", "") == "1")

# --concurrent N: TPC-H throughput mode through the query service
_CONCURRENT = 0
if "--concurrent" in sys.argv[1:]:
    _ci = sys.argv.index("--concurrent")
    try:
        _CONCURRENT = int(sys.argv[_ci + 1])
    except (IndexError, ValueError):
        print("bench: --concurrent needs a stream count", file=sys.stderr)
        sys.exit(2)

# --chaos SEED: fault-injection soak — concurrent TPC-H under a
# randomized (but seeded, reproducible) fault plan, asserting
# byte-identical results, zero strict-kind ledger imbalance, and
# bounded retries. The plan is derived from SEED alone: re-running
# with the same seed re-derives the same plan.
_CHAOS = None
if "--chaos" in sys.argv[1:]:
    _ci = sys.argv.index("--chaos")
    try:
        _CHAOS = int(sys.argv[_ci + 1])
    except (IndexError, ValueError):
        print("bench: --chaos needs an integer seed", file=sys.stderr)
        sys.exit(2)
    # the soak's cleanliness claims need the witnesses live from the
    # first engine import; racedep record-only (findings fail the pass
    # through its report, not by raising mid-query)
    os.environ.setdefault("SRTPU_LOCKDEP", "1")
    os.environ.setdefault("SRTPU_LEDGER", "1")
    os.environ.setdefault("SRTPU_RACEDEP", "1")
    os.environ.setdefault("SRTPU_RACEDEP_RAISE", "0")

# --zipfian (with --concurrent N): repeat-heavy variant — streams draw
# from a zipfian query mix through a cache-ENABLED session, with
# interleaved side-table writes proving invalidation soundness. This is
# the result-cache headline mode (target: >=10x q/s over the uniform
# all-fresh throughput baseline, byte-identical results).
_ZIPFIAN = "--zipfian" in sys.argv[1:]
if _ZIPFIAN and not _CONCURRENT:
    print("bench: --zipfian needs --concurrent N", file=sys.stderr)
    sys.exit(2)

# --fleet N (with --concurrent S): multi-host serving fabric mode — N
# REAL worker processes (python -m spark_rapids_tpu.fleet.worker) share
# one on-disk peer directory; S client streams draw a zipfian query mix
# and route every draw by plan fingerprint through the gateway `route`
# verb, so repeats land on the peer that already holds the bytes and
# cold keys are fetched over the peer-cache wire. Reports q/s vs a
# single-worker pass over the same workload, per-peer route/hit stats
# in extra.fleet, and asserts every routed result byte-identical to a
# local reference. A cold (N+1)th worker then joins mid-fleet and must
# reach steady-state latency within 5 queries (warm pull + peer hits).
_FLEET = 0
if "--fleet" in sys.argv[1:]:
    _fi = sys.argv.index("--fleet")
    try:
        _FLEET = int(sys.argv[_fi + 1])
    except (IndexError, ValueError):
        print("bench: --fleet needs a worker count", file=sys.stderr)
        sys.exit(2)
    if not _CONCURRENT:
        print("bench: --fleet needs --concurrent N", file=sys.stderr)
        sys.exit(2)
    if _FLEET < 1:
        print("bench: --fleet needs >= 1 worker", file=sys.stderr)
        sys.exit(2)

# --compile-tail: cold vs warm first-run compile tail across TPC-H —
# per-query sync compiles + compile wall ms on a cold process program
# cache, the fresh-rerun floor (must compile nothing), and the tail a
# service restart pays when an AOT warm pack is preloaded
# (sql.service.warmPack.path + stage-ahead prewarm from seeded specs).
_COMPILE_TAIL = "--compile-tail" in sys.argv[1:]

# --multichip: SPMD-stage dryrun — q3/q6 distributed shapes over an
# 8-device mesh through three paths (host shuffle / round-based mesh
# exchange / fused SpmdStageExec), asserting byte parity, exactly one
# compiled program per fused stage, and a compile-free warm rerun. The
# workload runs in a SUBPROCESS (workloads/spmd_bench.py): the virtual
# CPU device count must be in XLA_FLAGS before jax first imports, which
# this process cannot guarantee for itself. Results land in
# MULTICHIP_r06.json and extra.spmd_stage.
_MULTICHIP = "--multichip" in sys.argv[1:]

if _CHAOS is not None and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # chaos soak: give the CPU backend 8 virtual devices so the mesh
    # path (and its mesh.collective fault point) is live in the soak —
    # must be in the env before jax first imports; no-op on real
    # multi-chip backends
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

# milestone metrics flushed verbatim when the budget expires mid-run
_partial = {"extra": {}}


class _BenchTimeout(Exception):
    """A per-query / per-section / global time budget expired."""


def _remaining() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


@contextlib.contextmanager
def _alarm(seconds: float, what: str):
    """Raise _BenchTimeout inside the block after `seconds` (SIGALRM;
    fires when control next returns to Python — per-dispatch granularity
    under jax). <=0 seconds raises immediately: the global budget is
    already gone."""
    if seconds <= 0:
        raise _BenchTimeout(f"{what}: global budget exhausted")

    def on_alarm(signum, frame):
        raise _BenchTimeout(f"{what}: exceeded {seconds:.0f}s budget")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


def _section_budget() -> float:
    """Seconds a pre-sweep section may spend: bounded per section, and
    always reserving tail budget so the sweep + final flush still run."""
    return min(240.0, _remaining() - 120.0)


def _arm(what: str):
    """Start a section budget (SIGALRM -> _BenchTimeout). Statement
    form of _alarm for main's straight-line sections."""
    secs = _section_budget()
    if secs <= 0:
        raise _BenchTimeout(f"{what}: global budget exhausted")

    def on_alarm(signum, frame):
        raise _BenchTimeout(f"{what}: exceeded {secs:.0f}s budget")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, secs)


def _disarm():
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, signal.SIG_DFL)


def _best(fn, iters):
    fn()  # warm
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _best_fresh(build, iters, on_warm=None):
    """Honest engine timing: `build()` returns a NEW DataFrame tree each
    iteration, so every timed run re-plans and re-executes from scratch
    (planning + program-cache lookups included) instead of replaying a
    resident physical plan's device state. The first build warms the
    process-global program cache — XLA compiles are a process cost, not
    a per-query cost — and is untimed. `on_warm` fires between the warm
    run and the timed runs so callers can split compile activity into a
    cold (first execution) and warm (rerun) share."""
    build().to_arrow()  # warm: first-ever shapes pay their XLA compiles
    if on_warm is not None:
        on_warm()
    best = float("inf")
    for _ in range(max(iters, 1)):
        q = build()
        t0 = time.perf_counter()
        q.to_arrow()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    """Run the bench under the global budget; on budget exhaustion flush
    the milestones reached so far as the SAME one-line JSON shape (never
    rc=124 with no artifact)."""
    try:
        _main_impl()
    except _BenchTimeout as e:
        extra = _partial.get("extra", {})
        extra["budget_exhausted"] = str(e)
        extra["budget_s"] = _BUDGET_S
        print(f"bench: budget exhausted, flushing partial results: {e}",
              file=sys.stderr)
        print(json.dumps({
            "metric": _partial.get("metric", "tpch_bench_partial"),
            "value": _partial.get("value"),
            "unit": _partial.get("unit", "rows/s"),
            "vs_baseline": _partial.get("vs_baseline"),
            "extra": extra,
        }))


def _main_impl():
    sf = float(os.environ.get("BENCH_SF", "0.1" if _SMOKE else "10.0"))
    sf_agg = float(os.environ.get("BENCH_SF_AGG",
                                  "0.1" if _SMOKE else "2.0"))
    sf_join = float(os.environ.get("BENCH_SF_JOIN",
                                   "0.1" if _SMOKE else "1.0"))
    iters = int(os.environ.get("BENCH_ITERS", "2" if _SMOKE else "5"))
    plat = os.environ.get("BENCH_PLATFORM")
    if not plat and _MULTICHIP:
        # the multichip dryrun runs entirely in a subprocess on virtual
        # CPU devices; this parent must stay off the chip
        plat = "cpu"
    import jax
    if plat:
        jax.config.update("jax_platforms", plat)
    elif jax.default_backend() != "tpu":
        # no platform asked for means "measure the chip": a CPU number
        # is never printed under a device metric's name
        print(f"bench: default backend is {jax.default_backend()!r}, not "
              f"'tpu'; set BENCH_PLATFORM=cpu to run on the CPU on "
              f"purpose", file=sys.stderr)
        sys.exit(3)

    import spark_rapids_tpu as st
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.workloads import tpch

    # ---- standalone chaos soak: bench.py --chaos SEED -----------------
    if _CHAOS is not None:
        sf_c = float(os.environ.get("BENCH_SF_FULL",
                                    "0.05" if _SMOKE else "0.2"))
        with _alarm(_remaining() - 15.0, f"chaos soak seed={_CHAOS}"):
            soak = _chaos_soak(st, sf_c, _CHAOS,
                               n_streams=2 if _SMOKE else 4)
        print(json.dumps({
            "metric": f"tpch_chaos_soak_sf{sf_c}",
            "value": soak["queries_completed"],
            "unit": "queries",
            "vs_baseline": None,
            "extra": soak,
        }))
        if not soak["ok"]:
            print(f"bench: chaos soak FAILED: "
                  f"mismatched={soak['mismatched']} "
                  f"errors={soak.get('errors')} "
                  f"ledger_ok={soak['ledger'].get('balanceOk')} "
                  f"lockdep_findings="
                  f"{soak['lockdep'].get('findings')} "
                  f"fleet_ok={soak['fleet'].get('ok')}",
                  file=sys.stderr)
            sys.exit(1)
        return

    # ---- standalone multichip mode: bench.py --multichip --------------
    if _MULTICHIP:
        with _alarm(_remaining() - 15.0, "multichip spmd dryrun"):
            doc = _multichip_spmd()
        spmd = doc.get("spmd_stage") or {}
        # carried through partial flushes: a budget-killed later section
        # still ships the spmd_stage section it already earned
        _partial["extra"]["spmd_stage"] = spmd
        try:
            with open(os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "MULTICHIP_r06.json"),
                    "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        except OSError as e:
            print(f"bench: MULTICHIP_r06.json write failed: {e}",
                  file=sys.stderr)
        n_stages = sum(int(q.get("spmd_stages", 0))
                       for q in spmd.get("queries", {}).values())
        print(json.dumps({
            "metric": "tpch_multichip_spmd_dryrun",
            "value": n_stages,
            "unit": "fused_stages",
            "vs_baseline": None,
            "extra": doc,
        }))
        if not doc.get("ok"):
            print(f"bench: multichip spmd dryrun FAILED: rc={doc['rc']} "
                  f"queries="
                  f"{ {k: v.get('ok') for k, v in spmd.get('queries', {}).items()} } "
                  f"tail={doc.get('tail', '')[-400:]}", file=sys.stderr)
            sys.exit(1)
        return

    # ---- standalone compile-tail mode: bench.py --compile-tail --------
    if _COMPILE_TAIL:
        sf_c = float(os.environ.get("BENCH_SF_FULL",
                                    "0.05" if _SMOKE else "0.2"))
        with _alarm(_remaining() - 15.0, f"compile tail sf={sf_c}"):
            tail = _compile_tail(
                st, sf_c,
                qids=((1, 3, 5, 6, 10, 12, 14, 19)
                      if _SMOKE else None))
        print(json.dumps({
            "metric": f"tpch_compile_tail_sf{sf_c}",
            "value": tail.get("cold_compiles_geomean"),
            "unit": "xla_compiles_geomean",
            "vs_baseline": None,
            "extra": tail,
        }))
        return

    # ---- standalone throughput mode: bench.py --concurrent N ----------
    if _CONCURRENT:
        sf_c = float(os.environ.get("BENCH_SF_FULL",
                                    "0.05" if _SMOKE else "1.0"))
        # ---- fleet fabric mode: bench.py --concurrent S --fleet N -----
        if _FLEET:
            with _alarm(_remaining() - 15.0,
                        f"fleet x{_FLEET} ({_CONCURRENT} streams)"):
                flt = _fleet_throughput(st, _FLEET, _CONCURRENT,
                                        plat or "cpu")
            _partial["extra"]["fleet"] = flt
            print(json.dumps({
                "metric": (f"tpch_fleet_{_FLEET}workers_"
                           f"{_CONCURRENT}streams_q_per_s"),
                "value": flt.get("queries_per_sec"),
                "unit": "queries/s",
                "vs_baseline": flt.get("speedup_vs_single"),
                "extra": flt,
            }))
            if not flt.get("ok"):
                print(f"bench: fleet mode FAILED: "
                      f"mismatched={flt.get('mismatched')} "
                      f"errors={flt.get('errors')}", file=sys.stderr)
                sys.exit(1)
            return
        # the throughput mode is the whole run: no pre-sweep sections
        # follow it, so reserve only the final-flush tail
        mode = "zipfian" if _ZIPFIAN else "throughput"
        with _alarm(_remaining() - 15.0, f"{mode} x{_CONCURRENT}"):
            if _ZIPFIAN:
                # smoke keeps the serial fresh pass (one execution per
                # distinct query, XLA compiles included) inside the CI
                # budget by drawing from a fast 8-query mix
                conc = _zipfian_throughput(
                    st, sf_c, _CONCURRENT,
                    qids=((1, 3, 5, 6, 10, 12, 14, 19)
                          if _SMOKE else None))
            else:
                s = st.TpuSession()
                conc = _concurrent_throughput(s, sf_c, _CONCURRENT)
        try:
            conc["telemetry"] = _telemetry_snapshot()
        except Exception:  # advisory: never lose the bench result
            pass
        print(json.dumps({
            "metric": (f"tpch_{mode}_{_CONCURRENT}streams_"
                       f"sf{sf_c}_q_per_s"),
            "value": conc["queries_per_sec"],
            "unit": "queries/s",
            "vs_baseline": conc.get("speedup_vs_uncached",
                                    conc.get("throughput_vs_serial")),
            "extra": conc,
        }))
        return

    # ---- Q6 @ BENCH_SF --------------------------------------------------
    _arm("q6 hot")
    at = tpch.gen_lineitem(sf=sf, seed=7)
    n = at.num_rows

    def unscaled(t, name):
        return np.asarray(
            Column.host_from_arrow(t.column(name))[2]["data"][:t.num_rows])

    ship = at.column("l_shipdate").to_numpy()
    qty = unscaled(at, "l_quantity")
    price = unscaled(at, "l_extendedprice")
    disc = unscaled(at, "l_discount")
    base_q6_val = tpch.q6_numpy_baseline(ship, disc, qty, price)
    cpu_q6 = _best(lambda: tpch.q6_numpy_baseline(ship, disc, qty, price),
                   min(iters, 3))

    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 1 << 22})
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
    df = s.create_dataframe(at.select(cols)).cache()
    q = tpch.q6(df)
    r = q.to_arrow()
    import decimal
    got = r.column(0).to_pylist()[0]
    expect = decimal.Decimal(base_q6_val).scaleb(-4)
    assert got == expect, f"Q6 mismatch: {got} != {expect}"
    # headline: FRESH execution — a new query tree per timed iteration
    # (the cached input table stays; that is the GpuInMemoryTableScan
    # analog, not resident operator state). Same-object replay is the
    # old optimistic number, reported separately as resident_replay.
    tpu_q6 = _best_fresh(lambda: tpch.q6(df), iters)
    tpu_q6_replay = _best(lambda: q.to_arrow(), iters)
    _disarm()
    _partial.update({"metric": f"tpch_q6_sf{sf}_rows_per_sec",
                     "value": round(n / tpu_q6, 1),
                     "vs_baseline": round(cpu_q6 / tpu_q6, 3)})
    _partial["extra"]["q6_fresh_ms"] = round(tpu_q6 * 1e3, 2)
    _partial["extra"]["q6_resident_replay_ms"] = round(
        tpu_q6_replay * 1e3, 2)

    # ---- cold Q6 (parquet -> result, same SF) ---------------------------
    import shutil
    _arm("q6 cold")
    pq_dir = tempfile.mkdtemp(prefix="srtpu-bench-")
    try:
        pq_path = os.path.join(pq_dir, "lineitem.parquet")
        import pyarrow.parquet as pq_mod
        pq_mod.write_table(at.select(cols), pq_path)

        def cold_q6():
            s2 = st.TpuSession(
                {"spark.rapids.tpu.sql.batchSizeRows": 1 << 22})
            return tpch.q6(s2.read.parquet(pq_path)).to_arrow()

        cold_val = cold_q6().column(0).to_pylist()[0]
        assert cold_val == expect, f"cold Q6 mismatch: {cold_val}"
        t0 = time.perf_counter()
        cold_q6()
        tpu_q6_cold = time.perf_counter() - t0
    finally:
        shutil.rmtree(pq_dir, ignore_errors=True)
    _disarm()
    _partial["extra"]["q6_cold_s"] = round(tpu_q6_cold, 3)
    # smoke gate: a FRESH rerun of an already-seen shape must compile
    # nothing — the process-global program cache's core guarantee
    if _SMOKE:
        from spark_rapids_tpu.profiler import xla_stats
        x0 = xla_stats.snapshot()
        tpch.q6(df).to_arrow()
        x1 = xla_stats.snapshot()
        fresh_compiles = int(x1["compiles"] - x0["compiles"])
        _partial["extra"]["fresh_rerun_compiles"] = fresh_compiles
        assert fresh_compiles == 0, (
            f"fresh rerun of q6 compiled {fresh_compiles} XLA programs; "
            f"the program cache must make it zero")
    del df, q
    if sf != sf_agg:
        del at, ship, qty, price, disc

    # ---- Q1 @ BENCH_SF_AGG ---------------------------------------------
    _arm("q1")
    at1 = tpch.gen_lineitem(sf=sf_agg, seed=7)
    n1 = at1.num_rows
    ship1 = at1.column("l_shipdate").to_numpy()
    qty1 = unscaled(at1, "l_quantity")
    price1 = unscaled(at1, "l_extendedprice")
    disc1 = unscaled(at1, "l_discount")
    tax1 = unscaled(at1, "l_tax")
    rf_codes = np.select(
        [at1.column("l_returnflag").to_numpy(zero_copy_only=False) == c
         for c in ("A", "N", "R")], [0, 1, 2])
    ls_codes = np.select(
        [at1.column("l_linestatus").to_numpy(zero_copy_only=False) == c
         for c in ("F", "O")], [0, 1])
    cpu_q1 = _best(lambda: tpch.q1_numpy_baseline(
        ship1, rf_codes, ls_codes, qty1, price1, disc1, tax1),
        min(iters, 3))
    df1 = s.create_dataframe(at1).cache()
    q1 = tpch.q1(df1)
    q1.to_arrow()
    tpu_q1 = _best_fresh(lambda: tpch.q1(df1), min(iters, 3))
    tpu_q1_replay = _best(lambda: q1.to_arrow(), min(iters, 3))
    _disarm()
    _partial["extra"]["q1_rows_per_sec"] = round(n1 / tpu_q1, 1)
    _partial["extra"]["q1_resident_replay_ms"] = round(
        tpu_q1_replay * 1e3, 2)
    del df1, q1

    # ---- Q3 @ BENCH_SF_JOIN --------------------------------------------
    _arm("q3")
    at3 = (at1 if sf_join == sf_agg
           else tpch.gen_lineitem(sf=sf_join, seed=7))
    cust = tpch.gen_customer(sf=sf_join)
    orders = tpch.gen_orders(sf=sf_join)
    segs = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"])
    c_seg = np.select(
        [cust.column("c_mktsegment").to_numpy(zero_copy_only=False) == s_
         for s_ in segs], [0, 1, 2, 3, 4])
    # best-of-3 for the baseline too: r2 recorded a single 2.33s sample
    # for a loop that takes 0.41s warm, and the resulting "4.49x" was an
    # artifact that r3 then "regressed" from (VERDICT r3 missing #2)
    cpu_q3 = _best(lambda: tpch.q3_numpy_baseline(
        cust.column("c_custkey").to_numpy(), c_seg,
        orders.column("o_orderkey").to_numpy(),
        orders.column("o_custkey").to_numpy(),
        orders.column("o_orderdate").to_numpy(),
        orders.column("o_shippriority").to_numpy(),
        at3.column("l_orderkey").to_numpy(),
        at3.column("l_shipdate").to_numpy(),
        unscaled(at3, "l_extendedprice"), unscaled(at3, "l_discount")), 3)
    df3 = s.create_dataframe(at3).cache()
    cust_df = s.create_dataframe(cust).cache()
    ord_df = s.create_dataframe(orders).cache()
    q3 = tpch.q3(cust_df, ord_df, df3)
    q3.to_arrow()
    tpu_q3 = _best_fresh(lambda: tpch.q3(cust_df, ord_df, df3), 2)
    tpu_q3_replay = _best(lambda: q3.to_arrow(), 2)
    _disarm()
    _partial["extra"]["q3_s"] = round(tpu_q3, 3)
    _partial["extra"]["q3_resident_replay_s"] = round(tpu_q3_replay, 3)

    # ---- full TPC-H sweep @ BENCH_SF_FULL (geomean over all 22) ---------
    # default SF1: the round-4 verdict's bar is
    # tpch_all22_vs_pandas_geomean >= 1.0 at SF >= 1
    sf_full = float(os.environ.get("BENCH_SF_FULL",
                                   "0.05" if _SMOKE else "1.0"))
    tpch_all = _tpch_sweep(s, sf_full)
    _partial["extra"].update(tpch_all)

    # ---- scan profile: device-decode eligibility + time split ----------
    # (ISSUE 4 acceptance: eligibility fraction of the snappy bench
    # dataset's column-chunk bytes, and where scan wall time goes).
    # Skipped under --smoke: it rewrites the whole dataset as parquet.
    if _SMOKE:
        _partial["extra"]["smoke"] = True
        from spark_rapids_tpu.runtime import lockdep as _lockdep
        _lw = _lockdep.witness()
        if _lw is not None:
            # filled in now so a budget-expiry partial flush still
            # carries it; refreshed after the concurrent tail below
            _partial["extra"]["lockdep"] = _lw.report()
        from spark_rapids_tpu.runtime import ledger as _ledger
        _lg = _ledger.ledger()
        if _lg is not None:
            # resource acquire/release balance for the run so far —
            # same partial-flush/refresh lifecycle as lockdep
            _partial["extra"]["ledger"] = _lg.report()
        # AQE replan counters accumulated by the sweep above (ISSUE 12):
        # coalesced partitions, skew splits, join demotions, calibration
        # hits — filled in now for partial flushes, refreshed after the
        # concurrent tail so its replans count too
        try:
            from spark_rapids_tpu.plan.aqe import aqe_stats as _aqe_stats
            _partial["extra"]["aqe"] = _aqe_stats()
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["aqe"] = {"error": repr(e)[:300]}
        # exchange-pipeline smoke (ISSUE 9): reuse dedup, q4 map-thread
        # speedup, serial/parallel/reused parity — before the
        # concurrent section so both share what budget remains
        try:
            with _alarm(max(0.0, _remaining() - 60.0),
                        "exchange pipeline smoke"):
                _partial["extra"]["exchange"] = _exchange_smoke(sf_full)
        except _BenchTimeout as e:
            _partial["extra"]["exchange"] = {"error": f"timeout: {e}"}
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["exchange"] = {"error": repr(e)[:300]}
            print(f"bench: exchange smoke failed: {e!r}",
                  file=sys.stderr)
        # result-cache smoke (ISSUE 11): 2-stream zipfian mix over a
        # fast query subset through a cache-enabled session — hit rate,
        # byte identity vs fresh, and write-invalidation soundness land
        # in extra.result_cache
        try:
            with _alarm(max(0.0, _remaining() - 45.0),
                        "result cache smoke"):
                _partial["extra"]["result_cache"] = _zipfian_throughput(
                    st, sf_full, 2, draws=8, qids=(1, 3, 6, 12, 14))
        except _BenchTimeout as e:
            _partial["extra"]["result_cache"] = {"error": f"timeout: {e}"}
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["result_cache"] = {"error": repr(e)[:300]}
            print(f"bench: result cache smoke failed: {e!r}",
                  file=sys.stderr)
        # chaos smoke (ISSUE 14): a short seeded fault-injection soak
        # over a fast query subset — injected/recovered counters land
        # in extra.chaos and survive partial flushes
        try:
            with _alarm(max(0.0, _remaining() - 30.0), "chaos smoke"):
                soak = _chaos_soak(st, sf_full, seed=7, n_streams=2,
                                   qids=(3, 6, 12))
            _partial["extra"]["chaos"] = {
                "ok": soak["ok"],
                "seed": soak["seed"],
                "queries_completed": soak["queries_completed"],
                "mismatched": soak["mismatched"],
                "injected": soak["injected"],
                "recovered": soak["recovered"],
                "regenerations": soak["regenerations"],
                "query_retries": soak["query_retries"],
                "degradations": soak["degradations"],
                "fleet": soak["fleet"],
                "schedule_perturbation": soak["schedule_perturbation"],
                **({"errors": soak["errors"]}
                   if soak.get("errors") else {}),
            }
        except _BenchTimeout as e:
            _partial["extra"]["chaos"] = {"error": f"timeout: {e}"}
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["chaos"] = {"error": repr(e)[:300]}
            print(f"bench: chaos smoke failed: {e!r}", file=sys.stderr)
        # 2-stream throughput variant: the concurrent query service's
        # smoke surface (byte-identical to serial, no leaks after a
        # forced cancel, service counters in extra.service). This is
        # the LAST section before the final emit, so it reserves only
        # the flush tail (the 120s _arm reserve would starve it — the
        # sweep already drained the budget near its own floor), and it
        # runs an 8-query warm-replay-fast subset, not all 22.
        try:
            with _alarm(max(0.0, _remaining() - 10.0),
                        "concurrent 2-stream smoke"):
                conc = _concurrent_throughput(
                    s, sf_full, 2,
                    qids=(3, 5, 6, 9, 11, 12, 14, 19))
            _partial["extra"]["concurrent_2stream"] = conc
            _partial["extra"]["service"] = conc["service"]
        except _BenchTimeout as e:
            _partial["extra"]["concurrent_2stream"] = {
                "error": f"timeout: {e}"}
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["concurrent_2stream"] = {
                "error": repr(e)[:300]}
            print(f"bench: concurrent smoke failed: {e!r}",
                  file=sys.stderr)
        # live-telemetry extract (ISSUE 17): latency/queue-wait
        # histograms, pool saturation and per-category critical-path
        # shares across everything this smoke ran — recorded into the
        # partial so a budget-exhausted flush still carries it
        try:
            _partial["extra"]["telemetry"] = _telemetry_snapshot()
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["telemetry"] = {"error": repr(e)[:300]}
    else:
        try:
            _arm("scan profile")
            _partial["extra"]["scan_profile"] = _scan_profile(st, sf_full)
            _disarm()
        except _BenchTimeout as e:
            _partial["extra"]["scan_profile"] = {"error": f"timeout: {e}"}
        except Exception as e:  # advisory: never lose the bench result
            _partial["extra"]["scan_profile"] = {"error": repr(e)[:300]}
            print(f"bench: scan profile failed: {e!r}", file=sys.stderr)

    rows_per_s = n / tpu_q6
    from spark_rapids_tpu.runtime import program_cache
    pc = program_cache.stats()
    extra = {
        # every headline number below times a FRESH query tree per
        # iteration (new DataFrame, re-planned); *_resident_replay_* are
        # the old same-object reruns, kept for comparison only
        "methodology": "fresh",
        "q6_fresh_ms": round(tpu_q6 * 1e3, 2),
        "q6_resident_replay_ms": round(tpu_q6_replay * 1e3, 2),
        "q6_cold_s": round(tpu_q6_cold, 3),
        "q6_cold_rows_per_sec": round(n / tpu_q6_cold, 1),
        "q1_sf": sf_agg,
        "q1_rows_per_sec": round(n1 / tpu_q1, 1),
        "q1_resident_replay_ms": round(tpu_q1_replay * 1e3, 2),
        "q1_vs_numpy": round(cpu_q1 / tpu_q1, 3),
        "q3_sf": sf_join,
        "q3_s": round(tpu_q3, 3),
        "q3_resident_replay_s": round(tpu_q3_replay, 3),
        "q3_vs_numpy": round(cpu_q3 / tpu_q3, 3),
        "program_cache": {
            "hits": int(pc.get("program_cache_hits", 0)),
            "misses": int(pc.get("program_cache_misses", 0)),
            "evictions": int(pc.get("program_cache_evictions", 0)),
        },
        **tpch_all,
    }
    # milestone-only keys (scan profile, smoke flag) must survive into
    # the success-path JSON too, not just the partial flush
    if "lockdep" in _partial["extra"]:
        # refresh: the report should cover the concurrent tail too
        from spark_rapids_tpu.runtime import lockdep as _lockdep
        _lw = _lockdep.witness()
        if _lw is not None:
            _partial["extra"]["lockdep"] = _lw.report()
    if "aqe" in _partial["extra"]:
        # refresh: the concurrent tail's replans should count too
        try:
            from spark_rapids_tpu.plan.aqe import aqe_stats as _aqe_stats
            _partial["extra"]["aqe"] = _aqe_stats()
        except Exception:
            pass
    if "ledger" in _partial["extra"]:
        # refresh: the concurrent tail's queries must balance too
        from spark_rapids_tpu.runtime import ledger as _ledger
        _lg = _ledger.ledger()
        if _lg is not None:
            _partial["extra"]["ledger"] = _lg.report()
    for k in ("scan_profile", "smoke", "fresh_rerun_compiles",
              "concurrent_2stream", "service", "exchange", "lockdep",
              "result_cache", "aqe", "ledger", "chaos", "telemetry"):
        if k in _partial["extra"]:
            extra[k] = _partial["extra"][k]
    # ---- regression gate vs the previous round's JSON -------------------
    # Engine-time metrics only (rows/s, q*_s): the *_vs_numpy ratios mix in
    # the baseline sample and the host machine, which is exactly how the
    # r2->r3 "Q3 regression" was misread (VERDICT r3 weak #9 / missing #2).
    try:
        regressions = _regression_gate({
            "q6_rows_per_sec": rows_per_s,
            "q1_rows_per_sec": n1 / tpu_q1,
            "q3_s": tpu_q3,
            # cold + whole-suite metrics: the r3->r4 2.3x cold-Q6
            # regression slipped through a gate that only watched hot
            # paths (VERDICT r4 weak #2)
            "q6_cold_s": extra.get("q6_cold_s"),
            "tpch_all22_geomean_s": tpch_all.get("tpch_all22_geomean_s"),
        }, {"q1_sf": sf_agg, "q3_sf": sf_join, "q6_sf": sf,
                      "tpch_sf": tpch_all.get("tpch_all22_sf")},
            xla_per_query=tpch_all.get("tpch_xla_per_query"),
            telemetry=extra.get("telemetry"))
    except Exception as e:  # advisory: never lose the bench result
        regressions = []
        extra["regression_gate_error"] = repr(e)
        print(f"bench: regression gate failed: {e!r}", file=sys.stderr)
    if regressions:
        extra["regressions_vs_prev_round"] = regressions
        for r in regressions:
            print(f"bench: REGRESSION {r}", file=sys.stderr)
    print(json.dumps({
        "metric": f"tpch_q6_sf{sf}_rows_per_sec",
        "value": round(rows_per_s, 1),
        "unit": "rows/s",
        "vs_baseline": round(cpu_q6 / tpu_q6, 3),
        "extra": extra,
    }))


def _tpch_sweep(s, sf: float):
    """All 22 TPC-H queries once (hot, tables cached): per-query seconds,
    geomean, and geomean speedup vs the pandas oracles on the same data
    (the CPU single-core stand-in; VERDICT r3 next #2 'geomean
    reported')."""
    import math
    from spark_rapids_tpu.workloads import tpch
    from spark_rapids_tpu.workloads.tpch_oracle import ORACLES, to_pandas
    with _alarm(min(180.0, _remaining() - 45.0), "tpch sweep setup"):
        tabs = tpch.gen_all(sf=sf, seed=7)
        dfs = {k: s.create_dataframe(v).cache() for k, v in tabs.items()}
        host = to_pandas(tabs)
    from spark_rapids_tpu.profiler import xla_stats
    reg = tpch.queries()
    engine_s, oracle_s, errors = {}, {}, {}
    replay_s = {}
    profile, xla = {}, {}
    for qn in range(1, 23):
        # per-query guard: one failing OR straggling query (unsupported
        # op on a new backend, OOM, runaway plan) must not lose the whole
        # bench result — the BENCH_r05 rc=124 failure mode. Timed-out /
        # skipped queries land in errors; the geomean below covers
        # whatever completed.
        left = _remaining() - 30.0       # reserve the final-flush tail
        if left <= 2.0:
            for m in range(qn, 23):
                errors[f"q{m}"] = "skipped: bench global budget exhausted"
            print(f"bench: global budget exhausted at q{qn}; "
                  f"flushing partial sweep", file=sys.stderr)
            break
        try:
            with _alarm(min(_QUERY_BUDGET_S, left), f"tpch q{qn}"):
                q = reg[qn](dfs)
                x0 = xla_stats.snapshot()
                # headline: fresh tree per timed iteration; the same-
                # object rerun is the optimistic resident_replay number
                xw = {}
                e_t = _best_fresh(lambda: reg[qn](dfs), 2,
                                  on_warm=lambda:
                                  xw.update(xla_stats.snapshot()))
                x1 = xla_stats.snapshot()
                r_t = _best(lambda: q.to_arrow(), 1)
                o_t = _best(lambda: ORACLES[qn](host), 2)
            # assign together: a failed oracle must not leave a dangling
            # engine_s entry that KeyErrors the geomean below
            engine_s[qn], oracle_s[qn] = e_t, o_t
            replay_s[qn] = r_t
            # XLA activity across the query's 3 runs (warm + 2 timed):
            # the whole-stage fusion acceptance metric — fewer programs
            # compiled and fewer per-batch dispatches at equal results
            rec = {
                "compiles": int(x1["compiles"] - x0["compiles"]),
                "dispatches": int(x1["dispatches"] - x0["dispatches"]),
            }
            if xw:
                # cold/warm split: the warm-up run pays the first-run
                # compile tail (the --compile-tail target metric); the
                # timed fresh reruns must compile nothing (PR 6 gate)
                rec["compiles_cold"] = int(xw["compiles"]
                                           - x0["compiles"])
                rec["compiles_warm"] = int(x1["compiles"]
                                           - xw["compiles"])
                rec["compile_ms_cold"] = round(
                    float(xw.get("program_cache_compile_ms", 0.0)
                          - x0.get("program_cache_compile_ms", 0.0)), 1)
            xla[f"q{qn}"] = rec
            if _PROFILE:
                try:
                    from spark_rapids_tpu.profiler.event_log import (
                        op_metrics_records, top_operators)
                    root = getattr(q, "_last_root", None)
                    if root is not None:
                        profile[f"q{qn}"] = top_operators(
                            op_metrics_records(root, q.last_metrics()),
                            5)
                except Exception as pe:  # attribution is advisory
                    profile[f"q{qn}"] = f"profile failed: {pe!r}"
        except _BenchTimeout as e:
            errors[f"q{qn}"] = f"timeout: {e}"
            print(f"bench: tpch q{qn} timed out: {e}", file=sys.stderr)
        except Exception as e:
            errors[f"q{qn}"] = repr(e)[:300]
            print(f"bench: tpch q{qn} failed: {e!r}", file=sys.stderr)
    out = {"tpch_all22_sf": sf}
    if engine_s:
        k = len(engine_s)
        geo = math.exp(sum(math.log(v) for v in engine_s.values()) / k)
        geo_speedup = math.exp(
            sum(math.log(oracle_s[q] / engine_s[q]) for q in engine_s) / k)
        out.update({
            "tpch_all22_geomean_s": round(geo, 4),
            "tpch_all22_vs_pandas_geomean": round(geo_speedup, 3),
            "tpch_all22_per_query_ms": {
                f"q{q}": round(v * 1e3, 1) for q, v in engine_s.items()},
        })
        if replay_s:
            k_r = len(replay_s)
            geo_r = math.exp(
                sum(math.log(v) for v in replay_s.values()) / k_r)
            out["tpch_all22_resident_replay_geomean_s"] = round(geo_r, 4)
            out["tpch_all22_resident_replay_per_query_ms"] = {
                f"q{q}": round(v * 1e3, 1)
                for q, v in replay_s.items()}
    if xla:
        out["tpch_xla_per_query"] = xla
    if profile:
        out["tpch_profile"] = profile
    if errors:
        out["tpch_all22_errors"] = errors
    return out


def _compile_tail(st, sf: float, qids=None) -> dict:
    """Cold vs warm first-run compile tail (ISSUE 15 acceptance).

    Per query, on a process program cache cleared once up front:
    `cold` = the first execution (sync compiles, compile wall ms,
    end-to-end seconds — the first-user-visible-query tail), `warm` =
    a fresh-tree rerun (must compile nothing, PR 6 gate; wall is the
    steady-state floor). After the sweep the observed program set is
    saved as a warm pack, the cache is cleared again (simulated fresh
    process), the pack preloaded, and each query tree stage-ahead
    prewarmed from the seeded specs with the pool drained before the
    `packed` execution — the tail a service restart actually pays with
    `sql.service.warmPack.path` set."""
    import math
    import shutil
    import tempfile

    from spark_rapids_tpu.exec.base import prewarm_tree
    from spark_rapids_tpu.profiler import xla_stats
    from spark_rapids_tpu.runtime import (compile_pool, program_cache,
                                          warm_pack)
    from spark_rapids_tpu.workloads import tpch

    s = st.TpuSession()
    tabs = tpch.gen_all(sf=sf, seed=7)
    dfs = {k: s.create_dataframe(v).cache() for k, v in tabs.items()}
    reg = tpch.queries()
    qids = [q for q in (qids or range(1, 23)) if q in reg]
    program_cache.clear()
    program_cache.set_active_conf(s.conf)

    def _pc_ms(x):
        return float(x.get("program_cache_compile_ms", 0.0))

    per_q, errors = {}, {}
    for qn in qids:
        left = _remaining() - 30.0
        if left <= 2.0:
            errors[f"q{qn}"] = "skipped: bench global budget exhausted"
            continue
        try:
            with _alarm(min(_QUERY_BUDGET_S * 2, left),
                        f"compile-tail q{qn}"):
                x0 = xla_stats.snapshot()
                t0 = time.perf_counter()
                reg[qn](dfs).to_arrow()
                cold_s = time.perf_counter() - t0
                x1 = xla_stats.snapshot()
                t0 = time.perf_counter()
                reg[qn](dfs).to_arrow()
                warm_s = time.perf_counter() - t0
                x2 = xla_stats.snapshot()
            per_q[f"q{qn}"] = {
                "cold_compiles": int(x1["compiles"] - x0["compiles"]),
                "cold_compile_ms": round(_pc_ms(x1) - _pc_ms(x0), 1),
                "cold_s": round(cold_s, 4),
                "warm_compiles": int(x2["compiles"] - x1["compiles"]),
                "warm_s": round(warm_s, 4),
            }
        except _BenchTimeout as e:
            errors[f"q{qn}"] = f"timeout: {e}"
        except Exception as e:
            errors[f"q{qn}"] = repr(e)[:300]

    out = {"compile_tail_sf": sf, "per_query": per_q}
    if errors:
        out["errors"] = errors
    if per_q:
        # geomean over max(1, count): zero-compile queries must not
        # zero the product, and the acceptance metric is the trajectory
        # of this number vs earlier BENCH tpch_xla_per_query artifacts
        k = len(per_q)
        out["cold_compiles_geomean"] = round(math.exp(
            sum(math.log(max(1, v["cold_compiles"]))
                for v in per_q.values()) / k), 2)
        out["cold_compile_ms_total"] = round(
            sum(v["cold_compile_ms"] for v in per_q.values()), 1)
        out["warm_compiles_total"] = sum(
            v["warm_compiles"] for v in per_q.values())

    # ---- packed phase: simulated service restart with a warm pack ----
    tmpd = tempfile.mkdtemp(prefix="srtpu_pack_")
    try:
        pack = warm_pack.save(s.conf, os.path.join(tmpd, "tpch.pack"))
        if pack and _remaining() > 60.0:
            program_cache.clear()
            program_cache.set_active_conf(s.conf)
            summary = warm_pack.preload(s, pack)
            pool = compile_pool.get_pool(s.conf)
            packed = {}
            for qn in qids:
                if f"q{qn}" not in per_q or _remaining() <= 45.0:
                    continue
                try:
                    with _alarm(min(_QUERY_BUDGET_S * 2,
                                    _remaining() - 30.0),
                                f"compile-tail packed q{qn}"):
                        q = reg[qn](dfs)
                        root, _ = q._execute(None)  # plan only
                        if pool is not None:
                            prewarm_tree(root, pool)
                            pool.drain(min(60.0, _remaining() - 40.0))
                        x0 = xla_stats.snapshot()
                        t0 = time.perf_counter()
                        q.to_arrow()
                        packed_s = time.perf_counter() - t0
                        x1 = xla_stats.snapshot()
                    packed[f"q{qn}"] = {
                        "compiles": int(x1["compiles"] - x0["compiles"]),
                        "compile_ms": round(_pc_ms(x1) - _pc_ms(x0), 1),
                        "s": round(packed_s, 4),
                    }
                except _BenchTimeout as e:
                    errors[f"packed_q{qn}"] = f"timeout: {e}"
                except Exception as e:
                    errors[f"packed_q{qn}"] = repr(e)[:300]
            out["packed_per_query"] = packed
            out["warm_pack"] = {
                "programs": summary.get("programs"),
                "matched": summary.get("programs_matched"),
                "seeded": summary.get("seeded"),
                "submitted": summary.get("submitted"),
            }
            if packed:
                out["packed_compile_ms_total"] = round(
                    sum(v["compile_ms"] for v in packed.values()), 1)
            if errors:
                out["errors"] = errors
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)
    return out


def _multichip_spmd() -> dict:
    """Run the SPMD-stage dryrun (workloads/spmd_bench.py) in a
    subprocess with 8 virtual CPU devices forced into XLA_FLAGS — the
    flag must precede jax's first import, which only a fresh process
    guarantees — and fold its one-JSON-document stdout into the
    MULTICHIP artifact shape ({n_devices, rc, ok, tail} plus
    the new spmd_stage section)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = env.get("BENCH_PLATFORM") or "cpu"
    if "--xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    env.setdefault("SPMD_BENCH_SF", "0.01" if _SMOKE else "0.02")
    here = os.path.dirname(os.path.abspath(__file__))
    budget = max(30.0, _remaining() - 30.0)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "spark_rapids_tpu.workloads.spmd_bench"],
            cwd=here, env=env, capture_output=True, text=True,
            timeout=budget)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = (e.stdout or b"").decode() if isinstance(
            e.stdout, bytes) else (e.stdout or "")
        err = f"timeout after {budget:.0f}s"
    tail = (err or "")[-2000:]
    spmd = None
    for line in reversed((out or "").strip().splitlines()):
        try:
            spmd = json.loads(line)
            break
        except ValueError:
            continue
    doc = {
        "n_devices": (spmd or {}).get("n_devices", 8),
        "rc": rc,
        "ok": bool(rc == 0 and spmd is not None
                   and spmd.get("ok", False)),
        "tail": tail,
        "spmd_stage": spmd,
    }
    return doc


def _fleet_rpc(addr, req: dict, timeout: float = 60.0) -> dict:
    """One JSON-line request/response against a worker gateway."""
    import socket
    with socket.create_connection(tuple(addr), timeout=timeout) as c:
        with c.makefile("rwb") as f:
            f.write((json.dumps(req) + "\n").encode("utf-8"))
            f.flush()
            line = f.readline()
    if not line:
        raise ConnectionError(f"gateway {addr} closed the connection")
    return json.loads(line)


def _fleet_spawn(n: int, fleet_dir: str, views, confs, plat: str,
                 log_dir: str, tag: str, timeout: float = 240.0) -> list:
    """Launch n fleet workers and wait for their READY lines. Each is a
    REAL interpreter (cold program cache, own GIL); stderr goes to a
    per-worker log whose tail is surfaced on startup failure."""
    import select
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = plat   # workers must not fight over a TPU
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "spark_rapids_tpu.fleet.worker",
           "--fleet-dir", fleet_dir]
    for name, path in views:
        cmd += ["--view", f"{name}={path}"]
    for kv in confs:
        cmd += ["--conf", kv]
    workers, procs = [], []
    for i in range(n):
        log = open(os.path.join(log_dir, f"{tag}{i}.log"), "w")
        procs.append((subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=log, env=env, text=True, bufsize=1), log))
    deadline = time.monotonic() + timeout
    try:
        for proc, log in procs:
            info = None
            while time.monotonic() < deadline:
                r, _, _ = select.select(
                    [proc.stdout], [], [],
                    max(0.1, deadline - time.monotonic()))
                if not r:
                    break
                line = proc.stdout.readline()
                if not line:
                    break
                if line.startswith("READY "):
                    info = json.loads(line[len("READY "):])
                    break
            if info is None:
                tail = ""
                try:
                    log.flush()
                    with open(log.name) as lf:
                        tail = lf.read()[-600:]
                except OSError:
                    pass
                raise RuntimeError(
                    f"fleet worker {log.name} not READY in {timeout:.0f}s"
                    f" (rc={proc.poll()}): ...{tail}")
            workers.append({"proc": proc, "log": log,
                            "addr": (info["host"], info["port"]),
                            "peer_id": info["peer_id"],
                            "warm": info.get("warm")})
    except BaseException:
        for proc, log in procs:
            _fleet_stop({"proc": proc, "log": log})
        raise
    return workers


def _fleet_stop(w) -> None:
    proc, log = w["proc"], w["log"]
    try:
        if proc.stdin and not proc.stdin.closed:
            proc.stdin.write("stop\n")
            proc.stdin.flush()
            proc.stdin.close()
    except OSError:
        pass
    try:
        proc.wait(timeout=20)
    except Exception:  # noqa: BLE001 — last resort below
        proc.kill()
        proc.wait(timeout=10)
    try:
        log.close()
    except OSError:
        pass


def _fleet_run_one(entry_addr, sql: str, tenant: str):
    """Route one draw through an entry gateway, execute it on the
    routed peer, fetch the JSON-serialized result, release the lease.
    Returns (peer_id, sticky, columns) or ("", None, None) when the
    router rejected the tenant (admission cap)."""
    r = _fleet_rpc(entry_addr, {"op": "route", "sql": sql,
                                "tenant": tenant})
    if not r.get("ok"):
        if r.get("rejected"):
            return "", None, None
        raise RuntimeError(f"route failed: {r}")
    try:
        cols = _fleet_exec((r["host"], r["port"]), sql)
    finally:
        try:
            _fleet_rpc(entry_addr, {"op": "route_done",
                                    "lease": r["lease"]})
        except Exception:  # noqa: BLE001 — lazy TTL reclaims the lease
            pass
    return r["peer_id"], bool(r.get("sticky")), cols


def _fleet_exec(addr, sql: str) -> dict:
    """Submit directly to one gateway (no routing) and fetch the
    JSON-serialized result columns."""
    sub = _fleet_rpc(addr, {"op": "submit", "sql": sql})
    if not sub.get("ok"):
        raise RuntimeError(f"submit failed: {sub}")
    qid = sub["query_id"]
    while True:
        stt = _fleet_rpc(addr, {"op": "status", "query_id": qid})
        if stt.get("state") in ("FINISHED", "FAILED", "CANCELLED",
                                "TIMED_OUT"):
            break
        time.sleep(0.005)
    fr = _fleet_rpc(addr, {"op": "fetch", "query_id": qid,
                           "page_rows": 1 << 20})
    if not fr.get("ok"):
        raise RuntimeError(f"fetch failed on {addr}: {fr}")
    return fr["columns"]


def _fleet_workload(workers, queries, refs, n_streams: int,
                    draws: int, seed: int) -> dict:
    """Zipfian draw loop over the fleet: each stream round-robins its
    ENTRY gateway (any peer can front any query) and executes where the
    router points. Every fetched result is compared against the local
    reference for that query."""
    import random
    import threading

    order = list(range(len(queries)))
    random.Random(99).shuffle(order)
    weights = [1.0 / (k + 1) ** 1.2 for k in range(len(order))]
    results, errors = [], []     # (qi, peer_id, sticky, lat_s, match)
    lock = threading.Lock()

    def stream(i: int):
        rng = random.Random(seed + i)
        for j in range(draws):
            qi = rng.choices(order, weights=weights, k=1)[0]
            entry = workers[(i + j) % len(workers)]["addr"]
            t1 = time.perf_counter()
            try:
                peer, sticky, cols = _fleet_run_one(
                    entry, queries[qi], f"tenant{i % 2}")
                lat = time.perf_counter() - t1
                if cols is None:
                    with lock:
                        results.append((qi, "", None, lat, "rejected"))
                    continue
                ok = cols == refs[qi]
                with lock:
                    results.append((qi, peer, sticky, lat, ok))
            except Exception as e:  # noqa: BLE001 — reported in JSON
                with lock:
                    errors.append(f"stream{i} q{qi}: {e!r}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=stream, args=(i,),
                                name=f"bench-fleet-{i}")
               for i in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    makespan = time.perf_counter() - t0

    per_peer = {}
    mismatched, rejected, sticky_n = set(), 0, 0
    lats = []
    for qi, peer, sticky, lat, ok in results:
        if ok == "rejected":
            rejected += 1
            continue
        per_peer[peer] = per_peer.get(peer, 0) + 1
        lats.append(lat)
        sticky_n += 1 if sticky else 0
        if ok is not True:
            mismatched.add(qi)
    lats.sort()
    done = len(lats)
    out = {
        "queries_completed": done,
        "rejected": rejected,
        "makespan_s": round(makespan, 3),
        "queries_per_sec": round(done / max(makespan, 1e-9), 3),
        "p50_s": round(lats[done // 2], 4) if lats else None,
        "p99_s": round(lats[min(done - 1, int(0.99 * done))], 4)
        if lats else None,
        "sticky": sticky_n,
        "spilled": done - sticky_n,
        "per_peer_queries": per_peer,
        "mismatched": sorted(mismatched),
    }
    if errors:
        out["errors"] = errors[:10]
    return out


def _fleet_throughput(st, n_workers: int, n_streams: int,
                      plat: str) -> dict:
    """Fleet fabric acceptance pass (ISSUE 20): (a) single-worker
    baseline over the zipfian mix, (b) the same workload over N fresh
    workers with fingerprint-sticky routing — q/s speedup plus
    cross-peer cache-tier hits, (c) a cold worker joining the live
    fleet must reach steady-state latency within 5 queries (warm-state
    pull + peer fetches instead of recompiles). Every routed result is
    asserted equal to a locally computed reference."""
    import shutil

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.service.server import _json_value

    root = tempfile.mkdtemp(prefix="bench_fleet_")
    os.makedirs(os.path.join(root, "logs"))
    fleet_base = os.path.join(root, "fleets")
    os.makedirs(fleet_base)
    rows = 60_000 if _SMOKE else 400_000
    path = os.path.join(root, "t.parquet")
    pq.write_table(pa.table({
        "a": [i % 997 for i in range(rows)],
        "g": [i % 7 for i in range(rows)],
        "b": [float(i % 10_000) for i in range(rows)],
    }), path)
    queries = [
        "SELECT sum(b) AS s, count(1) AS n FROM t WHERE a > 13",
        "SELECT avg(b) AS m FROM t WHERE a > 101",
        "SELECT min(b) AS lo, max(b) AS hi FROM t WHERE a > 7",
        "SELECT g, sum(b) AS s FROM t GROUP BY g ORDER BY g",
        "SELECT g, count(1) AS n FROM t WHERE a > 251 "
        "GROUP BY g ORDER BY g",
        "SELECT sum(b) AS s FROM t WHERE a > 503",
        "SELECT g, avg(b) AS m, min(b) AS lo FROM t WHERE a > 37 "
        "GROUP BY g ORDER BY g",
        "SELECT count(1) AS n FROM t WHERE a > 701",
        "SELECT g, max(b) AS hi FROM t WHERE a > 149 "
        "GROUP BY g ORDER BY g",
        "SELECT sum(b) AS s, avg(b) AS m FROM t WHERE a > 317",
        "SELECT g, sum(b) AS s, count(1) AS n FROM t WHERE a > 431 "
        "GROUP BY g ORDER BY g",
        "SELECT min(b) AS lo FROM t WHERE a > 587",
    ]
    draws = int(os.environ.get("BENCH_FLEET_DRAWS",
                               "14" if _SMOKE else "30"))

    # local reference, serialized exactly the way the gateway fetch
    # verb serializes (same _json_value), so equality is byte-level on
    # the wire representation
    s_ref = st.TpuSession()
    s_ref.read.parquet(path).create_or_replace_temp_view("t")
    refs = {}
    for i, sql in enumerate(queries):
        tbl = s_ref.sql(sql).to_arrow()
        refs[i] = {name: [_json_value(v) for v in
                          tbl.column(j).to_pylist()]
                   for j, name in enumerate(tbl.column_names)}

    views = [("t", path)]
    confs = [
        "spark.rapids.tpu.sql.cache.enabled=true",
        # record served SQL so the warm-state payload a donor serves to
        # the cold joiner carries a replayable query list
        "spark.rapids.tpu.sql.service.warmPack.record="
        + os.path.join(root, "warm_record.json"),
        # small per-peer in-flight cap: hot queries spill off a
        # saturated owner, so the fabric's cross-peer cache tier (not
        # just sticky routing) carries load during the run
        "spark.rapids.tpu.sql.fleet.peerMaxInflight=1",
    ]
    out = {"workers": n_workers, "streams": n_streams, "draws": draws,
           "distinct_queries": len(queries), "rows": rows,
           "worker_platform": plat}
    _partial["extra"]["fleet"] = out
    workers = []
    try:
        # ---- (a) single-worker baseline (fresh process, own dir) ----
        base_ws = _fleet_spawn(1, os.path.join(fleet_base, "solo"),
                               views, confs, plat,
                               os.path.join(root, "logs"), "solo")
        try:
            base = _fleet_workload(base_ws, queries, refs,
                                   n_streams, draws, seed=4321)
        finally:
            for w in base_ws:
                _fleet_stop(w)
        out["single_worker"] = base

        # ---- (b) the fleet: N fresh workers, shared directory -------
        fleet_dir = os.path.join(fleet_base, "fabric")
        workers = _fleet_spawn(n_workers, fleet_dir, views, confs,
                               plat, os.path.join(root, "logs"), "w")
        flt = _fleet_workload(workers, queries, refs,
                              n_streams, draws, seed=4321)
        out["fleet"] = flt
        out["queries_per_sec"] = flt["queries_per_sec"]
        out["speedup_vs_single"] = round(
            flt["queries_per_sec"]
            / max(base["queries_per_sec"], 1e-9), 3)
        # the >=1.6x q/s target needs real process parallelism: with
        # fewer than 2 cores per worker the N interpreters serialize on
        # the same cores and the ratio is hardware-capped at ~1.0
        out["cores"] = os.cpu_count()
        out["speedup_target_met"] = (
            out["speedup_vs_single"] >= 1.6
            or (os.cpu_count() or 1) < 2 * n_workers)

        # per-peer fabric stats straight from each gateway
        peers = {}
        cross_hits = 0
        for w in workers:
            info = _fleet_rpc(w["addr"], {"op": "fleet"})
            if info.get("ok"):
                stats = info.get("stats", {})
                peers[w["peer_id"]] = {
                    k: stats.get(k) for k in
                    ("fleet_peer_hits", "fleet_peer_misses",
                     "fleet_publishes", "fleet_inv_broadcasts",
                     "fleet_export_entries", "fleet_export_bytes")}
                if "router" in info:
                    peers[w["peer_id"]]["router"] = info["router"]
                cross_hits += int(stats.get("fleet_peer_hits") or 0)
        out["per_peer"] = peers
        out["cross_peer_hits_fleet"] = cross_hits

        # ---- (c) cold joiner: warm pull + peer hits, not compiles ---
        cold = _fleet_spawn(1, fleet_dir, views, confs, plat,
                            os.path.join(root, "logs"), "cold")[0]
        try:
            out["cold_join_warm"] = cold["warm"]
            cold_lats = []
            # direct submit (no routing): the JOINER must execute, and
            # reach steady-state via peer fetches + pulled warm state
            # rather than recomputing/recompiling the fabric's keys
            for k in range(6):
                sql = queries[k % 3]
                t1 = time.perf_counter()
                cols = _fleet_exec(cold["addr"], sql)
                cold_lats.append(round(time.perf_counter() - t1, 4))
                if cols != refs[k % 3]:
                    out.setdefault("errors", []).append(
                        f"cold joiner diverged on draw {k}")
            cinfo = _fleet_rpc(cold["addr"], {"op": "fleet"})
            if cinfo.get("ok"):
                cs = cinfo.get("stats", {})
                out["cold_join_peer_hits"] = cs.get("fleet_peer_hits")
                out["cold_join_warm_pulls"] = cs.get("fleet_warm_pulls")
            fleet_p50 = flt.get("p50_s") or 0.01
            # within 5 queries the joiner must be serving at fabric
            # steady-state (peer fetch / cached), not recompiling
            out["cold_join_latencies_s"] = cold_lats
            out["cold_join_steady_by_5"] = (
                min(cold_lats[:5]) <= max(5.0 * fleet_p50, 0.5))
        finally:
            _fleet_stop(cold)

        out["mismatched"] = sorted(set(base["mismatched"])
                                   | set(flt["mismatched"]))
        errs = (base.get("errors", []) + flt.get("errors", [])
                + out.get("errors", []))
        if errs:
            out["errors"] = errs[:10]
        out["byte_identical"] = not out["mismatched"]
        out["cross_peer_hits"] = (cross_hits
                                  + int(out.get("cold_join_peer_hits")
                                        or 0))
        out["ok"] = (not out["mismatched"] and not errs
                     and flt["queries_completed"] > 0
                     and out["cross_peer_hits"] > 0
                     and bool(out["speedup_target_met"])
                     and bool(out["cold_join_steady_by_5"]))
    finally:
        for w in workers:
            _fleet_stop(w)
        shutil.rmtree(root, ignore_errors=True)
    return out


def _fleet_chaos(st) -> dict:
    """Chaos coverage for the peer.fetch fault point (ISSUE 20): two
    in-process fleet members over a real socket. (a) With every peer
    fetch failing, a requester must degrade to a byte-identical local
    recompute; (b) with the fault cleared the same key is a peer hit,
    byte-identical; (c) a delayed fetch still hits; (d) invalidation
    broadcasts under injected send failures must not compromise
    freshness — an external overwrite is caught by the snapshot-keyed
    lookup even when no broadcast was delivered."""
    import shutil

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import fleet
    from spark_rapids_tpu.fleet import context as fctx
    from spark_rapids_tpu.runtime import faults, result_cache

    root = tempfile.mkdtemp(prefix="bench_fleet_chaos_")
    out = {"skipped": False}
    p = os.path.join(root, "t.parquet")

    def write(version: int) -> None:
        pq.write_table(pa.table(
            {"a": list(range(256)),
             "b": [float(i * (version + 1)) for i in range(256)]}), p)

    write(0)
    s = st.TpuSession({
        "spark.rapids.tpu.sql.cache.enabled": "true",
        "spark.rapids.tpu.sql.fleet.directory":
            os.path.join(root, "dir"),
    })
    s.read.parquet(p).create_or_replace_temp_view("fleet_chaos_t")
    sql = ("SELECT sum(b) AS s, count(1) AS n FROM fleet_chaos_t "
           "WHERE a > 17")
    faults.clear_plan()
    a = fleet.join(s)
    b = fleet.FleetMember(s, s.conf, os.path.join(root, "dir"))
    try:
        with fctx.scoped(a):
            ref = s.sql(sql).to_arrow()

        # (a) every fetch fails: byte-identical local recompute
        # (clear_plan wipes the injection counters with the rules, so
        # accumulate them per leg)
        injected = 0
        result_cache.clear()
        faults.install_plan("peer.fetch:prob=1:raise=FetchFailed")
        with fctx.scoped(b):
            got_faulted = s.sql(sql).to_arrow()
        injected += faults.injection_counts().get("injected", 0)
        faults.clear_plan()
        out["degrade_parity"] = got_faulted.equals(ref)
        out["fetch_failures"] = b.stats["fleet_peer_fetch_failures"]

        # (b) fault cleared: same key is now a cross-peer hit
        result_cache.clear()
        b.export.clear()
        with fctx.scoped(b):
            got_hit = s.sql(sql).to_arrow()
        out["peer_hit_parity"] = got_hit.equals(ref)
        out["peer_hits"] = b.stats["fleet_peer_hits"]

        # (c) delayed fetch (retry path exercised) still hits
        result_cache.clear()
        faults.install_plan("peer.fetch:nth=1:delay=30")
        with fctx.scoped(b):
            got_slow = s.sql(sql).to_arrow()
        injected += faults.injection_counts().get("injected", 0)
        faults.clear_plan()
        out["delayed_hit_parity"] = got_slow.equals(ref)

        # (d) lost invalidation broadcast: arm send failures, overwrite
        # the table externally, broadcast (all sends fail), and require
        # the next read to reflect the NEW bytes via snapshot keys
        faults.install_plan("peer.fetch:prob=1:raise=FetchFailed")
        write(1)
        with fctx.scoped(b):
            result_cache.invalidate_prefix(root)
        injected += faults.injection_counts().get("injected", 0)
        faults.clear_plan()
        out["inv_broadcast_failures"] = \
            b.stats["fleet_inv_broadcast_failures"]
        with fctx.scoped(b):
            fresh = s.sql(sql).to_arrow()
        ref2 = None
        with fctx.scoped(a):
            result_cache.clear()
            ref2 = s.sql(sql).to_arrow()
        out["lost_broadcast_fresh"] = (not fresh.equals(ref)
                                       and fresh.equals(ref2))
        out["injected"] = injected
        out["ok"] = bool(
            out["degrade_parity"] and out["peer_hit_parity"]
            and out["delayed_hit_parity"] and out["lost_broadcast_fresh"]
            and out["fetch_failures"] >= 1 and out["peer_hits"] >= 1
            and out["inv_broadcast_failures"] >= 1
            and out["injected"] >= 2)
    finally:
        faults.clear_plan()
        b.leave()
        fleet.reset()
        result_cache.clear()
        shutil.rmtree(root, ignore_errors=True)
    return out


def _mesh_chaos(st, sf: float) -> dict:
    """Chaos coverage for the mesh.collective fault point: run the q6
    distributed shape through the fused SPMD-stage path, fault-free for
    a reference, then with the collective's first live launch failing —
    the stage must degrade to the round-based exchange (counted
    spmdDegraded) and still return byte-identical results. Skipped
    (ok=True) when the backend exposes fewer than 2 devices."""
    import jax

    from spark_rapids_tpu.runtime import faults
    from spark_rapids_tpu.workloads import spmd_bench, tpch

    n_dev = min(8, len(jax.devices()))
    if n_dev < 2:
        return {"skipped": True, "ok": True,
                "reason": f"{len(jax.devices())} device(s); mesh needs 2+"}
    s = st.TpuSession({
        "spark.rapids.tpu.mesh.devices": n_dev,
        "spark.rapids.tpu.sql.batchSizeRows": 2048,
        "spark.rapids.tpu.sql.resultCache.enabled": "false",
    })
    df = s.create_dataframe(tpch.gen_lineitem(sf=sf, seed=7)).cache()
    faults.clear_plan()
    ref_q = spmd_bench._q6_shape(df)
    ref = spmd_bench._canon(ref_q.to_arrow())
    stages = spmd_bench._metric_sum(ref_q, "spmdStages")

    faults.reset_recovery_stats()
    # prob=1/times=1 on the live (bg=0) path: the FIRST fused collective
    # launch fails, deterministically; prewarm hits are left alone
    faults.install_plan(
        "mesh.collective:prob=1.0:times=1:bg=0:raise=FetchFailed")
    try:
        q = spmd_bench._q6_shape(df)
        tbl = spmd_bench._canon(q.to_arrow())
        degraded = spmd_bench._metric_sum(q, "spmdDegraded")
    finally:
        counts = faults.injection_counts()
        faults.clear_plan()
    rec = faults.recovery_stats()
    df.uncache()
    out = {
        "skipped": False,
        "devices": n_dev,
        "spmd_stages_ref": stages,
        "injected": counts.get("injected", 0),
        "spmd_degraded": degraded,
        "degradations": rec.get("degradations", 0),
        "parity": tbl.equals(ref),
        "ok": bool(tbl.equals(ref) and stages > 0
                   and counts.get("injected", 0) >= 1 and degraded >= 1),
    }
    return out


def _chaos_soak(st, sf: float, seed: int, n_streams: int = 2,
                qids=(1, 3, 6, 12, 14), max_retries: int = 8) -> dict:
    """Fault-injection soak (ISSUE 14 acceptance): derive a randomized
    fault plan from `seed` alone, run N concurrent TPC-H streams through
    the SYNC path (so the service-level transparent retry, degradation,
    and OOM-retry recovery paths are all live), and require every result
    byte-identical to the fault-free serial reference, the strict-kind
    resource ledger balanced, and retries bounded. Same seed => same
    plan => same injection decisions for a fixed execution order."""
    import random
    import threading

    from spark_rapids_tpu.runtime import faults
    from spark_rapids_tpu.runtime import ledger as _ledger
    from spark_rapids_tpu.workloads import tpch

    s = st.TpuSession({
        # the cross-query result cache would serve the reference bytes
        # back verbatim and mask every downstream fault point
        "spark.rapids.tpu.sql.resultCache.enabled": "false",
        "spark.rapids.tpu.sql.service.maxQueryRetries":
            str(max_retries),
    })
    tabs = tpch.gen_all(sf=sf, seed=7)
    dfs = {k: s.create_dataframe(v).cache() for k, v in tabs.items()}
    reg = tpch.queries()
    qids = [q for q in qids if q in reg]

    # fault-free serial reference (also warms the program cache, so the
    # chaos pass measures recovery, not compiles)
    faults.clear_plan()
    serial = {qn: reg[qn](dfs).to_arrow() for qn in qids}

    # randomized-but-reproducible plan: every named point armed with a
    # seeded low-probability transient raise (kill/delay excluded: the
    # in-process soak must not kill the bench, and delays only stretch
    # the budget without exercising a recovery path)
    rng = random.Random(seed)
    raises = ["FetchFailed", "RESOURCE_EXHAUSTED", "ChaosError"]
    rules = []
    for point in sorted(faults.POINTS):
        prob = round(rng.uniform(0.05, 0.12), 3)
        rules.append(f"{point}:prob={prob}"
                     f":seed={rng.randrange(1 << 16)}"
                     f":raise={rng.choice(raises)}")
    plan = ";".join(rules)

    faults.reset_recovery_stats()
    faults.install_plan(plan)
    results, errors = [], []
    lock = threading.Lock()

    def stream(i: int):
        order = qids[:]
        random.Random(seed * 1000 + i).shuffle(order)
        for qn in order:
            try:
                tbl = reg[qn](dfs).to_arrow()
                with lock:
                    results.append((qn, tbl))
            except Exception as e:  # noqa: BLE001 — reported in JSON
                with lock:
                    errors.append(f"stream{i} q{qn}: {e!r}")

    try:
        threads = [threading.Thread(target=stream, args=(i,),
                                    name=f"chaos-stream-{i}")
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        # clear_plan() wipes the injection counters with the rules, so
        # snapshot them first
        counts = faults.injection_counts()
        faults.clear_plan()

    mismatched = sorted({qn for qn, tbl in results
                         if not tbl.equals(serial[qn])})
    rec = faults.recovery_stats()
    lg = _ledger.ledger()
    led = lg.report() if lg is not None else {"enabled": False,
                                             "balanceOk": True}
    from spark_rapids_tpu.runtime import lockdep as _lockdep
    lw = _lockdep.witness()
    lockrep = lw.report() if lw is not None else {"enabled": False,
                                                 "findings": 0}
    retries = rec.get("query_retries", 0)
    retry_budget = len(qids) * n_streams * max_retries

    # schedule-perturbation pass (ISSUE 18): seeded adversarial
    # interleavings — microsecond bytecode switch interval plus
    # RNG-chosen yields at instrumented shared-structure accesses —
    # with NO fault plan armed; byte-identity against the same serial
    # reference plus a balanced ledger and a collapse-free racedep
    # report prove the pools' sharing discipline rather than retry luck
    perturb = _schedule_perturbation(reg, dfs, serial, seed,
                                     n_streams, _ledger)
    for df in dfs.values():
        df.uncache()
    # focused mesh.collective pass: the randomized plan above arms the
    # point but the soak session runs mesh-less, so exercise the fused
    # SPMD stage -> round-based degradation path explicitly
    mesh = _mesh_chaos(st, min(sf, 0.02))
    # focused peer.fetch pass: the soak session runs fleet-less (cache
    # disabled, no dispatcher), so exercise the peer-cache degrade /
    # hit / lost-broadcast paths explicitly with in-process members
    try:
        fleet_c = _fleet_chaos(st)
    except Exception as e:  # noqa: BLE001 — reported in JSON
        fleet_c = {"ok": False, "error": repr(e)[:300]}
    out = {
        "seed": seed,
        "plan": plan,
        "streams": n_streams,
        "sf": sf,
        "wall_s": round(wall, 3),
        "queries_completed": len(results),
        "mismatched": mismatched,
        "injected": counts,
        "recovered": rec,
        "regenerations": rec.get("regenerations", 0),
        "query_retries": retries,
        "degradations": rec.get("degradations", 0),
        "retries_bounded": retries <= retry_budget,
        "ledger": led,
        "lockdep": lockrep,
        "mesh_collective": mesh,
        "fleet": fleet_c,
        "schedule_perturbation": perturb,
        "ok": (not mismatched and not errors
               and retries <= retry_budget
               and bool(led.get("balanceOk", True))
               and int(lockrep.get("findings", 0)) == 0
               and bool(mesh.get("ok", False))
               and bool(fleet_c.get("ok", False))
               and bool(perturb.get("ok", False))),
    }
    if errors:
        out["errors"] = errors[:10]
    return out


def _schedule_perturbation(reg, dfs, serial, seed: int, n_streams: int,
                           _ledger, qids=(3, 6)) -> dict:
    """Seeded adversarial-scheduling pass inside the chaos soak: arm
    racedep's perturbation mode (tiny `sys.setswitchinterval` + seeded
    yields at instrumented accesses), run the q3/q6 streams
    concurrently with NO faults, and require byte-identity against the
    serial reference, zero witnessed lockset collapses, and a balanced
    ledger under the hostile interleavings."""
    import random
    import threading

    from spark_rapids_tpu.runtime import racedep as _racedep

    pqids = [q for q in qids if q in serial]
    was_enabled = _racedep.enabled()
    rw = _racedep.witness() if was_enabled \
        else _racedep.enable(raise_on_race=False)
    base_findings = len(rw.findings)
    mismatched, errors = [], []
    lock = threading.Lock()

    def stream(i: int):
        order = pqids[:]
        random.Random(seed * 77 + i).shuffle(order)
        for qn in order:
            try:
                tbl = reg[qn](dfs).to_arrow()
                if not tbl.equals(serial[qn]):
                    with lock:
                        mismatched.append(qn)
            except Exception as e:  # noqa: BLE001 — reported in JSON
                with lock:
                    errors.append(f"perturb-stream{i} q{qn}: {e!r}")

    wall = 0.0
    _racedep.perturb(seed, yield_prob=0.2)
    try:
        threads = [threading.Thread(target=stream, args=(i,),
                                    name=f"chaos-perturb-{i}")
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        _racedep.restore()
    report = rw.report()
    new_findings = len(rw.findings) - base_findings
    if not was_enabled:
        _racedep.disable()
    lg = _ledger.ledger()
    led = lg.report() if lg is not None else {"enabled": False,
                                              "balanceOk": True}
    out = {
        "seed": seed,
        "qids": pqids,
        "streams": n_streams,
        "wall_s": round(wall, 3),
        "mismatched": sorted(set(mismatched)),
        "racedep": report,
        "race_findings": new_findings,
        "ledger_ok": bool(led.get("balanceOk", True)),
        "ok": (not mismatched and not errors and new_findings == 0
               and bool(led.get("balanceOk", True))),
    }
    if errors:
        out["errors"] = errors[:10]
    return out


def _telemetry_snapshot() -> dict:
    """Compact live-telemetry extract for the bench artifact: latency /
    queue-wait histogram summaries (p50/p95/p99 from the log-bucket
    registry), pool-saturation gauges, service counters, and the mean
    critical-path share per category across every traced query in this
    process — the numbers the regression gate compares across rounds."""
    from spark_rapids_tpu.profiler import telemetry
    snap = telemetry.snapshot()
    hists = snap.get("histograms") or {}
    shares = {}
    pfx = "critical_path_share_pct_"
    for hname, s2 in hists.items():
        if hname.startswith(pfx) and s2.get("count"):
            shares[hname[len(pfx):]] = round(s2["sum"] / s2["count"], 2)
    gauges = snap.get("gauges") or {}
    return {
        "histograms": {k: v for k, v in hists.items()
                       if not k.startswith(pfx)},
        "critical_path_shares": shares,
        "pool": {k: v for k, v in gauges.items()
                 if k.startswith(("compile_pool_", "service_"))},
        "counters": snap.get("counters") or {},
    }


def _concurrent_throughput(s, sf: float, n_streams: int,
                           qids=None) -> dict:
    """TPC-H throughput mode: N client streams each run a shuffled
    permutation of the 22 queries (or the `qids` subset) through the
    session's QueryManager (DataFrame.submit -> fair scheduler ->
    admission -> semaphore). Returns makespan, p50/p99 stream-query
    latency, queue-wait share, service counters, and asserts (a) every
    concurrent result is byte-identical to the serial reference and
    (b) a forced mid-stream cancel leaks nothing."""
    import random
    import threading

    from spark_rapids_tpu.memory.diagnostics import leak_report
    from spark_rapids_tpu.workloads import tpch

    tabs = tpch.gen_all(sf=sf, seed=7)
    dfs = {k: s.create_dataframe(v).cache() for k, v in tabs.items()}
    reg = tpch.queries()
    qids = sorted(reg) if qids is None else [q for q in qids if q in reg]

    # serial reference: one pass, results kept for the identity assert
    serial = {}
    t0 = time.perf_counter()
    for qn in qids:
        serial[qn] = reg[qn](dfs).to_arrow()
    serial_s = time.perf_counter() - t0

    mgr = s.query_manager()
    base_stats = dict(mgr.stats)
    lk0 = leak_report()

    results = []        # (qn, table, latency_s, queue_wait_ms)
    errors = []
    lock = threading.Lock()

    def stream(i: int):
        order = qids[:]
        random.Random(1234 + i).shuffle(order)
        for qn in order:
            t1 = time.perf_counter()
            try:
                h = reg[qn](dfs).submit()
                tbl = h.result()
                lat = time.perf_counter() - t1
                with lock:
                    results.append((qn, tbl, lat, h.queue_wait_ms))
            except Exception as e:  # noqa: BLE001 — reported in JSON
                with lock:
                    errors.append(f"stream{i} q{qn}: {e!r}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=stream, args=(i,),
                                name=f"bench-stream-{i}")
               for i in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    makespan = time.perf_counter() - t0

    mismatched = sorted({qn for qn, tbl, _, _ in results
                         if not tbl.equals(serial[qn])})
    assert not mismatched, (
        f"concurrent results diverge from serial reference for "
        f"queries {mismatched}")

    # forced mid-stream cancel: submit one more query, cancel at once,
    # and require the resource picture back at the pre-submit baseline
    h = reg[9](dfs).submit()
    h.cancel("bench forced mid-stream cancel")
    try:
        h.result(timeout=120)
    except Exception:  # noqa: BLE001 — cancelled or finished-first: both fine
        pass
    lk1 = leak_report()
    assert lk1["openHandles"] == lk0["openHandles"] \
        and lk1["deviceReservedBytes"] == lk0["deviceReservedBytes"], (
        f"resource leak after forced cancel: {lk0} -> {lk1}")

    lats = sorted(r[2] for r in results)
    stats = mgr.stats
    out = {
        "streams": n_streams,
        "sf": sf,
        "queries_completed": len(results),
        "makespan_s": round(makespan, 3),
        "serial_reference_s": round(serial_s, 3),
        # back-to-back serial time for the same N-stream workload,
        # divided by the concurrent makespan = throughput speedup
        "throughput_vs_serial": round(serial_s * n_streams
                                      / max(makespan, 1e-9), 3),
        "queries_per_sec": round(len(results) / max(makespan, 1e-9), 3),
        "p50_s": round(lats[len(lats) // 2], 4) if lats else None,
        "p99_s": round(lats[min(len(lats) - 1,
                                int(0.99 * len(lats)))], 4)
        if lats else None,
        "queue_wait_share": round(
            (sum(r[3] for r in results) / 1e3)
            / max(sum(lats), 1e-9), 4) if lats else None,
        "service": {
            "admitted": stats["admitted"] - base_stats["admitted"],
            "queued_peak": stats["queued_peak"],
            "cancelled": stats["cancelled"] - base_stats["cancelled"],
        },
    }
    if errors:
        out["errors"] = errors[:10]
    for df in dfs.values():
        df.uncache()
    return out


def _zipfian_throughput(st, sf: float, n_streams: int,
                        draws: int = 0, qids=None) -> dict:
    """Repeat-heavy throughput (the result-cache headline mode): N client
    streams draw from a zipfian distribution over the TPC-H mix — most
    draws repeat the few hot queries — through a cache-ENABLED session,
    while a writer thread overwrites a side parquet table mid-run.
    Asserts (a) every served result is byte-identical to that query's
    first fresh execution, (b) side-table reads never serve a stale sum
    (post-write lookups miss, then return the new data). The speedup
    baseline is the uncached equivalent: the sum over completed draws of
    each query's measured fresh serial time."""
    import random
    import shutil
    import tempfile
    import threading

    import pyarrow as pa

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.runtime import result_cache
    from spark_rapids_tpu.workloads import tpch

    s = st.TpuSession({"spark.rapids.tpu.sql.cache.enabled": True})
    result_cache.clear()
    rc0 = result_cache.stats()

    tabs = tpch.gen_all(sf=sf, seed=7)
    dfs = {k: s.create_dataframe(v).cache() for k, v in tabs.items()}
    reg = tpch.queries()
    qids = sorted(reg) if qids is None else [q for q in qids if q in reg]
    draws = draws or (24 if _SMOKE else 40)

    # zipf ranks: a fixed shuffle decides which queries are "hot";
    # P(rank k) ~ 1/k^1.2, so a handful of queries dominate the draws
    order = qids[:]
    random.Random(99).shuffle(order)
    weights = [1.0 / (k + 1) ** 1.2 for k in range(len(order))]

    # serial fresh pass: one execution per distinct query. It is at once
    # the byte-identity reference, the cache warmer, and the per-query
    # fresh-cost sample for the uncached-equivalent baseline.
    serial = {}
    fresh_s = {}
    t0 = time.perf_counter()
    for qn in qids:
        t1 = time.perf_counter()
        serial[qn] = reg[qn](dfs).to_arrow()
        fresh_s[qn] = time.perf_counter() - t1
    serial_pass_s = time.perf_counter() - t0

    # side table on disk: overwritten by the writer thread; readers must
    # never see a sum that was not the latest committed version
    side_dir = tempfile.mkdtemp(prefix="bench_rc_side_")
    side_path = os.path.join(side_dir, "side")

    def write_side(version: int) -> float:
        vals = [float(version * 100 + i) for i in range(64)]
        s.create_dataframe(pa.table({"v": vals})).write_parquet(
            side_path, mode="overwrite")
        return float(sum(vals))

    def side_query():
        return s.read.parquet(side_path).agg(
            total=F.sum("v")).to_arrow().column("total").to_pylist()[0]

    commit_lock = threading.Lock()   # serializes writes vs side reads
    committed = [write_side(0)]
    side_query()   # populate the whole-query tier for the side table

    # fragment-tier side workload (BENCH_r06 follow-up: the TPC-H
    # streams above are served from the whole-query tier — they never
    # replan, so substitute_fragments never runs for them, and the
    # single-partition side_query has no exchange; `fragment_hits: 0`
    # was structural, not a keying bug). This pair forces the workflow
    # the fragment tier exists for: a distributed shuffle join where
    # the writer invalidates ONE side and the re-planned re-run must
    # reuse the surviving side's exchange map output. A dedicated
    # session supplies the shuffle-forcing confs (the result cache is
    # process-global, so both sessions share one fragment table).
    s_frag = st.TpuSession({
        "spark.rapids.tpu.sql.cache.enabled": True,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
        "spark.rapids.tpu.sql.batchSizeRows": 64,
        "spark.rapids.tpu.sql.shuffle.partitions": 2})
    import pyarrow.parquet as _pq_mod
    stable_dir = os.path.join(side_dir, "frag_stable")
    hot_dir = os.path.join(side_dir, "frag_hot")
    os.makedirs(stable_dir), os.makedirs(hot_dir)
    for i in range(3):   # multi-file: keeps >1 scan partition => real
        _pq_mod.write_table(pa.table(   # exchanges on both join sides
            {"a": [(j + i * 50) % 7 for j in range(50)],
             "b": [float(j + i) for j in range(50)]}),
            os.path.join(stable_dir, f"p{i}.parquet"))

    def write_hot(version: int) -> None:
        _pq_mod.write_table(pa.table(
            {"a": [(j + version) % 7 for j in range(50)],
             "c": [float(j * 2 + version) for j in range(50)]}),
            os.path.join(hot_dir, "p0.parquet"))
        for i in (1, 2):
            if not os.path.exists(os.path.join(hot_dir,
                                               f"p{i}.parquet")):
                _pq_mod.write_table(pa.table(
                    {"a": [(j + i * 50) % 7 for j in range(50)],
                     "c": [float(j * 2) for j in range(50)]}),
                    os.path.join(hot_dir, f"p{i}.parquet"))

    def side_join():
        l = s_frag.read.parquet(stable_dir)
        r = s_frag.read.parquet(hot_dir)
        return l.join(r, on="a").agg(
            n=F.count(F.lit(1)), sb=F.sum("b")).to_arrow()

    write_hot(0)
    side_join()   # stores both sides' exchange fragments

    results = []   # (qn, table, latency_s)
    errors = []
    side_reads = 0
    lock = threading.Lock()
    stop = threading.Event()
    n_writes = 3 if _SMOKE else 6

    def writer():
        for v in range(1, n_writes + 1):
            if stop.wait(0.4):
                break
            with commit_lock:
                committed.append(write_side(v))
                write_hot(v)   # invalidates the hot join side only

    def stream(i: int):
        nonlocal side_reads
        rng = random.Random(4321 + i)
        for j in range(draws):
            qn = rng.choices(order, weights=weights, k=1)[0]
            t1 = time.perf_counter()
            try:
                tbl = reg[qn](dfs).to_arrow()
                lat = time.perf_counter() - t1
                with lock:
                    results.append((qn, tbl, lat))
                if j % 5 == 2:
                    # under commit_lock no write can interleave, so the
                    # read MUST serve exactly the latest committed sum —
                    # a stale cache entry is a hard failure
                    with commit_lock:
                        got = side_query()
                        want = committed[-1]
                    with lock:
                        side_reads += 1
                        if got != want:
                            errors.append(f"stream{i}: stale side read "
                                          f"{got} != {want}")
                if j % 7 == 3:
                    # fragment-tier traffic: re-planned shuffle join
                    # whose stable side must come from the cache; under
                    # commit_lock so the hot-side writer cannot change
                    # files mid-scan (SnapshotMismatch is the engine's
                    # correct answer to that torn read, not a cache bug)
                    with commit_lock:
                        side_join()
            except Exception as e:  # noqa: BLE001 — reported in JSON
                with lock:
                    errors.append(f"stream{i} q{qn}: {e!r}")

    t0 = time.perf_counter()
    wt = threading.Thread(target=writer, name="bench-rc-writer")
    threads = [threading.Thread(target=stream, args=(i,),
                                name=f"bench-zipf-{i}")
               for i in range(n_streams)]
    wt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    wt.join()
    makespan = time.perf_counter() - t0

    # quiesced miss-then-correct: one final overwrite, then the very
    # next read must return the new sum (and count an invalidation)
    inv_before = result_cache.stats()["result_cache_invalidations"]
    committed.append(write_side(n_writes + 1))
    final = side_query()
    assert final == committed[-1], (
        f"stale post-write read: {final} != {committed[-1]}")
    invalidation_ok = (final == committed[-1]
                       and result_cache.stats()
                       ["result_cache_invalidations"] > inv_before)

    # quiesced fragment check: one more invalidating write on the hot
    # join side, then the re-planned join MUST reuse the stable side's
    # exchange fragment (and agree with a cache-free execution)
    fh0 = result_cache.stats()["result_cache_fragment_hits"]
    write_hot(n_writes + 7)
    frag_tbl = side_join()
    frag_hits_after_write = (result_cache.stats()
                             ["result_cache_fragment_hits"] - fh0)
    assert frag_hits_after_write >= 1, (
        "stable-side exchange fragment must hit after the hot-side "
        "write invalidated its sibling")
    s_nocache = st.TpuSession({
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
        "spark.rapids.tpu.sql.batchSizeRows": 64,
        "spark.rapids.tpu.sql.shuffle.partitions": 2})
    frag_fresh = s_nocache.read.parquet(stable_dir).join(
        s_nocache.read.parquet(hot_dir), on="a").agg(
        n=F.count(F.lit(1)), sb=F.sum("b")).to_arrow()
    assert frag_tbl.equals(frag_fresh), (
        "fragment-served join diverges from cache-free execution")

    mismatched = sorted({qn for qn, tbl, _ in results
                         if not tbl.equals(serial[qn])})
    assert not mismatched, (
        f"cached results diverge from the fresh reference for "
        f"queries {mismatched}")
    assert not errors, errors[:5]

    rc1 = result_cache.stats()
    hits = rc1["result_cache_hits"] - rc0["result_cache_hits"]
    misses = rc1["result_cache_misses"] - rc0["result_cache_misses"]
    uncached_equiv = sum(fresh_s[qn] for qn, _, _ in results)
    lats = sorted(r[2] for r in results)
    out = {
        "streams": n_streams,
        "sf": sf,
        "draws_per_stream": draws,
        "distinct_queries": len(qids),
        "queries_completed": len(results),
        "makespan_s": round(makespan, 3),
        "serial_fresh_pass_s": round(serial_pass_s, 3),
        "uncached_equivalent_s": round(uncached_equiv, 3),
        "speedup_vs_uncached": round(
            uncached_equiv / max(makespan, 1e-9), 2),
        "queries_per_sec": round(len(results) / max(makespan, 1e-9), 3),
        "p50_s": round(lats[len(lats) // 2], 4) if lats else None,
        "p99_s": round(lats[min(len(lats) - 1,
                                int(0.99 * len(lats)))], 4)
        if lats else None,
        "hit_rate": round(hits / max(hits + misses, 1), 4),
        "cache": {
            "hits": int(hits),
            "misses": int(misses),
            "fragment_hits": int(rc1["result_cache_fragment_hits"]
                                 - rc0["result_cache_fragment_hits"]),
            "stores": int(rc1["result_cache_stores"]
                          - rc0["result_cache_stores"]),
            "evictions": int(rc1["result_cache_evictions"]
                             - rc0["result_cache_evictions"]),
            "invalidation_events": int(
                rc1["result_cache_invalidations"]
                - rc0["result_cache_invalidations"]),
            "entries": int(rc1["result_cache_entries"]),
            "bytes": int(rc1["result_cache_bytes"]),
        },
        "side_writes": len(committed),
        "side_reads": side_reads,
        "invalidation_ok": invalidation_ok,
        "fragment_hits_after_side_write": int(frag_hits_after_write),
        "byte_identical": True,
    }
    for df in dfs.values():
        df.uncache()
    result_cache.clear()
    shutil.rmtree(side_dir, ignore_errors=True)
    return out


def _exchange_smoke(sf: float) -> dict:
    """Exchange-pipeline smoke surface (ISSUE 9 acceptance): (a) a
    duplicate-exchange query (shuffled self-join) executes its map
    phase once per DISTINCT subtree — `exchangeReuseHits >= 1`, the
    map-side execution counter is equal across serial-map and
    parallel-map runs with reuse on, and strictly below the reuse-off
    counter; (b) fresh q4 wall-clock with the parallel map side vs the
    serial-map baseline on this machine; (c) byte-identical results
    across the serial / parallel / reused paths for every TPC-H query
    the remaining budget covers."""
    import spark_rapids_tpu as st
    from spark_rapids_tpu.exec.exchange import map_partitions_executed
    from spark_rapids_tpu.workloads import tpch

    def mk(threads, reuse):
        return st.TpuSession({
            "spark.rapids.tpu.sql.shuffle.partitions": 4,
            "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
            "spark.rapids.tpu.sql.exec.exchange.mapThreads": threads,
            "spark.rapids.tpu.sql.exec.exchange.reuse.enabled": reuse})

    out = {}

    # ---- (a) duplicate-exchange dedup (deterministic: hard asserts) ----
    def dup_run(threads, reuse):
        s2 = mk(threads, reuse)
        df = s2.create_dataframe({"k": list(range(64)) * 8,
                                  "v": list(range(512))})
        m0 = map_partitions_executed()
        j = df.join(df, on="k")
        rows = sorted(map(tuple, j.collect()))
        hits = sum(int(m.get("exchangeReuseHits", 0))
                   for m in j.last_metrics().values())
        return rows, map_partitions_executed() - m0, hits

    rows_ser, maps_ser, hits_ser = dup_run(1, True)
    rows_par, maps_par, hits_par = dup_run(4, True)
    rows_off, maps_off, _ = dup_run(4, False)
    assert hits_par >= 1, "exchange reuse did not fire on self-join"
    assert maps_ser == maps_par, \
        "parallel map changed the map-side execution counter"
    assert maps_par < maps_off, \
        "reuse did not elide the duplicate map phase"
    assert rows_ser == rows_par == rows_off, \
        "self-join rows differ across serial/parallel/reuse paths"
    out["reuse_hits"] = hits_par
    out["dup_map_execs_reused"] = maps_par
    out["dup_map_execs_no_reuse"] = maps_off

    reg = tpch.queries()
    tabs = tpch.gen_all(sf=sf, seed=7)

    # ---- (b) fresh q4: parallel map vs serial-map baseline -------------
    try:
        def q4_time(threads):
            s2 = mk(threads, True)
            dfs = {k: s2.create_dataframe(v).cache()
                   for k, v in tabs.items()}
            reg[4](dfs).to_arrow()          # warm the program cache
            t = _best_fresh(lambda: reg[4](dfs), 2)
            for df in dfs.values():
                df.uncache()
            return t

        with _alarm(min(120.0, max(5.0, _remaining() - 90.0)),
                    "exchange q4 speedup"):
            ser_t = q4_time(1)
            par_t = q4_time(0)              # 0 = auto min(4, cores)
        out["q4_serial_map_s"] = round(ser_t, 4)
        out["q4_parallel_map_s"] = round(par_t, 4)
        out["q4_map_speedup"] = round(ser_t / par_t, 3)
        out["q4_speedup_pass"] = (ser_t / par_t) >= 1.3
        if not out["q4_speedup_pass"]:
            print(f"bench: exchange q4 map speedup "
                  f"{ser_t / par_t:.2f}x < 1.3x target",
                  file=sys.stderr)
    except _BenchTimeout as e:
        out["q4_speedup_error"] = f"timeout: {e}"
    except Exception as e:  # advisory: keep the dedup evidence
        out["q4_speedup_error"] = repr(e)[:300]

    # ---- (c) serial / parallel / reused parity over the suite ----------
    try:
        sessions = [mk(1, False), mk(4, False), mk(4, True)]
        all_dfs = [{k: s2.create_dataframe(v).cache()
                    for k, v in tabs.items()} for s2 in sessions]
        verified, identical, mismatches = 0, 0, []
        for qn in sorted(reg):
            left = _remaining() - 45.0      # flush + concurrent tail
            if left <= 2.0:
                out["parity_note"] = \
                    f"budget exhausted after q{qn - 1}"
                break
            try:
                with _alarm(min(_QUERY_BUDGET_S, left),
                            f"exchange parity q{qn}"):
                    ref = reg[qn](all_dfs[0]).to_arrow()
                    same = all(reg[qn](d).to_arrow().equals(ref)
                               for d in all_dfs[1:])
                verified += 1
                identical += bool(same)
                if not same:
                    mismatches.append(qn)
            except _BenchTimeout:
                out.setdefault("parity_timeouts", []).append(qn)
        out["parity_verified"] = verified
        out["parity_identical"] = identical
        if mismatches:
            out["parity_mismatches"] = mismatches
        assert not mismatches, \
            f"exchange paths disagree on queries {mismatches}"
        for dfs in all_dfs:
            for df in dfs.values():
                df.uncache()
    except Exception as e:  # advisory beyond the mismatch assert
        out.setdefault("parity_error", repr(e)[:300])
        if "disagree" in str(e):
            raise
    return out


def _scan_profile(st, sf: float) -> dict:
    """Write the SF`sf` TPC-H tables as SNAPPY parquet (the bench
    dataset layout: decimals stored as integers so they take INT32/
    INT64 physical types), then report

      - device-decode eligibility: fraction of column chunks and of
        column-chunk BYTES the device path can decode, plus fallback
        bytes by reason (codec/type/encoding/nested),
      - the scan/decompress/upload/prefetch-wait time split of a
        device-decoded q6-shaped scan over lineitem, vs the host path,
      - result parity between the two paths (byte-identical collect).
    """
    import shutil
    import tempfile

    import pyarrow.parquet as pq_mod
    from spark_rapids_tpu.io.parquet_device import (eligible_chunks,
                                                    fallback_reasons)
    from spark_rapids_tpu.workloads import tpch

    d = tempfile.mkdtemp(prefix="srtpu-scanprof-")
    out = {"sf": sf, "compression": "snappy"}
    try:
        tabs = tpch.gen_all(sf=sf, seed=7)
        paths = {}
        for name, t in tabs.items():
            p = os.path.join(d, f"{name}.parquet")
            try:
                pq_mod.write_table(t, p, compression="snappy",
                                   store_decimal_as_integer=True)
            except TypeError:  # older pyarrow: FLBA decimals fall back
                pq_mod.write_table(t, p, compression="snappy")
            paths[name] = p

        elig_cols = total_cols = 0
        elig_bytes = total_bytes = 0
        reason_bytes = {}
        per_table = {}
        for name, p in paths.items():
            pf = pq_mod.ParquetFile(p)
            md = pf.metadata
            cols = list(pf.schema_arrow.names)
            tb = eb = 0
            for rg in range(md.num_row_groups):
                elig = eligible_chunks(pf, rg, cols)
                reasons = fallback_reasons(pf, rg, cols)
                name_of = {}
                for ci in range(md.num_columns):
                    col = md.row_group(rg).column(ci)
                    name_of[ci] = ".".join(
                        col.path_in_schema.split("."))
                for ci in range(md.num_columns):
                    col = md.row_group(rg).column(ci)
                    b = col.total_compressed_size
                    total_cols += 1
                    tb += b
                    if name_of[ci] in elig:
                        elig_cols += 1
                        eb += b
                    else:
                        cat = reasons.get(name_of[ci],
                                          ("other", ""))[0]
                        reason_bytes[cat] = reason_bytes.get(cat, 0) + b
            total_bytes += tb
            elig_bytes += eb
            per_table[name] = round(eb / tb, 4) if tb else None
        out.update({
            "eligible_column_chunk_frac":
                round(elig_cols / total_cols, 4) if total_cols else None,
            "eligible_byte_frac":
                round(elig_bytes / total_bytes, 4) if total_bytes
                else None,
            "fallback_bytes_by_reason": reason_bytes,
            "per_table_eligible_byte_frac": per_table,
        })

        # q6-shaped scan over parquet lineitem: device path vs host path
        def run(device: bool):
            conf = {"spark.rapids.tpu.sql.batchSizeRows": 1 << 22,
                    "spark.rapids.tpu.sql.format.parquet."
                    "deviceDecode.enabled": device}
            s2 = st.TpuSession(conf)
            q = tpch.q6(s2.read.parquet(paths["lineitem"]))
            q.to_arrow()      # warm: XLA compiles must not land in the
            t0 = time.perf_counter()   # timers of the measured run
            res = q.to_arrow()
            return res, time.perf_counter() - t0, q.last_metrics()

        dev_res, dev_s, dev_m = run(True)
        host_res, host_s, _ = run(False)
        out["device_matches_host"] = dev_res.equals(host_res)
        scan = {}
        for _op, ms in dev_m.items():
            if "deviceDecodedChunks" in ms or "scanTime" in ms:
                for k in ("scanTime", "decompressBusySecs",
                          "uploadSecs", "prefetchWaitSecs",
                          "deviceDecodedChunks", "deviceDecodeBytes",
                          "stagingPoolHits", "stagingPoolMisses"):
                    if k in ms:
                        scan[k] = scan.get(k, 0) + ms[k]
        out["q6_scan"] = {
            "device_wall_s": round(dev_s, 3),
            "host_wall_s": round(host_s, 3),
            "scan_s": round(scan.get("scanTime", 0), 4),
            "decompress_s": round(scan.get("decompressBusySecs", 0), 4),
            "upload_s": round(scan.get("uploadSecs", 0), 4),
            "prefetch_wait_s": round(scan.get("prefetchWaitSecs", 0),
                                     4),
            "device_decoded_chunks":
                int(scan.get("deviceDecodedChunks", 0)),
            "staging_pool_hits": int(scan.get("stagingPoolHits", 0)),
            # the off-thread proof: the compute side waited less than
            # the decode work took
            "prefetch_wait_lt_decode":
                scan.get("prefetchWaitSecs", 0)
                < (scan.get("scanTime", 0)
                   + scan.get("decompressBusySecs", 0)),
        }
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _regression_gate(current: dict, sfs: dict,
                     xla_per_query: dict = None, telemetry: dict = None):
    """Compare engine-time metrics against the newest BENCH_r*.json that
    is not an old CPU-fallback record. Returns a list of
    human-readable regression strings for slips >15%, plus per-query
    XLA compile-count growth >1.5x (plan-shape churn shows up as
    recompiles long before it shows up in wall time at small SF), plus
    critical-path share growth >1.5x for the queue/spill categories
    (a scheduling or memory regression shows up as where the wall clock
    goes before it moves the totals)."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []  # (round_number, path) — advisory gate: never crash bench
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m0 = re.fullmatch(r"BENCH_r(\d+)\.json", os.path.basename(path))
        if m0:
            rounds.append((int(m0.group(1)), path))
    prev = None
    for _, path in sorted(rounds, reverse=True):
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
        except Exception:
            continue
        if "backend_fallback" in parsed:
            continue  # an old CPU-fallback record: never a baseline
        if (parsed.get("extra") or {}).get("methodology") != "fresh":
            # pre-fresh-methodology artifact: its numbers timed resident
            # same-object replays, which this bench no longer reports as
            # headline — comparing would misread the methodology change
            # as a perf regression
            continue
        prev = (os.path.basename(path), parsed)
        break
    if prev is None:
        return []
    name, parsed = prev
    extra = parsed.get("extra") or {}
    metric = parsed.get("metric", "")
    m = re.search(r"sf([\d.]+)", metric)
    prev_sfs = {"q6_sf": float(m.group(1)) if m else None,
                "q1_sf": extra.get("q1_sf"), "q3_sf": extra.get("q3_sf"),
                "tpch_sf": extra.get("tpch_all22_sf")}
    prev_vals = {
        "q6_rows_per_sec": parsed.get("value"),
        "q1_rows_per_sec": extra.get("q1_rows_per_sec"),
        "q3_s": extra.get("q3_s"),
        "q6_cold_s": extra.get("q6_cold_s"),
        "tpch_all22_geomean_s": extra.get("tpch_all22_geomean_s"),
    }
    sf_key_of = {"q6_rows_per_sec": "q6_sf", "q1_rows_per_sec": "q1_sf",
                 "q3_s": "q3_sf", "q6_cold_s": "q6_sf",
                 "tpch_all22_geomean_s": "tpch_sf"}
    out = []
    for k, cur in current.items():
        old = prev_vals.get(k)
        if not old or not cur:
            continue
        sf_key = sf_key_of.get(k, k.split("_")[0] + "_sf")
        if prev_sfs.get(sf_key) != sfs.get(sf_key):
            continue  # different scale factor: not comparable
        # q3_s is time (lower better); rows/s higher better
        ratio = (old / cur) if k.endswith("_s") else (cur / old)
        if ratio < 0.85:
            out.append(f"{k}: {cur:.4g} vs {old:.4g} in {name} "
                       f"({ratio:.2f}x)")
    # per-query XLA compile counts: only comparable at the same sweep SF,
    # and only above a noise floor (tiny plans recompile for benign
    # reasons like a first-touch dtype specialization)
    if xla_per_query and prev_sfs.get("tpch_sf") == sfs.get("tpch_sf"):
        old_xla = extra.get("tpch_xla_per_query") or {}
        for q in sorted(xla_per_query):
            cur_rec = xla_per_query.get(q)
            old_rec = old_xla.get(q)
            if not isinstance(cur_rec, dict) or not isinstance(old_rec,
                                                               dict):
                continue
            cc = int(cur_rec.get("compiles") or 0)
            oc = int(old_rec.get("compiles") or 0)
            if oc > 0 and cc >= 8 and cc > 1.5 * oc:
                out.append(f"{q}: xla compiles {cc} vs {oc} in {name} "
                           f"({cc / oc:.2f}x growth)")
    # critical-path share drift: queue-wait / spill-wait growing >1.5x
    # vs the prior artifact means queries newly stalled on admission or
    # memory pressure; floor at 5% so jitter on near-zero shares never
    # warns
    cur_sh = (telemetry or {}).get("critical_path_shares") or {}
    old_sh = ((extra.get("telemetry") or {})
              .get("critical_path_shares") or {})
    for cat in ("queue", "spill"):
        cur, old = cur_sh.get(cat), old_sh.get(cat)
        if cur and old and cur >= 5.0 and cur > 1.5 * old:
            out.append(f"critical-path {cat} share: {cur:.1f}% vs "
                       f"{old:.1f}% in {name} ({cur / old:.2f}x growth)")
    return out


if __name__ == "__main__":
    main()
