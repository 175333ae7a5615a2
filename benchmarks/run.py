#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

  python3 benchmarks/run.py --workload tpch_sf1_hbm.q6 --seed 7 --seconds 30 --trace 0
  JAX_PLATFORMS=cpu python3 benchmarks/run.py --workload tpch_sf1_hbm.q6 \
      --seed 7 --seconds 2 --trace 0 --rehearse --sf 0.01    (off the chip)

Set-up (generate the cell's tables from --seed, load them, the first answer,
the warm executions), then a closed loop of one client for --seconds, then
the plain references and the comparison. Every line but the last is a JSON
object of facts about a phase; the LAST line of standard output is the
result. benchmarks/README.md says what each line means.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import datagen  # noqa: E402
from benchmarks.harness import (bytecount, compare, engine,  # noqa: E402
                                manifest, tracereduce)
from benchmarks.harness.peaks import peaks  # noqa: E402

MAX_RAISED = 10         # executions that may raise before the window ends
TRACE_SECONDS = 5.0     # --trace 1: whole executions until this much is traced
NO_CHIP = 3             # exit code: no accelerator, or fewer than asked
NO_PROGRAM = 4          # exit code: the checkout holds no program to measure


def say(**facts):
    print(json.dumps(facts), flush=True)


def counters_since(system, before):
    now = system.counters()
    return {k: now[k] - before[k] for k in now}


def closed_loop(system, n_queries, seconds, trace_dir):
    """One client, the next execution when the last has returned its table;
    execution k runs the mix's query k modulo its length. Starts executions
    until `seconds` have passed, runs the one in flight to its end and takes
    the elapsed time to that end. With a `trace_dir` the first whole
    executions, up to TRACE_SECONDS, run under the profiler."""
    latencies, answers, raised = [], [], []
    tracing = False
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # TraceAnnotations stay; less drag
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing = True
    before = system.counters()
    t0 = time.perf_counter()
    while True:
        t1 = time.perf_counter()
        try:
            answers.append(system.execute(len(answers) % n_queries))
        except Exception as e:  # counted as failed; the window goes on
            answers.append(None)
            raised.append(repr(e))
        t2 = time.perf_counter()
        latencies.append(t2 - t1)
        if tracing and t2 - t0 >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing = False
            t2 = time.perf_counter()
        if t2 - t0 >= seconds or len(raised) > MAX_RAISED:
            break
    if tracing:
        jax.profiler.stop_trace()
    return {"elapsed_s": t2 - t0, "latencies": latencies, "answers": answers,
            "raised": raised, "attempted": len(answers),
            "completed": len(answers) - len(raised),
            "counters": counters_since(system, before)}


def read_metric(name, run):
    """A metric's reader is benchmarks/metrics/<name>.py."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def judge(tables, queries, window, warm, off_device):
    """Every number that decides `correct`, each beside its limit (all exact,
    so 0). The plain references run here: after the window has closed, the
    memory peak has been read and the program's state is freed. Answer k is
    query k modulo the mix's length, in the warm-up and in the window."""
    t0 = time.perf_counter()
    wanted = [importlib.import_module(
        "benchmarks.reference." + q["reference"]).reference(tables)
        for q in queries]
    seen = [[] for _ in queries]

    def wrong(answers):
        n = 0
        for k, got in enumerate(answers):
            if got is None:
                continue
            which = k % len(queries)
            for table, bad in seen[which]:   # answers repeat: compare once
                if got.equals(table):
                    break
            else:
                bad = compare.mismatches(got, wanted[which]) > 0
                seen[which].append((got, bad))
            n += bad
        return n
    compared = {"wrong_answers": wrong(window["answers"]),
                "missing_answers": len(window["raised"]),
                "wrong_warm_answers": wrong(warm), **off_device}
    say(phase="reference", seconds=time.perf_counter() - t0,
        distinct_answers=[len(s) for s in seen],
        rows=[len(w.rows) for w in wanted])
    return {k: {"value": v, "limit": 0} for k, v in compared.items()}


def run_cell(cell, seed, seconds, trace, sf=None, system_factory=None,
             t_start=None):
    """Drive one run and return the result object (with the true `correct`).
    `system_factory(config, traffic, work_dir)` makes the system under test;
    the tests pass a broken one."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, config, traffic = cell["bench"], cell["config"], cell["traffic"]
    sf = config["scale_factor"] if sf is None else sf
    queries = traffic["queries"]
    devs = jax.devices()
    cardinality = manifest.cardinality(config, sf)
    work_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    trace_dir = os.path.join(work_dir, "trace") if trace else None
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)

    t0 = time.perf_counter()
    reads = sorted({t for q in queries for t in q["reads"]})
    tables = datagen.generate(reads, sf, seed)
    say(phase="generate", sf=sf, seed=seed, seconds=time.perf_counter() - t0,
        rows={k: v.num_rows for k, v in tables.items()},
        arrow_bytes=sum(v.nbytes for v in tables.values()))

    system = (system_factory or engine.System)(config, traffic, work_dir)
    try:
        say(phase="native", library=system.native())
        t0 = time.perf_counter()
        system.load(tables)
        say(phase="load", storage=config["storage"]["kind"],
            seconds=time.perf_counter() - t0)

        # the first answer: one execution of each query of the mix
        before, t0 = system.counters(), time.perf_counter()
        warm = [system.execute(k) for k in range(len(queries))]
        first = {"seconds": time.perf_counter() - t0,
                 "counters": counters_since(system, before)}
        say(phase="first_answer", **first)
        t0 = time.perf_counter()
        warm += [system.execute(k % len(queries)) for k in range(
            len(queries), len(queries) * (1 + traffic["warm_executions"]))]
        say(phase="warm", executions=len(warm) - len(queries),
            seconds=time.perf_counter() - t0)

        setup_s = time.perf_counter() - t_start
        window = closed_loop(system, len(queries), seconds, trace_dir)
        lat_ms = [1e3 * x for x in window["latencies"]]
        say(phase="window", elapsed_s=window["elapsed_s"],
            attempted=window["attempted"], completed=window["completed"],
            median_ms=statistics.median(lat_ms), max_ms=max(lat_ms),
            quarter_median_ms=[statistics.median(
                lat_ms[len(lat_ms) * i // 4:len(lat_ms) * (i + 1) // 4]
                or lat_ms) for i in range(4)],
            each_ms=lat_ms if len(lat_ms) <= 32 else None,
            counters=window["counters"], raised=window["raised"][:3])
        off_device = system.off_device() if window["completed"] else {}
        mem = devs[0].memory_stats() or {}
    finally:
        system.close()

    compared = judge(tables, queries, window, warm, off_device)
    correct = (window["completed"] > 0 and len(off_device) == 3
               and all(c["value"] <= c["limit"] for c in compared.values()))
    failed = (compared["wrong_answers"]["value"]
              + compared["missing_answers"]["value"])

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": failed, "metrics": {}, "device": device}
    # what a metric's reader is handed (benchmarks/README.md)
    run = {"setup_s": setup_s, "first_answer": first, "window": window,
           "trace": None, "reduced": None,
           "queries": [{
               "rows": bytecount.scanned_rows(cardinality, q["reads"]),
               "least_bytes": bytecount.least_bytes(
                   config["schema"], cardinality, q["reads"], q["result"])}
               for q in queries],
           "peaks": peaks(device["kind"])
           if device["platform"] == "tpu" else None}
    if trace:
        xplane = tracereduce.find_xplane(trace_dir)
        if xplane:
            run["trace"] = tracereduce.load(xplane)
            run["reduced"] = reduced = tracereduce.reduce(run["trace"])
        if run["reduced"]:
            say(phase="trace", executions=reduced["executions"],
                each_execution=reduced["each_execution"])
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    for m in manifest.metrics_of(bench, cell["name"],
                                 "per_layer" if trace else "end_to_end"):
        value = read_metric(m["name"], run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip; `correct` is printed false")
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor, only with --rehearse")
    args = ap.parse_args(argv)
    if args.sf is not None and not args.rehearse:
        ap.error("--sf only with --rehearse: a cell runs at its own scale")
    cell = manifest.cell(ROOT, args.workload)
    try:
        import spark_rapids_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"no run: no program in this checkout: {e}", file=sys.stderr)
        return NO_PROGRAM

    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < cell["chips"]):
        print(f"no run: the cell asks for {cell['chips']} TPU chip(s), jax "
              f"found {len(devs)} x {devs[0].platform}", file=sys.stderr)
        return NO_CHIP
    result = run_cell(cell, args.seed, args.seconds, args.trace, sf=args.sf,
                      t_start=T_START)
    if args.rehearse:
        result["rehearsal"] = {"correct_off_the_chip": result["correct"]}
        result["correct"] = False
        result["compared"] = result.pop("compared")   # stays last
    for name, c in result["compared"].items():
        print(f"compared {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
