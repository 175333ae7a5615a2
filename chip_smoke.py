#!/usr/bin/env python3
"""Smoke: TPC-H q6, q1 and q3 at SF1 through TpuSession on one TPU chip.

One process, the user's entry points (TpuSession, create_dataframe,
session.read.parquet, workloads/tpch.queries()), results compared exactly
with the pandas oracles of workloads/tpch_oracle.py. Every phase prints one
JSON line of set-up facts (not measurements); the LAST line is
{"ok": ..., "device": {...}} and `ok` means "passed on a chip".

  python chip_smoke.py              one chip, SF1 (what the driver runs)
  python chip_smoke.py --chips 4    q3 over tables sharded on four chips, only
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --sf 0.05   sandbox
"""
import argparse
import decimal
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
P = "spark.rapids.tpu."
# the device is never hidden: no host re-run of a refused kernel, no host
# operator for an expression with no device form, no cached answers
CONF = {P + "sql.exec.degradeToHost.enabled": "false",
        P + "sql.allowCpuFallback": "false",
        P + "sql.cache.enabled": "false"}
QUERIES = (6, 1, 3)
COLS = {"lineitem": ["l_orderkey", "l_quantity", "l_extendedprice",
                     "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                     "l_shipdate"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                   "o_shippriority"],
        "customer": ["c_custkey", "c_mktsegment"]}


def say(**kw):
    print(json.dumps(kw), flush=True)


def need(cond, why):
    """A check that survives `python -O`; any failure ends the smoke."""
    if not cond:
        raise RuntimeError(why)


def rows_of(got, exp, ordered):
    """(engine rows, oracle rows), oracle decimals rounded HALF_UP to the
    engine's scale (Spark's rule); unordered results sorted canonically."""
    import pyarrow as pa
    cols = list(exp.columns)
    need(got.column_names == cols, f"columns {got.column_names} != {cols}")
    g, e = [], []
    for c in cols:
        t = got.schema.field(c).type
        ev = exp[c].tolist()
        if pa.types.is_decimal(t):
            q = decimal.Decimal(1).scaleb(-t.scale)
            ev = [decimal.Decimal(v).quantize(q, decimal.ROUND_HALF_UP)
                  for v in ev]
        g.append(got.column(c).to_pylist())
        e.append(ev)
    g, e = list(zip(*g)), list(zip(*e))
    return (g, e) if ordered else (sorted(g), sorted(e))


def plan_nodes(node):
    yield node
    for ch in list(node.children) + list(getattr(node, "members", [])):
        yield from plan_nodes(ch)


def check_on_device(df, name):
    from spark_rapids_tpu.runtime import program_cache
    host = [type(n).__name__ for n in plan_nodes(df._last_root)
            if type(n).__name__ in ("HostProjectExec", "HostFilterExec")]
    need(not host, f"{name}: host operators in the plan: {host}")
    deg = sum(int(m.get("degradedToHost", 0))
              for m in df.last_metrics().values())
    need(deg == 0, f"{name}: degradedToHost={deg}")
    bg = program_cache.stats()["program_cache_background_failures"]
    need(bg == 0, f"{name}: {bg} background compiles failed")


def phase(name, build, expected, ordered=False):
    """Run `build()` twice from a fresh query tree (the second run is what
    shows a donated or deleted buffer); compare both with the oracle."""
    import jax
    from spark_rapids_tpu.profiler import xla_stats
    secs, compiled, hits, csecs = [], [], [], []
    for _ in range(2):
        df = build()
        s0, t0 = xla_stats.snapshot(), time.perf_counter()
        got = df.to_arrow()
        secs.append(time.perf_counter() - t0)
        s1 = xla_stats.snapshot()
        hits.append(s1["cache_hits"] - s0["cache_hits"])
        compiled.append(s1["compiles"] - s0["compiles"] - hits[-1])
        csecs.append(s1["compile_secs"] - s0["compile_secs"])
        g, e = rows_of(got, expected, ordered)
        need(g == e, f"{name}: engine != oracle\n{g[:3]}\n{e[:3]}")
        check_on_device(df, name)
    mem = jax.devices()[0].memory_stats() or {}
    say(phase=name, rows=got.num_rows, run1_secs=secs[0], run2_secs=secs[1],
        programs_compiled=compiled, persistent_cache_hits=hits,
        compile_secs=csecs,
        peak_bytes_in_use=mem.get("peak_bytes_in_use"))


def one_chip(args, tables):
    import pyarrow.parquet as pq
    import spark_rapids_tpu as st
    from spark_rapids_tpu.utils.native import native_lib
    from spark_rapids_tpu.workloads import tpch, tpch_oracle
    say(phase="native", lib="built" if native_lib() else "absent (numpy)")
    t0 = time.perf_counter()
    exact = {k: tables[k].select(c).to_pandas() for k, c in COLS.items()}
    want = {n: getattr(tpch_oracle, f"q{n}")(exact) for n in QUERIES}
    del exact
    say(phase="oracle", secs=time.perf_counter() - t0)
    s = st.TpuSession(CONF)
    t0 = time.perf_counter()
    dfs = {k: s.create_dataframe(v).cache() for k, v in tables.items()}
    say(phase="load", secs=time.perf_counter() - t0,
        rows={k: v.num_rows for k, v in tables.items()})
    reg = tpch.queries()
    for n in QUERIES:
        phase(f"q{n}", lambda n=n: reg[n](dfs), want[n], ordered=(n == 3))
    path = os.path.join(args.out, "lineitem.parquet")
    try:
        pq.write_table(tables["lineitem"], path, compression="snappy",
                       row_group_size=1 << 20)
        phase("q6_parquet",
              lambda: reg[6]({"lineitem": s.read.parquet(path)}), want[6])
    finally:
        if os.path.exists(path):
            os.remove(path)


def four_chips(args, tables):
    """What the cell tpch_sf1_mesh4.q3 runs, and nothing else: q3 over tables
    that cache() row-shards over four chips (mesh.devices=4), the exchanges as
    fused SPMD stages and the joins in lockstep, against the oracle."""
    import spark_rapids_tpu as st
    from spark_rapids_tpu.workloads import tpch, tpch_oracle
    want = tpch_oracle.q3({k: tables[k].select(c).to_pandas()
                           for k, c in COLS.items()})
    s = st.TpuSession(dict(CONF, **{P + "mesh.devices": 4,
                                    P + "mesh.spmdStage.maxBytes": 4 << 30}))
    t0 = time.perf_counter()
    dfs = {k: s.create_dataframe(v).cache() for k, v in tables.items()}
    held = {k: len(df.cached_devices()) for k, df in dfs.items()}
    say(phase="load_mesh4", secs=time.perf_counter() - t0, devices=held)
    need(set(held.values()) == {4}, f"tables not on every chip: {held}")
    ran = []

    def build():
        ran.append(tpch.queries()[3](dfs))
        return ran[-1]
    phase("q3_mesh4", build, want, ordered=True)
    counts = {k: sum(int(m.get(k, 0)) for df in ran
                     for m in df.last_metrics().values())
              for k in ("spmdStages", "spmdDegraded", "meshRounds",
                        "collectiveBytes")}
    say(phase="q3_mesh4_stages", executions=len(ran), **counts)
    need(counts["spmdStages"] > 0, "no fused SPMD stage ran")
    need(counts["spmdDegraded"] == 0 and counts["meshRounds"] == 0,
         f"a stage fell back to the round-based exchange: {counts}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=1.0,
                    help="scale factor; below 1 only to rehearse")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases off the chip; ok stays false")
    ap.add_argument("--out", default=os.path.join(HERE, ".chip_smoke_out"))
    args = ap.parse_args()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    ok = False
    try:
        need(args.rehearse or device["platform"] == "tpu",
             f"no TPU: jax.devices()[0].platform is {device['platform']!r}")
        need(len(devs) >= args.chips, f"need {args.chips} devices: {device}")
        from spark_rapids_tpu.workloads import tpch
        os.makedirs(args.out, exist_ok=True)
        t0 = time.perf_counter()
        tables = {"lineitem": tpch.gen_lineitem(args.sf, args.seed, True),
                  "orders": tpch.gen_orders(args.sf, args.seed + 1, True),
                  "customer": tpch.gen_customer(args.sf, args.seed + 2, True)}
        say(phase="generate", sf=args.sf, seed=args.seed,
            secs=time.perf_counter() - t0,
            arrow_bytes=sum(t.nbytes for t in tables.values()))
        (four_chips if args.chips == 4 else one_chip)(args, tables)
        ok = device["platform"] == "tpu"
    finally:  # the exception, if any, goes on to end the process non-zero
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
