"""Scale-test harness: configurable-size synthetic workloads with
per-query timing JSON (analog of the reference's datagen/ScaleTest.md
scale test: complexity-scaled data generation + a fixed query battery
reporting elapsed times for regression tracking).

Usage:
    python -m spark_rapids_tpu.workloads.scale_test \
        --scale 1.0 --data-dir /tmp/srtpu-scale --out report.json

Scale 1.0 ~= 6M lineitem rows; data generates once per (scale, seed)
and is reused. Each query runs `iterations` times (first = cold,
including compile; min of the rest = hot) and the report carries
rows/s so runs at different scales compare."""
from __future__ import annotations

import json
import os
import time

__all__ = ["run_scale_test", "QUERIES"]


def _ensure_data(session, data_dir: str, scale: float, seed: int):
    from . import tpch
    os.makedirs(data_dir, exist_ok=True)
    marker = os.path.join(data_dir, f"_ready_sf{scale}_s{seed}")
    tables = {}
    gens = {
        "lineitem": lambda: tpch.gen_lineitem(sf=scale, seed=seed,
                                              full=True),
        "orders": lambda: tpch.gen_orders(sf=scale, seed=seed,
                                          full=True),
        "customer": lambda: tpch.gen_customer(sf=scale, seed=seed,
                                              full=True),
    }
    for name, gen in gens.items():
        path = os.path.join(data_dir, name)
        if not os.path.exists(marker):
            df = session.create_dataframe(gen())
            df.write.mode("overwrite").parquet(path)
        tables[name] = path
    open(marker, "w").close()
    return tables


def _q_scan_agg(s, t):
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.functions import col
    df = s.read.parquet(t["lineitem"])
    return df.group_by("l_returnflag").agg(
        F.sum(col("l_extendedprice")).alias("rev"),
        F.avg(col("l_discount")).alias("ad"),
        F.count(col("l_quantity")).alias("n")).to_arrow()


def _q_filter_project(s, t):
    from spark_rapids_tpu.functions import col
    df = s.read.parquet(t["lineitem"])
    return df.filter((col("l_discount") >= 0.05)
                     & (col("l_quantity") < 24)).select(
        (col("l_extendedprice") * (1 - col("l_discount")))
        .alias("x")).to_arrow()


def _q_join_agg(s, t):
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.functions import col
    li = s.read.parquet(t["lineitem"])
    od = s.read.parquet(t["orders"])
    j = li.join(od, on=(col("l_orderkey") == col("o_orderkey")))
    return j.group_by("o_orderpriority").agg(
        F.sum(col("l_extendedprice")).alias("rev")).to_arrow()


def _q_window(s, t):
    from spark_rapids_tpu.window import Window, win_sum, row_number
    from spark_rapids_tpu.functions import col
    df = s.read.parquet(t["orders"])
    w = Window.partition_by("o_orderpriority").order_by("o_orderdate")
    return df.select(
        col("o_orderkey"),
        row_number().over(w).alias("rn"),
        win_sum(col("o_totalprice").cast("double")).over(w)
        .alias("run"),
    ).to_arrow()


def _q_sort_limit(s, t):
    df = s.read.parquet(t["lineitem"])
    return df.sort("l_extendedprice", ascending=False).limit(100) \
        .to_arrow()


QUERIES = {
    "scan_agg": _q_scan_agg,
    "filter_project": _q_filter_project,
    "join_agg": _q_join_agg,
    "window": _q_window,
    "sort_limit": _q_sort_limit,
}


def run_scale_test(scale: float = 0.1, data_dir: str = "/tmp/srtpu-scale",
                   iterations: int = 3, seed: int = 0,
                   conf: dict = None, queries=None) -> dict:
    import spark_rapids_tpu as st
    s = st.TpuSession(conf or {})
    tables = _ensure_data(s, data_dir, scale, seed)
    li_rows = s.read.parquet(tables["lineitem"]).count()
    report = {"scale": scale, "lineitem_rows": li_rows, "queries": {}}
    for name in (queries or QUERIES):
        fn = QUERIES[name]
        times = []
        out_rows = 0
        for _ in range(max(1, iterations)):
            t0 = time.perf_counter()
            out = fn(s, tables)
            times.append(time.perf_counter() - t0)
            out_rows = out.num_rows
        hot = min(times[1:]) if len(times) > 1 else times[0]
        report["queries"][name] = {
            "cold_s": round(times[0], 4),
            "hot_s": round(hot, 4),
            "output_rows": out_rows,
            "input_rows_per_sec": round(li_rows / hot, 1),
        }
    return report


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--data-dir", default="/tmp/srtpu-scale")
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default=None,
                    help="jax platform override (e.g. 'cpu')")
    args = ap.parse_args()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    rep = run_scale_test(args.scale, args.data_dir, args.iterations,
                         args.seed)
    text = json.dumps(rep, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
