"""Multichip SPMD-stage dryrun: the worker behind ``bench.py
--multichip``.

Runs the q3/q6 distributed shapes over an N-device mesh (virtual CPU
devices in CI — the parent process forces
``--xla_force_host_platform_device_count`` BEFORE jax imports, which is
why this lives in a subprocess) through THREE engine paths and prints
ONE JSON document on the last stdout line:

  host    mesh disabled (``mesh.devices 0``) — the single-chip + host
          shuffle reference every other path must match byte-for-byte
  round   mesh on, ``mesh.spmdStage.enabled false`` — the streaming
          round-based MeshExchangeExec (bounded-memory fallback)
  fused   mesh on, SPMD stages on — exchange + consumer as ONE
          shard_map program per stage (the PR 16 tentpole)

Per query the document carries the fused-stage count, collective bytes
moved, programs compiled cold vs on a warm rerun (the warm count must
be zero — the stage program is keyed on mesh topology + plan
fingerprints, so a rerun recompiles nothing), and parity booleans
against the host path. ``bench.py`` folds the document into
MULTICHIP_r06.json and regression-gates the parity bits.

Results are canonicalized (rows sorted by every column) before
comparison: the three paths partition rows differently, so row ORDER
is path-dependent while row CONTENT must not be.
"""
from __future__ import annotations

import json
import os
import sys


def _canon(tbl):
    """Row-order canonical form: sort by all columns (paths shard rows
    differently; content, not order, is the parity contract)."""
    import pyarrow.compute as pc
    if tbl.num_rows <= 1:
        return tbl
    idx = pc.sort_indices(
        tbl, sort_keys=[(name, "ascending") for name in tbl.column_names])
    return tbl.take(idx)


def _q6_shape(lineitem):
    """TPC-H Q6 distributed shape: the Q6 predicate stack feeding a
    grouped revenue sum (plain Q6 is a global reduction — no exchange
    to fuse — so the dryrun groups by return flag to route the same
    filter+agg shape through the mesh exchange)."""
    import decimal

    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.expr.expressions import col, lit
    d = decimal.Decimal
    return (lineitem.filter(
                (col("l_shipdate") >= 8766) & (col("l_shipdate") < 9131)
                & (col("l_discount") >= lit(d("0.05")))
                & (col("l_discount") <= lit(d("0.07")))
                & (col("l_quantity") < lit(d("24"))))
            .group_by("l_returnflag")
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def _q3_shape(customer, orders, lineitem):
    """TPC-H Q3 distributed shape — filter + join + join + grouped agg
    (the topk tail is dropped: limit-ties would make cross-path byte
    parity order-dependent, which is not what this dryrun measures)."""
    import decimal

    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.expr.expressions import col, lit
    d = decimal.Decimal
    rev = col("l_extendedprice") * (lit(d("1")) - col("l_discount"))
    return (customer.filter(col("c_mktsegment") == lit("BUILDING"))
            .join(orders.with_column("c_custkey", col("o_custkey")),
                  on=["c_custkey"], how="inner")
            .filter(col("o_orderdate") < 9204)
            .with_column("l_orderkey", col("o_orderkey"))
            .join(lineitem, on=["l_orderkey"], how="inner")
            .filter(col("l_shipdate") > 9204)
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(rev).alias("revenue")))


def _metric_sum(df, key) -> int:
    """Sum `key` over the per-operator metrics of `df`'s last action."""
    return int(sum(m.get(key, 0)
                   for m in df.last_metrics().values()))


def _spmd_compiles(events) -> int:
    return sum(1 for ev in events
               if ev.get("program", "").startswith("SpmdStageExec"))


def main() -> int:
    import jax

    import spark_rapids_tpu as st
    from spark_rapids_tpu.runtime import program_cache
    from spark_rapids_tpu.workloads import tpch

    n_dev = min(int(os.environ.get("SPMD_BENCH_DEVICES", "8")),
                len(jax.devices()))
    doc = {"n_devices": n_dev, "queries": {}, "ok": True}
    if n_dev < 2:
        doc.update(ok=False,
                   reason=f"{len(jax.devices())} device(s); mesh needs 2+")
        print(json.dumps(doc))
        return 2

    sf = float(os.environ.get("SPMD_BENCH_SF", "0.02"))
    # small batches force multiple shards/batches per partition so the
    # collective actually moves rows between devices
    batch = int(os.environ.get("SPMD_BENCH_BATCH", "2048"))
    li = tpch.gen_lineitem(sf=sf, seed=7)
    od = tpch.gen_orders(sf=sf, seed=8)
    cu = tpch.gen_customer(sf=sf, seed=9)

    def build(s, qname):
        dfs = {k: s.create_dataframe(v)
               for k, v in (("lineitem", li), ("orders", od),
                            ("customer", cu))}
        if qname == "q6":
            return _q6_shape(dfs["lineitem"])
        return _q3_shape(dfs["customer"], dfs["orders"], dfs["lineitem"])

    def session(extra):
        conf = {"spark.rapids.tpu.sql.batchSizeRows": batch,
                "spark.rapids.tpu.sql.resultCache.enabled": "false"}
        conf.update(extra)
        return st.TpuSession(conf)

    mesh_on = {"spark.rapids.tpu.mesh.devices": n_dev}
    for qname in ("q6", "q3"):
        host = _canon(build(session(
            {"spark.rapids.tpu.mesh.devices": 0}), qname).to_arrow())

        s_round = session(dict(
            mesh_on, **{"spark.rapids.tpu.mesh.spmdStage.enabled":
                        "false"}))
        round_df = build(s_round, qname)
        round_tbl = _canon(round_df.to_arrow())
        round_rounds = _metric_sum(round_df, "meshRounds")
        round_bytes = _metric_sum(round_df, "collectiveBytes")

        s_fused = session(dict(mesh_on))
        program_cache.drain_compile_events()
        fused_df = build(s_fused, qname)
        fused_tbl = _canon(fused_df.to_arrow())
        cold = _spmd_compiles(program_cache.drain_compile_events())
        stages = _metric_sum(fused_df, "spmdStages")
        fused_bytes = _metric_sum(fused_df, "collectiveBytes")
        degraded = _metric_sum(fused_df, "spmdDegraded")
        # warm rerun: fresh query tree, same session — the mesh-keyed
        # program cache must serve every stage program without compiling
        warm_df = build(s_fused, qname)
        warm_tbl = _canon(warm_df.to_arrow())
        warm = _spmd_compiles(program_cache.drain_compile_events())

        q = {
            "rows": host.num_rows,
            "spmd_stages": stages,
            "collective_bytes_fused": fused_bytes,
            "collective_bytes_round": round_bytes,
            "mesh_rounds_round_path": round_rounds,
            "programs_compiled_cold": cold,
            "programs_compiled_warm": warm,
            "spmd_degraded": degraded,
            "parity_fused_vs_host": fused_tbl.equals(host),
            "parity_round_vs_host": round_tbl.equals(host),
            "parity_warm_rerun": warm_tbl.equals(host),
        }
        q["ok"] = bool(q["parity_fused_vs_host"]
                       and q["parity_round_vs_host"]
                       and q["parity_warm_rerun"]
                       and stages > 0 and degraded == 0
                       and cold > 0 and warm == 0)
        doc["queries"][qname] = q
        doc["ok"] = doc["ok"] and q["ok"]
        print(f"spmd_bench: {qname} rows={q['rows']} stages={stages} "
              f"cold={cold} warm={warm} ok={q['ok']}", file=sys.stderr)

    print(json.dumps(doc))
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
