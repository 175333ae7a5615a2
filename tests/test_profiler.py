"""Query profiler: event-log schema round-trip, EXPLAIN ANALYZE (local
and distributed), and the profiling-tool CLI's A/B diff attribution
(ISSUE 2 — the consumer half of the operator-metric story)."""
import json
import os
import sys

import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.profiler.analyze import render_analyze
from spark_rapids_tpu.profiler.event_log import (aggregate_ops,
                                                 op_metrics_records,
                                                 op_time_seconds,
                                                 plan_tree,
                                                 read_event_log,
                                                 top_operators)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))
import profile_report  # noqa: E402


def _session(tmp_path, **extra):
    return st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 4096,
        "spark.rapids.tpu.sql.eventLog.enabled": True,
        "spark.rapids.tpu.sql.eventLog.dir": str(tmp_path / "events"),
        **extra})


def _three_way_q3ish(s):
    """A 3-way TPC-H-shaped join + agg (customer |x| orders |x|
    lineitem, grouped revenue)."""
    cust = s.create_dataframe({
        "c_custkey": list(range(50)),
        "c_seg": ["A" if i % 2 else "B" for i in range(50)]})
    orders = s.create_dataframe({
        "o_orderkey": list(range(200)),
        "o_custkey": [i % 50 for i in range(200)],
        "o_date": [i % 30 for i in range(200)]})
    li = s.create_dataframe({
        "l_orderkey": [i % 200 for i in range(1000)],
        "l_price": [float(i % 97) for i in range(1000)],
        "l_disc": [0.01 * (i % 5) for i in range(1000)]})
    rev = col("l_price") * (lit(1.0) - col("l_disc"))
    return (cust.filter(col("c_seg") == lit("A"))
            .join(orders.with_column("c_custkey", col("o_custkey")),
                  on=["c_custkey"], how="inner")
            .with_column("l_orderkey", col("o_orderkey"))
            .join(li, on=["l_orderkey"], how="inner")
            .group_by("o_date")
            .agg(F.sum(rev).alias("revenue")))


# ----------------------------------------------------------------------
# event-log schema round-trip
# ----------------------------------------------------------------------
def test_event_log_roundtrip(tmp_path):
    s = _session(tmp_path)
    q = _three_way_q3ish(s)
    out = q.to_arrow()
    assert out.num_rows > 0
    path = s.last_event_log
    assert path and os.path.exists(path)
    evs = read_event_log(path)
    kinds = [e["event"] for e in evs]
    # the query service prepends its admission lifecycle (docs/service.md)
    assert kinds[:3] == ["query_queued", "query_admitted", "query_start"]
    assert kinds[-1] == "query_end"
    for required in ("plan", "op_metrics", "watermarks", "xla_compile"):
        assert required in kinds
    # every event is json-round-trippable and tagged with the query id
    qid = evs[0]["query_id"]
    for e in evs:
        assert e["query_id"] == qid
        assert json.loads(json.dumps(e)) == e
    # plan tree carries lore ids; op records key into them
    plan = next(e["plan"] for e in evs if e["event"] == "plan")
    lore_ids = set()

    def walk(n):
        assert {"lore_id", "name", "describe", "children"} <= set(n)
        lore_ids.add(n["lore_id"])
        for c in n["children"]:
            walk(c)

    walk(plan)
    assert None not in lore_ids and len(lore_ids) >= 5
    ops = next(e["ops"] for e in evs if e["event"] == "op_metrics")
    assert {r["lore_id"] for r in ops} == lore_ids
    # a join + agg query must attribute SOME operator time and rows
    assert sum(op_time_seconds(r["metrics"]) for r in ops) > 0
    assert any(r["metrics"].get("numOutputRows") for r in ops)
    end = evs[-1]
    assert end["status"] == "ok" and end["wall_s"] > 0
    wm = next(e for e in evs if e["event"] == "watermarks")
    assert wm["devicePeakBytes"] > 0


def test_event_log_off_by_default(tmp_path):
    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 4096})
    s.create_dataframe({"a": [1, 2, 3]}).to_arrow()
    assert s.last_event_log is None


# ----------------------------------------------------------------------
# EXPLAIN ANALYZE (local)
# ----------------------------------------------------------------------
def test_explain_analyze_local(tmp_path, capsys):
    s = _session(tmp_path)
    q = _three_way_q3ish(s)
    text = q.explain("ANALYZE")
    assert text == capsys.readouterr().out.rstrip("\n")
    # plan nodes annotated with rows / batches / op time, lore ids on
    # every line, top sinks flagged
    assert "HashJoinExec" in text and "AggregateExec" in text
    assert "rows=" in text and "batches=" in text and "time=" in text
    assert "[loreId=" in text
    assert "time sink #1" in text
    assert "total attributed op time" in text


def test_explain_analyze_shows_shuffle_bytes(tmp_path):
    # force the partial/exchange/final agg topology so a
    # ShuffleExchangeExec with byte metrics is in the plan
    s = _session(tmp_path, **{
        "spark.rapids.tpu.sql.shuffle.partitions": 4,
        "spark.rapids.tpu.sql.batchSizeRows": 1024})
    df = s.create_dataframe({
        "k": [i % 7 for i in range(5000)],
        "v": [float(i) for i in range(5000)]})
    q = df.repartition(3).group_by("k").agg(F.sum(col("v")).alias("s"))
    text = q.explain("ANALYZE")
    assert "ShuffleExchangeExec" in text
    assert "shuffle=" in text


def test_sql_explain_statement(tmp_path):
    s = _session(tmp_path)
    s.create_dataframe({"a": [1, 2, 2], "b": [1.0, 2.0, 3.0]}) \
        .create_or_replace_temp_view("t")
    plain = s.sql("EXPLAIN SELECT a, sum(b) FROM t GROUP BY a")
    txt = plain.collect()[0][0]
    assert "[loreId=" in txt and "Aggregate" in txt
    analyzed = s.sql("EXPLAIN ANALYZE SELECT a, sum(b) FROM t GROUP BY a")
    atxt = analyzed.collect()[0][0]
    assert "time=" in atxt and "time sink #1" in atxt


def test_explain_all_carries_lore_ids(tmp_path, capsys):
    s = _session(tmp_path)
    q = _three_way_q3ish(s)
    text = q.explain("ALL")
    capsys.readouterr()
    assert "[loreId=1]" in text
    # lore ids in explain match the ids EXPLAIN ANALYZE reports, so a
    # hot operator maps directly to a lore.idsToDump replay id; ids of
    # operators fused into a FusedStage survive as `Name[id]` members
    # of the fused node's line
    analyzed = q.explain("ANALYZE")
    import re
    ids_plain = set(re.findall(r"loreId=(\d+)", text))
    ids_analyzed = set(re.findall(r"loreId=(\d+)", analyzed))
    ids_analyzed |= set(re.findall(r"\w+\[(\d+)\]", analyzed))
    assert ids_plain and ids_plain <= ids_analyzed


# ----------------------------------------------------------------------
# metrics sync conf (timer-skew satellite)
# ----------------------------------------------------------------------
def test_metrics_sync_timer(tmp_path):
    s = _session(tmp_path, **{"spark.rapids.tpu.sql.metrics.sync": True})
    df = s.create_dataframe({"a": [1, 2, 3, 4] * 64})
    q = df.group_by("a").agg(F.count(col("a")).alias("n"))
    q.to_arrow()
    # timers still record (now stream-synced) positive values
    ms = q.last_metrics()
    assert any(v > 0 for snap in ms.values()
               for k, v in snap.items() if k.endswith("Time"))


# ----------------------------------------------------------------------
# distributed runner: executor metrics reach the driver
# ----------------------------------------------------------------------
def test_explain_analyze_distributed(tmp_path):
    import pyarrow.parquet as pq
    from spark_rapids_tpu.cluster.driver import ClusterManager
    from spark_rapids_tpu.cluster.query import DistributedRunner
    from spark_rapids_tpu.workloads import tpch, tpch_cluster

    li = tpch.gen_lineitem(sf=0.01, seed=7)
    cust = tpch.gen_customer(sf=0.01, seed=7)
    orders = tpch.gen_orders(sf=0.01, seed=7)
    cust_p = str(tmp_path / "customer.parquet")
    ord_p = str(tmp_path / "orders.parquet")
    pq.write_table(cust, cust_p)
    pq.write_table(orders, ord_p)
    n = li.num_rows
    splits = []
    for i in range(2):
        p = str(tmp_path / f"lineitem-{i}.parquet")
        pq.write_table(li.slice(i * n // 2,
                                (i + 1) * n // 2 - i * n // 2), p)
        splits.append({"lineitem": p, "customer": cust_p,
                       "orders": ord_p})

    cm = ClusterManager(2)
    cm.start()
    try:
        runner = DistributedRunner(cm, {
            "spark.rapids.tpu.sql.batchSizeRows": 8192,
            "spark.rapids.tpu.sql.eventLog.enabled": True,
            "spark.rapids.tpu.sql.eventLog.dir":
                str(tmp_path / "events")})
        got = runner.run(splits, tpch_cluster.q3_map,
                         part_keys=["l_orderkey"],
                         reduce_fn=tpch_cluster.q3_reduce, n_reduce=2,
                         final_fn=tpch_cluster.q3_final)
    finally:
        cm.shutdown()
    assert got.num_rows > 0
    # executor MetricSet snapshots crossed the RPC and aggregated
    stages = runner.last_profile["stages"]
    assert stages["map"]["tasks"] == 2
    assert stages["reduce"]["tasks"] == 2
    text = runner.explain_analyze()
    assert "== map stage: 2 tasks" in text
    assert "== reduce stage: 2 tasks" in text
    assert "HashJoinExec" in text and "rows=" in text
    assert "time sink #1" in text
    # driver-side event log carries both stages
    evs = read_event_log(runner.last_event_log)
    kinds = [e["event"] for e in evs]
    assert kinds.count("stage_submit") >= 2
    assert kinds.count("op_metrics") == 2
    assert kinds[-1] == "query_end" and evs[-1]["status"] == "ok"
    per_stage = {e["stage"] for e in evs if e["event"] == "op_metrics"}
    assert per_stage == {"map", "reduce"}


# ----------------------------------------------------------------------
# profiling-tool CLI: report + A/B diff attribution
# ----------------------------------------------------------------------
def _synthetic_log(path, query_id, slow_join=False):
    """Two-operator synthetic event log; run B's join is 10x slower."""
    plan = {"lore_id": 1, "name": "HashAggregateExec",
            "describe": "HashAggregateExec[keys=['k']]",
            "children": [{"lore_id": 2, "name": "HashJoinExec",
                          "describe": "HashJoinExec[inner]",
                          "children": []}]}
    join_t = 0.5 if slow_join else 0.05
    events = [
        {"event": "query_start", "ts": 0.0, "query_id": query_id,
         "action": "collect"},
        {"event": "plan", "ts": 0.0, "query_id": query_id, "plan": plan},
        {"event": "op_metrics", "ts": 1.0, "query_id": query_id, "ops": [
            {"lore_id": 1, "name": "HashAggregateExec",
             "describe": "HashAggregateExec[keys=['k']]",
             "metrics": {"opTime": 0.02, "numOutputRows": 10,
                         "numOutputBatches": 1}},
            {"lore_id": 2, "name": "HashJoinExec",
             "describe": "HashJoinExec[inner]",
             "metrics": {"opTime": join_t, "numOutputRows": 1000,
                         "numOutputBatches": 2}}]},
        {"event": "query_end", "ts": 1.0, "query_id": query_id,
         "status": "ok", "wall_s": 1.0},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return path


def test_cli_diff_attributes_regressed_operator(tmp_path, capsys):
    a = _synthetic_log(str(tmp_path / "a.jsonl"), "qa", slow_join=False)
    b = _synthetic_log(str(tmp_path / "b.jsonl"), "qb", slow_join=True)
    ranked = profile_report.diff_ops(profile_report.load_events(a),
                                     profile_report.load_events(b))
    assert ranked[0]["name"] == "HashJoinExec"
    assert ranked[0]["delta_s"] == pytest.approx(0.45)
    assert ranked[0]["ratio"] == pytest.approx(10.0)
    # and through the CLI entry point
    rc = profile_report.main(["--diff", a, b])
    out = capsys.readouterr().out
    assert rc == 0
    assert "most regressed operator" in out
    assert "HashJoinExec" in out.split("most regressed operator")[1]


def test_cli_report_renders_tree(tmp_path, capsys):
    log = _synthetic_log(str(tmp_path / "a.jsonl"), "qa")
    rc = profile_report.main([log])
    out = capsys.readouterr().out
    assert rc == 0
    assert "HashJoinExec" in out and "[loreId=2]" in out
    assert "time sink #1" in out


def test_cli_diff_on_real_logs(tmp_path):
    """Diff two REAL event logs of the same plan: an injected slowdown
    (sleep inside a host-eval projection) lands on the right operator."""
    import time as _t
    evdir = tmp_path / "ev"
    s = st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 4096,
        "spark.rapids.tpu.sql.eventLog.enabled": True,
        "spark.rapids.tpu.sql.eventLog.dir": str(evdir)})
    df = s.create_dataframe({"a": [1, 2, 3, 4] * 32,
                             "b": [1.0, 2.0, 3.0, 4.0] * 32})

    def run():
        q = df.group_by("a").agg(F.sum(col("b")).alias("s"))
        q.to_arrow()
        return s.last_event_log

    # warm the jit caches first: the first execution pays XLA compile
    # INSIDE the aggregate's opTime timer (~1s), which would swamp log A
    # and make every operator look faster in B (the seed failure mode:
    # no operator regresses, diff comes back empty). Two warm-ups: the
    # second also drains one-shot global-state work (e.g. spill-store
    # pressure hooks left registered by earlier test modules) that would
    # otherwise inflate log A by tens of ms.
    run()
    run()
    log_a = run()
    # injected slowdown: patch the aggregate's timer target
    from spark_rapids_tpu.exec import aggregate as agg_exec
    orig = agg_exec.HashAggregateExec.execute_partition

    def slow(self, ctx, pid):
        m = ctx.metrics_for(self._op_id)
        with m.timer("opTime"):
            _t.sleep(0.25)
        return orig(self, ctx, pid)

    agg_exec.HashAggregateExec.execute_partition = slow
    try:
        log_b = run()
    finally:
        agg_exec.HashAggregateExec.execute_partition = orig
    ranked = profile_report.diff_ops(profile_report.load_events(log_a),
                                     profile_report.load_events(log_b))
    regressed = [r for r in ranked if r["delta_s"] > 0]
    assert regressed[0]["name"] == "HashAggregateExec"
    assert regressed[0]["delta_s"] >= 0.2


# ----------------------------------------------------------------------
# helpers: aggregation + top operators (tools/profile_report.py path)
# ----------------------------------------------------------------------
def test_aggregate_ops_and_top_operators(tmp_path):
    s = _session(tmp_path)
    q = _three_way_q3ish(s)
    q.to_arrow()
    recs = op_metrics_records(q._last_root, q.last_metrics())
    # aggregation across two identical runs doubles additive metrics
    agg2 = aggregate_ops(recs + recs)
    one = aggregate_ops(recs)
    for key, rec in one.items():
        rows1 = rec["metrics"].get("numOutputRows")
        if rows1:
            assert agg2[key]["metrics"]["numOutputRows"] == 2 * rows1
    top = top_operators(recs, 5)
    assert 0 < len(top) <= 5
    assert top[0]["time_ms"] >= top[-1]["time_ms"]
    assert {"op", "loreId", "time_ms", "rows"} <= set(top[0])


def test_render_analyze_handles_missing_metrics():
    tree = {"lore_id": 1, "name": "X", "describe": "X[]", "children": [
        {"lore_id": 2, "name": "Y", "describe": "Y[]", "children": []}]}
    text = render_analyze(tree, {})
    assert "[loreId=1] X[]" in text and "[loreId=2] Y[]" in text
