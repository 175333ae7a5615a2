"""Structured per-query event log (the Spark event-log analog).

One JSONL file per query under `spark.rapids.tpu.sql.eventLog.dir`, with
typed events the profiling tool post-processes:

  query_queued  {pool, estimate_device_bytes, estimate_host_bytes}
                (query service, service/query_manager.py)
  query_admitted{pool, queue_wait_ms}            (query service)
  query_start   {query_id, action, ts}
  plan          {plan: nested {lore_id, name, describe, children}}
  plan_audit    {ok, nodes, findings: [{kind, reason, node, path,
                 lore_id}]}   (static auditor, analysis/audit.py)
  aqe_replan    {action, decisions: [{rule: shuffle_read|
                 demote_broadcast_join, ...lore ids old→new, partition
                 counts, split/byte thresholds}]}  (AQE stage driver,
                 plan/aqe.py; emitted between stage completion and
                 consumer launch when any replan decision was taken)
  stage_submit  {stage, n_tasks, attempt}        (distributed runner)
  stage_complete{stage, wall_s, shuffle_bytes}   (distributed runner)
  fetch_retry   {stage, pid, shuffle_id}         (distributed runner)
  op_metrics    {ops: [{lore_id, name, describe, metrics}], stage?}
  watermarks    {devicePeakBytes, hostPeakBytes, spill?, hostPressure?}
  xla_compile   {compiles, compile_secs, cache_hits, cache_misses,
                 dispatches}
  result_cache  {hits, misses, fragment_hits, fragment_misses, stores,
                 evictions, invalidations, entries, bytes, fast_path?,
                 rows?}   (cross-query result cache,
                 runtime/result_cache.py; emitted when
                 sql.cache.enabled — fast_path=True records a
                 whole-query hit answered without admission)
  query_cancelled{reason, lockdep?: {threads, findings, edges},
                 ledger?: {kinds, holders, findings}}
                (cooperative cancel / deadline kill; deadline kills
                 attach the runtime/lockdep.py all-threads dump and the
                 runtime/ledger.py outstanding-holders dump)
  concurrency_report{enabled, resources, orderEdges, maxOrderGraph,
                 acquires, findings}  (lockdep witness, when enabled)
  resource_ledger{enabled, kinds: {kind: {acquires, releases,
                 outstanding, peakOutstanding}}, balanceOk,
                 balancedQueries, imbalancedQueries, findings}
                (resource-lifetime ledger, runtime/ledger.py, when
                 enabled — per-kind acquire/release counters and the
                 per-query balance verdicts)
  race_report   {enabled, tracked, shared, accesses, findings,
                 perturbed}  (data-race witness, runtime/racedep.py,
                 when enabled — Eraser lockset tracking over the
                 instrumented shared structures)
  trace_span    {trace_id, span_id, parent_id, name, kind, start_ns,
                 end_ns, dur_ms, proc, attrs?}  (distributed tracing,
                 profiler/tracing.py — the query's assembled spans,
                 driver + pools + executors, one trace per query)
  trace_summary {total_ms, shares, share_pct, dominant, dominant_pct,
                 span_count}  (critical-path decomposition,
                 profiler/critical_path.py)
  query_end     {status: ok|error|cancelled|timeout, wall_s, error?}

Locally `session.py` wraps every action (`profile_query`); the
distributed runner (cluster/query.py) writes one log driver-side from
the executor `MetricSet` snapshots that ride back with task results.
Metric values honor `spark.rapids.tpu.sql.metrics.level`; op time is the
sum of the operator's `*Time` timers (see docs/observability.md for the
async-dispatch skew caveat and the `sql.metrics.sync` gate).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..utils.metrics import DEBUG

__all__ = ["EventLogWriter", "open_query_log", "read_event_log",
           "next_query_id", "plan_tree", "op_metrics_records",
           "aggregate_ops", "op_time_seconds", "top_operators",
           "profile_query", "log_fast_path"]

_QUERY_SEQ = itertools.count()


def next_query_id(prefix: str = "query") -> str:
    """Process-unique query id (also the event-log file stem)."""
    return f"{prefix}-{os.getpid()}-{next(_QUERY_SEQ)}"


def _json_default(o):
    try:
        return float(o)
    except Exception:
        return str(o)


class EventLogWriter:
    """Append-only JSONL writer; one file per query, flushed per event
    so a crashed query still leaves a readable prefix."""

    def __init__(self, path: str, query_id: str):
        self.path = path
        self.query_id = query_id
        self._lock = threading.Lock()
        self._f = open(path, "a", encoding="utf-8")

    def emit(self, event: str, **fields):
        rec = {"event": event, "ts": round(time.time(), 6),
               "query_id": self.query_id}
        rec.update(fields)
        line = json.dumps(rec, default=_json_default)
        with self._lock:
            if self._f is None:
                return
            try:
                self._f.write(line + "\n")
                self._f.flush()
            except OSError:
                # a full/yanked log volume must not fail the query; a
                # torn line is fine — the reader skips it
                f, self._f = self._f, None
                try:
                    f.close()
                except OSError:
                    pass

    def close(self):
        with self._lock:
            if self._f is not None:
                try:
                    self._f.close()
                except OSError:
                    pass
                self._f = None


def open_query_log(conf, query_id: str) -> Optional[EventLogWriter]:
    """EventLogWriter for this query, or None when logging is off."""
    from ..config import EVENT_LOG_DIR, EVENT_LOG_ENABLED
    if not conf.get(EVENT_LOG_ENABLED):
        return None
    d = conf.get(EVENT_LOG_DIR)
    try:
        os.makedirs(d, exist_ok=True)
        return EventLogWriter(os.path.join(d, f"{query_id}.jsonl"),
                              query_id)
    except OSError:
        return None


def read_event_log(path: str) -> List[dict]:
    """Parse a JSONL event log; tolerates a torn trailing line."""
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


# ---------------------------------------------------------------------
# plan / metric snapshots (shared by session, cluster runner, tools)
# ---------------------------------------------------------------------
def plan_tree(root) -> dict:
    """Physical plan as a JSON-able tree keyed by lore_id (stable across
    processes for the same plan — the cross-executor aggregation key)."""
    return {"lore_id": getattr(root, "lore_id", None),
            "name": root.node_name(),
            "describe": root.describe(),
            "children": [plan_tree(c) for c in root.children]}


def op_metrics_records(root, metrics_by_opid: Dict[str, object],
                       max_level: int = DEBUG) -> List[dict]:
    """Flatten the physical tree into per-operator metric records.
    `metrics_by_opid` maps `node._op_id` to a MetricSet OR an already
    snapshotted dict (DataFrame.last_metrics shape)."""
    recs = []

    def walk(node):
        ms = metrics_by_opid.get(node._op_id)
        if hasattr(ms, "snapshot"):
            ms = ms.snapshot(max_level)
        recs.append({"lore_id": getattr(node, "lore_id", None),
                     "name": node.node_name(),
                     "describe": node.describe(),
                     "metrics": dict(ms or {})})
        for c in node.children:
            walk(c)

    walk(root)
    return recs


def aggregate_ops(records: List[dict]) -> Dict[str, dict]:
    """Merge operator records across tasks/executors/queries, keyed by
    `lore_id:name` (stable for the same fragment plan in every worker
    process — id()-based _op_ids are NOT). Numeric metrics sum."""
    out: Dict[str, dict] = {}
    for r in records:
        key = f"{r.get('lore_id')}:{r.get('name')}"
        cur = out.setdefault(key, {"lore_id": r.get("lore_id"),
                                   "name": r.get("name"),
                                   "describe": r.get("describe"),
                                   "metrics": {}})
        for k, v in (r.get("metrics") or {}).items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                cur["metrics"][k] = v
            else:
                cur["metrics"][k] = cur["metrics"].get(k, 0) + v
    return out


def op_time_seconds(metrics: dict) -> float:
    """An operator's attributed time: the sum of its `*Time` timers
    (opTime, scanTime, buildTime, partitionTime, writeTime, ...)."""
    t = 0.0
    for k, v in (metrics or {}).items():
        if k.endswith("Time") and isinstance(v, (int, float)) \
                and not isinstance(v, bool):
            t += float(v)
    return t


def top_operators(records: List[dict], n: int = 5) -> List[dict]:
    """Top-n operators by attributed time (the EXPLAIN ANALYZE sink list
    and tools/profile_report.py)."""
    rows = []
    for r in records:
        m = r.get("metrics") or {}
        t = op_time_seconds(m)
        if t <= 0 and not m:
            continue
        rows.append({"op": r.get("describe"),
                     "loreId": r.get("lore_id"),
                     "time_ms": round(t * 1e3, 3),
                     "rows": m.get("numOutputRows")})
    rows.sort(key=lambda r: r["time_ms"], reverse=True)
    return rows[:n]


# ---------------------------------------------------------------------
# the per-action wrapper session.py runs every query inside
# ---------------------------------------------------------------------
@contextmanager
def profile_query(session, root, ctx, action: str, handle=None):
    """Emit the full event sequence for one local query action. No-op
    (beyond a cheap conf check) when event logging is disabled. With a
    query-service `handle`, the log file is named by the handle's
    query_id and carries queue/admission/cancellation events."""
    w = open_query_log(ctx.conf, handle.query_id if handle is not None
                       else next_query_id())
    if w is None:
        yield None
        return
    from ..memory import diagnostics
    from . import xla_stats
    if session is not None:
        session.last_event_log = w.path
    xla0 = xla_stats.snapshot()
    from ..runtime import result_cache
    rc_on = result_cache.enabled(ctx.conf)
    rc0 = result_cache.stats() if rc_on else None
    fleet0 = _fleet_stats()
    diagnostics.reset_watermarks()
    t0 = time.perf_counter()
    if handle is not None:
        # reconstructed from handle timestamps: by the time the action
        # body runs, the query has already been queued and admitted
        w.emit("query_queued", pool=handle.pool,
               estimate_device_bytes=int(handle.estimate[0]),
               estimate_host_bytes=int(handle.estimate[1]))
        w.emit("query_admitted", pool=handle.pool,
               queue_wait_ms=round(handle.queue_wait_ms, 3))
    w.emit("query_start", action=action)
    w.emit("plan", plan=plan_tree(root))
    audit = getattr(root, "audit_report", None)
    if audit is not None:
        # static-audit verdicts keyed by lore id (analysis/audit.py):
        # which nodes fall back, cannot run, or risk recompiles
        w.emit("plan_audit", ok=audit.ok, nodes=audit.node_count,
               findings=audit.to_events())
    status, err = "ok", None
    try:
        yield w
    except BaseException as e:
        from ..service.query_manager import QueryCancelled, QueryTimedOut
        if isinstance(e, QueryTimedOut):
            status = "timeout"
        elif isinstance(e, QueryCancelled):
            status = "cancelled"
        else:
            status = "error"
        err = repr(e)
        if status != "error":
            # deadline kills carry the lockdep all-threads dump (see
            # runtime/lockdep.attach_dump) — surface it so a timeout in
            # the log is attributable to held resources, not a mystery
            cancel_fields = {"reason": status}
            dump = getattr(e, "lockdep_dump", None)
            if dump is not None:
                cancel_fields["lockdep"] = dump
            ldump = getattr(e, "ledger_dump", None)
            if ldump is not None:
                cancel_fields["ledger"] = ldump
            w.emit("query_cancelled", **cancel_fields)
        raise
    finally:
        try:
            w.emit("op_metrics", ops=op_metrics_records(
                root, ctx.metrics, ctx.metrics_level))
            from ..runtime import ledger, lockdep, racedep
            lw = lockdep.witness()
            if lw is not None:
                w.emit("concurrency_report", **lw.report())
            lg = ledger.ledger()
            if lg is not None:
                w.emit("resource_ledger", **lg.report())
            rw = racedep.witness()
            if rw is not None:
                w.emit("race_report", **rw.report())
            w.emit("watermarks", **diagnostics.watermarks_snapshot())
            x1 = xla_stats.snapshot()
            w.emit("xla_compile",
                   **{k: round(x1[k] - xla0.get(k, 0), 6)
                      for k in x1})
            # per-compile events (program key hash, wall ms, sync vs
            # background) accumulated since the last drain; global, so
            # concurrent queries' compiles land in whichever query's
            # log drains first — attribution is best-effort, the
            # counters above are the invariant
            from ..runtime import program_cache
            for ev in program_cache.drain_compile_events():
                w.emit("compile", **ev)
            if rc_on:
                rc1 = result_cache.stats()
                w.emit("result_cache",
                       hits=rc1["result_cache_hits"]
                       - rc0["result_cache_hits"],
                       misses=rc1["result_cache_misses"]
                       - rc0["result_cache_misses"],
                       fragment_hits=rc1["result_cache_fragment_hits"]
                       - rc0["result_cache_fragment_hits"],
                       fragment_misses=rc1[
                           "result_cache_fragment_misses"]
                       - rc0["result_cache_fragment_misses"],
                       stores=rc1["result_cache_stores"]
                       + rc1["result_cache_fragment_stores"]
                       - rc0["result_cache_stores"]
                       - rc0["result_cache_fragment_stores"],
                       evictions=rc1["result_cache_evictions"]
                       - rc0["result_cache_evictions"],
                       invalidations=rc1["result_cache_invalidations"]
                       - rc0["result_cache_invalidations"],
                       entries=rc1["result_cache_entries"],
                       bytes=rc1["result_cache_bytes"])
            fleet1 = _fleet_stats()
            if fleet1 is not None:
                w.emit("fleet", **_fleet_delta(fleet0, fleet1))
            wall = time.perf_counter() - t0
            # distributed-tracing assembly: end the root span, drain
            # every span the query recorded (driver threads, pool
            # workers, executor-side spans absorbed from the
            # task-metric side channel) and reduce them to the
            # critical-path summary. Failure paths included — a trace
            # of a failed query is exactly when attribution matters.
            try:
                from . import tracing
                spans = tracing.finish(ctx, wall)
                for s in spans:
                    w.emit("trace_span", **s)
                summ = getattr(ctx, "trace_summary", None)
                if spans and summ is not None:
                    w.emit("trace_summary", **summ)
            except Exception:
                pass
            end = {"status": status, "wall_s": round(wall, 6)}
            if err is not None:
                end["error"] = err
            w.emit("query_end", **end)
        finally:
            w.close()


def _fleet_stats():
    """Counter snapshot of this thread's active fleet member, or None
    outside a fleet — the `fleet` event only appears in logs of fleet
    processes."""
    try:
        from ..fleet import context as fleet_context
    except Exception:
        return None
    m = fleet_context.active_member()
    if m is None:
        return None
    return {k: v for k, v in m.snapshot().items()
            if isinstance(v, (int, float))}


def _fleet_delta(before, after) -> dict:
    """Per-query deltas for the monotone counters, absolute values for
    the gauges (export size, live-peer count)."""
    before = before or {}
    out = {}
    for k, v in after.items():
        if k.startswith(("fleet_export_", "fleet_peers_")):
            out[k.replace("fleet_", "", 1)] = v
        else:
            out[k.replace("fleet_", "", 1)] = v - before.get(k, 0)
    return out


def log_fast_path(session, conf, handle, action: str, rows: int,
                  wall_s: float):
    """Compact event log for a result-cache FAST-PATH hit: the query
    never planned or executed, so the full profile_query sequence does
    not apply — but a served query must still leave an auditable
    record (query_start / result_cache / query_end)."""
    w = open_query_log(conf, handle.query_id if handle is not None
                       else next_query_id())
    if w is None:
        return
    try:
        if session is not None:
            session.last_event_log = w.path
        w.emit("query_start", action=action, fast_path=True)
        w.emit("result_cache", hits=1, misses=0, fast_path=True,
               rows=int(rows))
        w.emit("query_end", status="ok", wall_s=round(wall_s, 6))
    finally:
        w.close()
