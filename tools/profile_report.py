#!/usr/bin/env python
"""Profiling Tool CLI (the spark-rapids user-tools Profiling Tool analog):
post-process query event logs into per-operator breakdowns, and diff two
runs to attribute a regression to the operator that got slower.

Usage:
    # per-query operator breakdown of one or more logs
    python tools/profile_report.py /tmp/srtpu-events/query-123-0.jsonl

    # every log in a directory
    python tools/profile_report.py /tmp/srtpu-events

    # A/B regression attribution: which operator got slower in B?
    # (when both logs carry traces, a critical-path delta row names
    # the edge category whose share grew the most)
    python tools/profile_report.py --diff a.jsonl b.jsonl

    # per-query trace waterfall + critical-path share table
    python tools/profile_report.py --trace /tmp/srtpu-events/query-123-0.jsonl

Inputs: per-query JSONL event logs written by the engine
(`spark.rapids.tpu.sql.eventLog.enabled`, see docs/observability.md).
Operators are keyed `lore_id:name` — stable for
the same plan across runs and across executor processes — so the diff
lines up operators even when absolute times moved.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from spark_rapids_tpu.profiler import critical_path  # noqa: E402
from spark_rapids_tpu.profiler.analyze import fmt_bytes, render_analyze  # noqa: E402
from spark_rapids_tpu.profiler.event_log import (  # noqa: E402
    aggregate_ops, op_time_seconds, read_event_log)


load_events = read_event_log


def _expand(paths: List[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(
                os.path.join(p, f) for f in os.listdir(p)
                if f.endswith(".jsonl")))
        else:
            out.append(p)
    return out


def _ops_of(events: List[dict]) -> List[dict]:
    recs = []
    for e in events:
        if e.get("event") == "op_metrics":
            recs.extend(e.get("ops") or [])
    return recs


def report(events: List[dict], top: int = 0) -> str:
    """Per-query operator breakdown: the annotated plan tree when a plan
    event exists, else a flat time-sorted table."""
    by_query: Dict[str, List[dict]] = {}
    for e in events:
        by_query.setdefault(e.get("query_id", "?"), []).append(e)
    lines = []
    for qid, evs in by_query.items():
        start = next((e for e in evs if e["event"] == "query_start"), {})
        end = next((e for e in evs if e["event"] == "query_end"), {})
        hdr = f"== query {qid}"
        if start.get("action"):
            hdr += f" (action={start['action']}"
            if end.get("wall_s") is not None:
                hdr += f", wall {end['wall_s'] * 1e3:.0f}ms"
            hdr += f", status={end.get('status', '?')})"
        lines.append(hdr + " ==")
        plan = next((e["plan"] for e in evs if e["event"] == "plan"),
                    None)
        ops = _ops_of(evs)
        agg = aggregate_ops(ops)
        # mesh/SPMD summary: fused one-program stages vs round-based
        # exchange rounds, collective traffic, and fault degradations
        mesh = {k: 0 for k in ("spmdStages", "meshRounds",
                               "collectiveBytes", "spmdDegraded")}
        for r in agg.values():
            for k in mesh:
                mesh[k] += int(r["metrics"].get(k) or 0)
        if any(mesh.values()):
            lines.append(
                f"  mesh: {mesh['spmdStages']} spmd stage(s), "
                f"{mesh['meshRounds']} exchange round(s), "
                f"{fmt_bytes(mesh['collectiveBytes'])} collective"
                + (f", {mesh['spmdDegraded']} degraded to round-based"
                   if mesh["spmdDegraded"] else ""))
        if plan is not None:
            by_lore = {v["lore_id"]: v["metrics"] for v in agg.values()}
            lines.append(render_analyze(plan, by_lore))
        else:
            rows = sorted(agg.values(),
                          key=lambda r: -op_time_seconds(r["metrics"]))
            if top:
                rows = rows[:top]
            for r in rows:
                m = r["metrics"]
                t = op_time_seconds(m)
                extra = ""
                if m.get("numOutputRows") is not None:
                    extra = f"  rows={int(m['numOutputRows'])}"
                lines.append(f"  {t * 1e3:9.1f}ms  [loreId="
                             f"{r['lore_id']}] {r['describe']}{extra}")
        for e in evs:
            if e["event"] == "stage_complete":
                sb = e.get("shuffle_bytes")
                lines.append(
                    f"  stage {e.get('stage')}: wall "
                    f"{e.get('wall_s', 0) * 1e3:.0f}ms"
                    + (f", shuffle {fmt_bytes(sb)}"
                       if sb is not None else ""))
            elif e["event"] == "fetch_retry":
                lines.append(f"  FETCH RETRY pid={e.get('pid')} "
                             f"addr={e.get('addr')}")
            elif e["event"] == "aqe_replan":
                decs = e.get("decisions") or []
                parts = []
                for d in decs:
                    if d.get("rule") == "demote_broadcast_join":
                        parts.append(
                            ("demoted mesh join lore "
                             if d.get("mesh") else "demoted join lore ")
                            + f"{d.get('join_lore')} to broadcast "
                            f"({fmt_bytes(d.get('build_bytes', 0))} "
                            f"build, lores {d.get('old_lores')}"
                            f"→{d.get('new_lores')})")
                    elif d.get("rule") == "mesh_reshard":
                        parts.append(
                            f"resharded spmd stage lore "
                            f"{d.get('stage_lore')} "
                            f"{d.get('devices')}→{d.get('active')} "
                            f"active shards "
                            f"({fmt_bytes(d.get('staged_bytes', 0))} "
                            f"staged)")
                    else:
                        seg = (f"shuffle read "
                               f"{d.get('partitions_before')}"
                               f"→{d.get('partitions_after')} tasks")
                        if d.get("split_slices"):
                            seg += (f", {d.get('skewed_partitions')} "
                                    f"skewed→{d.get('split_slices')} "
                                    f"slices")
                        parts.append(seg)
                lines.append(
                    f"  aqe: {len(decs)} decision(s): "
                    + "; ".join(parts))
            elif e["event"] == "watermarks":
                lines.append(
                    f"  watermarks: device peak "
                    f"{fmt_bytes(e.get('devicePeakBytes', 0))}, host "
                    f"peak {fmt_bytes(e.get('hostPeakBytes', 0))}")
            elif e["event"] == "xla_compile" and (
                    e.get("compiles")
                    or e.get("program_cache_hits")
                    or e.get("program_cache_misses")):
                line = (f"  xla: {int(e.get('compiles', 0))} compiles, "
                        f"{e.get('compile_secs', 0):.2f}s compiling, "
                        f"{int(e.get('cache_hits', 0))} "
                        f"persistent-cache hits")
                if e.get("program_cache_hits") is not None \
                        or e.get("program_cache_misses") is not None:
                    line += (f"; program cache "
                             f"{int(e.get('program_cache_hits', 0))} "
                             f"hits / "
                             f"{int(e.get('program_cache_misses', 0))} "
                             f"misses / "
                             f"{int(e.get('program_cache_evictions', 0))}"
                             f" evictions")
                lines.append(line)
            elif e["event"] == "result_cache" and (
                    e.get("hits") or e.get("misses")
                    or e.get("fragment_hits") or e.get("stores")):
                line = (f"  result cache: {int(e.get('hits', 0))} hits / "
                        f"{int(e.get('misses', 0))} misses, "
                        f"{int(e.get('fragment_hits', 0))} fragment hits, "
                        f"{int(e.get('stores', 0))} stores")
                if e.get("fast_path"):
                    line += " [fast path]"
                if e.get("evictions") or e.get("invalidations"):
                    line += (f"; {int(e.get('evictions', 0))} evictions, "
                             f"{int(e.get('invalidations', 0))} "
                             f"invalidation events")
                if e.get("bytes") is not None:
                    line += (f"; resident "
                             f"{fmt_bytes(e.get('bytes', 0))} in "
                             f"{int(e.get('entries', 0))} entries")
                lines.append(line)
            elif e["event"] == "fleet" and (
                    e.get("peer_hits") or e.get("peer_misses")
                    or e.get("publishes") or e.get("inv_broadcasts")
                    or e.get("warm_pulls")):
                line = (f"  fleet: {int(e.get('peer_hits', 0))} peer "
                        f"hits / {int(e.get('peer_misses', 0))} peer "
                        f"misses, {int(e.get('publishes', 0))} "
                        f"published")
                bad = (int(e.get("peer_fetch_failures", 0)),
                       int(e.get("peer_stale_rejected", 0)))
                if any(bad):
                    line += (f"; {bad[0]} fetch failures, "
                             f"{bad[1]} stale rejected")
                if e.get("inv_broadcasts"):
                    line += (f"; {int(e.get('inv_broadcasts', 0))} "
                             f"invalidation broadcasts "
                             f"({int(e.get('inv_broadcast_failures', 0))}"
                             f" undelivered)")
                if e.get("warm_pulls"):
                    line += f"; warm state pulled"
                if e.get("export_bytes") is not None:
                    line += (f"; exporting "
                             f"{fmt_bytes(e.get('export_bytes', 0))} in "
                             f"{int(e.get('export_entries', 0))} "
                             f"entries to "
                             f"{int(e.get('peers_live', 0))} live peers")
                lines.append(line)
        lines.append("")
    return "\n".join(lines)


def _trace_spans_of(events: List[dict]) -> List[dict]:
    return [e for e in events if e.get("event") == "trace_span"]


def _trace_summary_of(events: List[dict]) -> dict | None:
    """The query's critical-path summary: the emitted trace_summary
    record when present, else recomputed from the trace_span records."""
    s = next((e for e in events if e.get("event") == "trace_summary"),
             None)
    if s is not None:
        return s
    spans = _trace_spans_of(events)
    return critical_path.summarize(spans) if spans else None


def trace_report(events: List[dict], max_rows: int = 60) -> str:
    """Per-query trace waterfall + critical-path share table."""
    by_query: Dict[str, List[dict]] = {}
    for e in events:
        by_query.setdefault(e.get("query_id", "?"), []).append(e)
    lines = []
    for qid, evs in by_query.items():
        spans = _trace_spans_of(evs)
        if not spans:
            continue
        lines.append(f"== trace {qid} ({len(spans)} spans) ==")
        lines.append(critical_path.render_waterfall(
            spans, max_rows=max_rows))
        summ = _trace_summary_of(evs)
        if summ:
            shares = summ.get("shares") or {}
            pct = summ.get("share_pct") or {}
            lines.append("")
            lines.append(f"  {'edge':<14} {'time':>10} {'share':>7}")
            for c in critical_path.CATEGORIES:
                ms = shares.get(c, 0.0)
                if ms <= 0:
                    continue
                lines.append(f"  {c:<14} {ms:9.1f}ms "
                             f"{pct.get(c, 0.0):6.1f}%")
            lines.append(f"  {'total':<14} "
                         f"{summ.get('total_ms', 0.0):9.1f}ms")
            lines.append(f"  critical path: {summ.get('dominant')} "
                         f"({summ.get('dominant_pct', 0.0):.1f}%)")
        lines.append("")
    if not lines:
        return ("(no trace_span records — run with "
                "spark.rapids.tpu.sql.trace.enabled=true)")
    return "\n".join(lines)


def diff_ops(a_events: List[dict], b_events: List[dict]) -> List[dict]:
    """A/B regression attribution: per `lore_id:name` operator key, the
    op-time delta B-A, sorted worst regression first. The top entry is
    'which operator got slower'."""
    a = aggregate_ops(_ops_of(a_events))
    b = aggregate_ops(_ops_of(b_events))
    out = []
    for key in sorted(set(a) | set(b)):
        ta = op_time_seconds((a.get(key) or {}).get("metrics") or {})
        tb = op_time_seconds((b.get(key) or {}).get("metrics") or {})
        rec = a.get(key) or b.get(key)
        out.append({"key": key, "name": rec.get("name"),
                    "describe": rec.get("describe"),
                    "a_time_s": round(ta, 6), "b_time_s": round(tb, 6),
                    "delta_s": round(tb - ta, 6),
                    "ratio": round(tb / ta, 3) if ta > 0 else None})
    out.sort(key=lambda r: -r["delta_s"])
    return out


def diff_report(a_events: List[dict], b_events: List[dict],
                top: int = 10) -> str:
    rows = diff_ops(a_events, b_events)
    lines = ["== A/B operator regression attribution (B - A, worst "
             "first) ==",
             f"{'delta':>10} {'A':>9} {'B':>9} {'ratio':>7}  operator"]
    for r in rows[:top] if top else rows:
        ratio = f"{r['ratio']:.2f}x" if r["ratio"] else "new"
        lines.append(f"{r['delta_s'] * 1e3:+9.1f}ms "
                     f"{r['a_time_s'] * 1e3:8.1f}ms "
                     f"{r['b_time_s'] * 1e3:8.1f}ms {ratio:>7}  "
                     f"[{r['key']}] {r['describe']}")
    regressed = [r for r in rows if r["delta_s"] > 0]
    if regressed:
        w = regressed[0]
        lines.append(f"most regressed operator: [{w['key']}] "
                     f"{w['describe']} "
                     f"(+{w['delta_s'] * 1e3:.1f}ms)")
    # critical-path delta: when both runs carry traces, name the edge
    # category whose absolute share grew the most — "the query got
    # slower because it now waits on X", one level above operators
    sa = _trace_summary_of(a_events)
    sb = _trace_summary_of(b_events)
    if sa and sb:
        da = sa.get("shares") or {}
        db = sb.get("shares") or {}
        deltas = {c: db.get(c, 0.0) - da.get(c, 0.0)
                  for c in critical_path.CATEGORIES}
        worst = max(deltas, key=lambda c: deltas[c])
        lines.append(
            f"critical path: A={sa.get('dominant')} "
            f"({sa.get('dominant_pct', 0.0):.1f}%), "
            f"B={sb.get('dominant')} "
            f"({sb.get('dominant_pct', 0.0):.1f}%); "
            f"largest share growth: {worst} "
            f"({deltas[worst]:+.1f}ms)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Post-process query event logs into per-operator "
                    "breakdowns and A/B diffs.")
    ap.add_argument("paths", nargs="+",
                    help="event-log .jsonl files or directories of them")
    ap.add_argument("--diff", action="store_true",
                    help="treat the two paths as runs A and B and "
                         "attribute the regression")
    ap.add_argument("--trace", action="store_true",
                    help="render the per-query span waterfall and "
                         "critical-path share table instead of the "
                         "operator breakdown")
    ap.add_argument("--top", type=int, default=10,
                    help="rows to show in diff / flat listings")
    args = ap.parse_args(argv)
    paths = _expand(args.paths)
    if args.trace:
        for p in paths:
            print(trace_report(load_events(p)))
        return 0
    if args.diff:
        if len(paths) != 2:
            ap.error("--diff needs exactly two logs (A and B)")
        print(diff_report(load_events(paths[0]), load_events(paths[1]),
                          args.top))
        return 0
    for p in paths:
        print(report(load_events(p), args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
