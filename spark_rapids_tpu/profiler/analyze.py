"""EXPLAIN ANALYZE renderer: the plan tree annotated with runtime
metrics per node, top time sinks flagged.

(reference: the SQL-UI per-node metric display wired by GpuExec /
GpuMetrics.scala — here rendered as text, since the standalone engine
has no UI process.) Works from the JSON plan tree + lore-keyed metric
dicts of profiler.event_log, so the same renderer serves the local
DataFrame path, the distributed runner's driver-side aggregation, and
the profiling-tool CLI reading an event log after the fact.
"""
from __future__ import annotations

from typing import Dict, Optional

from .event_log import op_time_seconds

__all__ = ["render_analyze", "fmt_bytes"]

_SHUFFLE_BYTE_KEYS = ("shuffleBytesWritten", "shuffleBytesRead",
                      "rawBytes")
_SHUFFLE_PHASE_BYTE_KEYS = (("raw", "shuffleRawBytes"),
                            ("d2h", "shuffleD2HBytes"),
                            ("h2d", "shuffleH2DBytes"))


def fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024
    return f"{n:.1f}GiB"


def render_analyze(tree: dict, metrics_by_lore: Dict[Optional[int], dict],
                   top_n: int = 3, title: Optional[str] = None) -> str:
    """Render the plan tree with per-node rows/batches/op-time/shuffle/
    spill annotations; the `top_n` largest time sinks are flagged with
    their share of total attributed operator time."""
    times = []

    def collect(node):
        m = metrics_by_lore.get(node.get("lore_id")) or {}
        times.append((node.get("lore_id"), op_time_seconds(m)))
        for c in node.get("children", ()):
            collect(c)

    collect(tree)
    total = sum(t for _, t in times)
    sinks = sorted((e for e in times if e[1] > 0), key=lambda e: -e[1])
    rank = {lid: i + 1 for i, (lid, _) in enumerate(sinks[:top_n])}

    lines = [] if title is None else [title]

    def walk(node, indent):
        lid = node.get("lore_id")
        m = metrics_by_lore.get(lid) or {}
        t = op_time_seconds(m)
        line = f"{'  ' * indent}[loreId={lid}] {node.get('describe')}"
        ann = []
        if "numOutputRows" in m:
            ann.append(f"rows={int(m['numOutputRows'])}")
        if "numOutputBatches" in m:
            ann.append(f"batches={int(m['numOutputBatches'])}")
        if t > 0:
            ann.append(f"time={t * 1e3:.1f}ms")
        shuffle = sum(m.get(k, 0) for k in _SHUFFLE_BYTE_KEYS)
        if shuffle:
            # the one-chip exchange's bytes at each host boundary: before
            # the codec, fetched by the map passes, uploaded padded by
            # the reduce side; and the blocks written
            parts = [f"{label}:{fmt_bytes(m[k])}"
                     for label, k in _SHUFFLE_PHASE_BYTE_KEYS if k in m]
            if "shuffleBlocksWritten" in m:
                parts.append(f"blocks:{int(m['shuffleBlocksWritten'])}")
            ann.append(f"shuffle={fmt_bytes(shuffle)}"
                       + ("{" + ", ".join(parts) + "}" if parts else ""))
        if m.get("spillBytes"):
            ann.append(f"spill={fmt_bytes(m['spillBytes'])}")
        if m.get("deviceDecodedChunks"):
            ann.append(f"devDecoded={int(m['deviceDecodedChunks'])}")
        if m.get("hostDecodedChunks"):
            ann.append(f"hostDecoded={int(m['hostDecodedChunks'])}")
        if m.get("decompressBusySecs"):
            ann.append(
                f"decompress={m['decompressBusySecs'] * 1e3:.1f}ms")
        if m.get("prefetchWaitSecs") is not None:
            ann.append(
                f"prefetchWait={m['prefetchWaitSecs'] * 1e3:.1f}ms")
        # per-column device-decode fallback reasons: why this scan (or
        # part of it) still decodes on the host — the printf-free answer
        fb = {k.split(".", 1)[1]: int(v) for k, v in m.items()
              if k.startswith("deviceDecodeFallback.")}
        if fb:
            ann.append("fallback={" + ", ".join(
                f"{k}:{v}" for k, v in sorted(fb.items())) + "}")
        # FusedStage member counters: post-stage live rows per fused
        # child (the per-member selectivity view; members are not plan
        # children, so their rows render on the fused node)
        fr = {k.split(".", 1)[1]: int(v) for k, v in m.items()
              if k.startswith("fusedRows.")}
        if fr:
            ann.append("memberRows={" + ", ".join(
                f"{k}:{v}" for k, v in sorted(fr.items())) + "}")
        if m.get("xlaCompiles") is not None:
            ann.append(f"xlaCompiles={int(m['xlaCompiles'])}")
        if m.get("xlaDispatches") is not None:
            ann.append(f"xlaDispatches={int(m['xlaDispatches'])}")
        if m.get("programCacheHits") is not None:
            ann.append(f"programCacheHits={int(m['programCacheHits'])}")
        if m.get("programCacheMisses") is not None:
            ann.append(
                f"programCacheMisses={int(m['programCacheMisses'])}")
        # compile-tail view: wall ms spent compiling during this action
        # and how many compiles ran off the dispatch path (stage-ahead
        # prewarm / warm-pack preload)
        if m.get("compileMs"):
            ann.append(f"compileMs={float(m['compileMs']):.1f}")
        if m.get("backgroundCompiles"):
            ann.append(
                f"backgroundCompiles={int(m['backgroundCompiles'])}")
        # exchange pipeline (docs/observability.md): parallel-map pool
        # waits, async broadcast overlap, and plan-level reuse hits
        if m.get("mapPoolWaitMs") is not None:
            ann.append(f"mapPoolWaitMs={float(m['mapPoolWaitMs']):.1f}")
        # how the exchange map ordered its rows: 32-bit words that rode
        # the sort by target, columns that still went by a gather
        if m.get("mapSortWords") is not None:
            ann.append(f"mapSortWords={int(m['mapSortWords'])}")
            ann.append("mapGatheredColumns="
                       f"{int(m.get('mapGatheredColumns', 0))}")
        # how the sort-segmented aggregate reduced: 32-bit words of a
        # row that rode its sorts, state and key columns that still went
        # by scatter or gather
        if m.get("aggSortWords") is not None:
            ann.append(f"aggSortWords={int(m['aggSortWords'])}")
            ann.append("aggScatteredColumns="
                       f"{int(m.get('aggScatteredColumns', 0))}")
        # 128-bit decimal arithmetic a row: the expression nodes in the
        # operator's program, the live rows its launches put through them
        if m.get("d128Exprs"):
            ann.append(f"d128Exprs={int(m['d128Exprs'])}")
            ann.append(f"d128Rows={int(m.get('d128Rows', 0))}")
        # 32-bit words of key a row of the join's build side
        if m.get("joinKeyWords") is not None:
            ann.append(f"joinKeyWords={int(m['joinKeyWords'])}")
        # keys of a multi-key join packed into that one word (0: not)
        if m.get("joinPackedKeys") is not None:
            ann.append(f"joinPackedKeys={int(m['joinPackedKeys'])}")
        if m.get("broadcastBuildOverlapMs") is not None:
            ann.append("broadcastBuildOverlapMs="
                       f"{float(m['broadcastBuildOverlapMs']):.1f}")
        if m.get("broadcastTimeoutFallbacks"):
            ann.append("broadcastTimeoutFallbacks="
                       f"{int(m['broadcastTimeoutFallbacks'])}")
        if m.get("exchangeReuseHits"):
            ann.append(
                f"exchangeReuseHits={int(m['exchangeReuseHits'])}")
        # AQE replan decisions (docs/aqe.md): coalesce/skew on the
        # shuffle readers, demotion on the rewritten join, plus the
        # exact per-reduce-partition byte distribution on exchanges
        if m.get("aqePartitionsBefore") is not None:
            ann.append(f"AQEShuffleRead[coalesced "
                       f"{int(m['aqePartitionsBefore'])}"
                       f"→{int(m['aqePartitionsAfter'])}]")
        if m.get("aqeSkewSplits"):
            ann.append(f"aqeSkewSplits={int(m['aqeSkewSplits'])}")
        if m.get("aqeDemotedBuildBytes") is not None:
            ann.append("aqeDemotedToBroadcast="
                       f"{fmt_bytes(m['aqeDemotedBuildBytes'])}")
        # mesh/SPMD stage metrics: rounds dispatched by the round-based
        # exchange, fused one-program stages, collective traffic, and
        # fault-driven degradations back to the round path
        if m.get("meshRounds"):
            ann.append(f"meshRounds={int(m['meshRounds'])}")
        if m.get("spmdStages"):
            ann.append(f"spmdStages={int(m['spmdStages'])}")
        if m.get("collectiveBytes"):
            ann.append(
                f"collectiveBytes={fmt_bytes(m['collectiveBytes'])}")
        if m.get("spmdDegraded"):
            ann.append(f"spmdDegraded={int(m['spmdDegraded'])}")
        if m.get("spmdActiveShards") is not None:
            ann.append(
                f"spmdActiveShards={int(m['spmdActiveShards'])}")
        if m.get("shufflePartitionBytesMax") is not None:
            ann.append(
                "shufflePartitionBytes="
                f"{fmt_bytes(m.get('shufflePartitionBytesMin', 0))}"
                f"/{fmt_bytes(m.get('shufflePartitionBytesMedian', 0))}"
                f"/{fmt_bytes(m['shufflePartitionBytesMax'])}")
        # query-service waits (root node): time queued behind other
        # queries + time blocked on the TpuSemaphore for the chip
        if m.get("queueWaitMs") is not None:
            ann.append(f"queueWaitMs={float(m['queueWaitMs']):.1f}")
        if m.get("semaphoreWaitMs") is not None:
            ann.append(
                f"semaphoreWaitMs={float(m['semaphoreWaitMs']):.1f}")
        if m.get("semaphoreAcquires") is not None:
            ann.append(
                f"semaphoreAcquires={int(m['semaphoreAcquires'])}")
        # critical-path attribution (root node): where the END-TO-END
        # wall clock went, reduced from the query's trace
        # (profiler/critical_path.py) — dominant edge plus every share
        # above the noise floor
        cps = {k.split(".", 1)[1]: float(v) for k, v in m.items()
               if k.startswith("criticalPathShare.")}
        if cps:
            from .critical_path import dominant_of_pct
            dom = dominant_of_pct(cps)
            tops = ", ".join(
                f"{c}:{cps[c]:.0f}%" for c in sorted(
                    cps, key=cps.get, reverse=True)
                if cps[c] >= 1.0)
            ann.append(f"criticalPath={dom} [{tops}]")
        # resource ledger (root node, when SRTPU_LEDGER/conf enabled):
        # staging-lease traffic this action + the global balance sample
        if m.get("ledgerBalanced") is not None:
            parts = []
            if m.get("ledgerLeaseAcquires"):
                parts.append(f"leases={int(m['ledgerLeaseAcquires'])}")
            if m.get("ledgerPeakLeases"):
                parts.append(f"peak={int(m['ledgerPeakLeases'])}")
            parts.append("balanced=" + ("yes" if m["ledgerBalanced"]
                                        else "NO"))
            ann.append("ledger[" + " ".join(parts) + "]")
        if ann:
            line += "  " + " ".join(ann)
        if lid in rank:
            pct = (100.0 * t / total) if total > 0 else 0.0
            line += (f"  <-- time sink #{rank[lid]} "
                     f"({pct:.0f}% of op time)")
        lines.append(line)
        for c in node.get("children", ()):
            walk(c, indent + 1)

    walk(tree, 0)
    if total > 0:
        lines.append(f"total attributed op time: {total * 1e3:.1f}ms")
    return "\n".join(lines)
