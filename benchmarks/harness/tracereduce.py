"""From a jax profiler trace (.xplane.pb) to device busy time, idle share
and a breakdown. Read with jax's own ProfileData; no other dependency.

A TPU's plane is `/device:TPU:<n>`; its lines `XLA Ops` (one event per
executed HLO op) and `XLA Modules` (one per executed program). Busy is the
union of the op intervals (of the module intervals where a trace has no op
line), cut to the window. The window runs from the first `bench.execution`
annotation's start to the last one's end, all on the profiler's clock.
"""
import glob
import os

OPS, MODULES = "XLA Ops", "XLA Modules"
EXECUTION = "bench.execution"
PHASES = ("bench.build_tree", "bench.to_arrow")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def load(path):
    """{"devices": {plane: {line: [(name, start, end)]}}, "host": [events]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {ln.name: _events(ln) for ln in plane.lines
                                   if ln.name in (OPS, MODULES)}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return {"devices": devices, "host": host}


def reduce(trace):
    """None where the trace holds no device operation inside the window; else
    busy_s (averaged over the device planes), window_s, executions,
    each_execution [[seconds, busy seconds]] where there are 32 or fewer (of
    the first device), device_ops [[name, s]] and idle_gaps [[what the host
    did, s]], the ten largest of each."""
    runs = [(s, e) for n, s, e in trace["host"] if n == EXECUTION]
    if not runs or not trace["devices"]:
        return None
    lo, hi = min(s for s, _ in runs), max(e for _, e in runs)
    busy, per_op, idle, each = [], {}, [], []
    for lines in trace["devices"].values():
        ops = lines.get(OPS) or lines.get(MODULES) or []
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        if len(runs) <= 32 and not each:   # few, long executions: each one
            each = [[(e - s) / 1e9,
                     sum(b - a for a, b in _clip(merged, s, e)) / 1e9]
                    for s, e in sorted(runs)]
        for name, s, e in lines.get(MODULES) or ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                per_op[name] = per_op.get(name, 0) + (ce - cs)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle.extend((gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                    if ge > gs)
    gaps = {}
    for (gs, ge), label in zip(idle, _host_labels(trace["host"], idle)):
        gaps[label] = gaps.get(label, 0) + (ge - gs)
    if not any(busy):
        return None
    n = len(busy)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "executions": len(runs), "each_execution": each,
            "device_ops": top(per_op),
            "idle_gaps": top(gaps)}


def _host_labels(host, gaps):
    """What the host was doing in each idle gap of the device: the
    benchmark's phase, then the innermost other host event that covers the
    gap's middle (the profiler's own names). One sweep over both lists."""
    events = sorted((e for e in host if e[0] != EXECUTION),
                    key=lambda ev: ev[1])
    order = sorted(range(len(gaps)), key=lambda i: sum(gaps[i]))
    labels, active, nxt = [None] * len(gaps), [], 0
    for i in order:
        mid = sum(gaps[i]) // 2
        while nxt < len(events) and events[nxt][1] <= mid:
            active.append(events[nxt])
            nxt += 1
        active = [ev for ev in active if ev[2] > mid]
        phase, inner = "between executions", None
        for name, s, e in active:
            if name in PHASES:
                phase = name
            elif inner is None or e - s < inner[1]:
                inner = (name, e - s)
        labels[i] = phase + (" / " + inner[0] if inner else "")
    return labels
