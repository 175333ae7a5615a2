"""The program's own spans in a loaded trace. The program enters a
`jax.profiler.TraceAnnotation("srt.<name>")` for every span it opens
(spark_rapids_tpu/profiler/tracing.py), so they sit among the host events of
`run["trace"]`, on the clock of the device's lines, on whatever thread opened
them. A time is the UNION of the named spans' intervals cut to the window
(first `bench.execution` start to last end) over the executions in it:
partitions run on a thread pool, so a sum would exceed the wall clock.

Every reader here returns None where there is nothing to read: no trace, no
device operation in the window (`run["reduced"]` is None, as off the chip), or
a program that opens no `srt.` span (one from before these spans existed).
"""
from benchmarks.harness.tracereduce import (EXECUTION, MODULES, OPS, _clip,
                                            _union)

PREFIX = "srt."
# the spans that only say "a query is running": a device idle inside these
# and inside nothing finer is not attributed to any layer
ENVELOPES = ("srt.query", "srt.admit", "srt.collect")


def window(run):
    """(lo, hi, executions) of the traced slice, or None."""
    if not run["trace"] or not run["reduced"]:
        return None
    runs = [(s, e) for n, s, e in run["trace"]["host"] if n == EXECUTION]
    return (min(s for s, _ in runs), max(e for _, e in runs),
            run["reduced"]["executions"])


def spans(run, wanted):
    """The (start, end) of every `srt.` host event that `wanted(name)` keeps;
    None where there is no window or the trace holds no `srt.` event at all."""
    if not window(run):
        return None
    named = [ev for ev in run["trace"]["host"] if ev[0].startswith(PREFIX)]
    if not named:
        return None
    return [(s, e) for n, s, e in named if wanted(n)]


def covered_ns(intervals, lo, hi):
    return sum(e - s for s, e in _union(_clip(intervals, lo, hi)))


def union_ms(run, *names):
    """Milliseconds an execution spends inside any span of `names`."""
    found = spans(run, lambda n: n in names)
    if found is None:
        return None
    lo, hi, executions = window(run)
    return covered_ns(found, lo, hi) / 1e6 / executions


def self_ms(run, *names):
    """union_ms(names) less what any other `srt.` span covers of it, on any
    thread: the named layers' self time."""
    every = spans(run, lambda n: True)
    if every is None:
        return None
    lo, hi, executions = window(run)
    others = spans(run, lambda n: n not in names)
    # own less others = (own or others) less others
    return ((covered_ns(every, lo, hi) - covered_ns(others, lo, hi))
            / 1e6 / executions)


def count_per_execution(run, name):
    """Spans of `name` that start inside the window, per execution."""
    found = spans(run, lambda n: n == name)
    if found is None:
        return None
    lo, hi, executions = window(run)
    return sum(lo <= s < hi for s, _ in found) / executions


def launches_per_execution(run):
    """Programs the device ran: events of its `XLA Modules` line that start
    inside the window (mean over the device planes), per execution. Every
    launch, the eager jnp ones too; needs no span of the program."""
    w = window(run)
    if not w:
        return None
    lo, hi, executions = w
    per_plane = [sum(lo <= s < hi for _, s, _ in lines.get(MODULES, []))
                 for lines in run["trace"]["devices"].values()]
    if not any(per_plane):
        return None
    return sum(per_plane) / len(per_plane) / executions


def idle_unattributed_pct(run):
    """Of the device's idle time in the window (the gaps of the union of its
    `XLA Ops`), the share whose gap's middle lies inside no `srt.` span finer
    than ENVELOPES: what no layer of the program has put its name to."""
    finer = spans(run, lambda n: n not in ENVELOPES)
    if finer is None:
        return None
    lo, hi, _ = window(run)
    finer = _union(_clip(finer, lo, hi))
    idle = unattributed = 0
    for lines in run["trace"]["devices"].values():
        ops = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        at = 0                           # gaps come in order: one sweep
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            idle += ge - gs
            mid = (gs + ge) // 2
            while at < len(finer) and finer[at][1] <= mid:
                at += 1
            if not (at < len(finer) and finer[at][0] <= mid):
                unattributed += ge - gs
    return 100.0 * unattributed / idle if idle else None
