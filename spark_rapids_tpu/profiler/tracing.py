"""Per-query distributed tracing: spans with context propagation.

The event log (profiler/event_log.py) records WHAT happened; spans
record WHERE the wall clock went once a query fans out across the
service gateway, the AQE stage driver, the compile pool, the
exchange/broadcast map pools and remote executors. One trace per query
(trace_id == query_id), assembled into `trace_span` records in the
query's event log and reduced to latency shares by
profiler/critical_path.py.

TWO SINKS behind the one API. Every span also enters a
`jax.profiler.TraceAnnotation("srt." + name)`, so while a profile is
being taken the program's spans sit on the profiler's clock beside the
device's timeline (whether a profile runs is the only switch; the
annotation is inert otherwise). The dict record below is kept only
under a sampled TraceContext. Spans of the kinds in `PROFILER_ONLY`
(`launch`, `d2h`, `op`, `io`: many per query, meaningful only beside
the device; `d2h` is the kind of `srt.fetch`, because `fetch` is
already the kind of a remote block fetch, a dict-recorded edge) write
no dict record at all, so critical_path.py's vocabulary stays what
docs/observability.md says it is. Back-dated spans
(`record_wait_span`) cannot be annotations and stay dict-only.

Design constraints, in order:

1. CHEAP WHEN OFF. `span()` resolves the active TraceContext with one
   attribute read; an unsampled/disabled trace enters the (inert)
   annotation, yields a shared no-op span and touches nothing else.
   The <3% q6 A/B overhead gate in tests/test_tracing.py holds the
   tracing-ON path to the same bar.
2. ONE TRACE PER QUERY ACROSS PROCESSES. The context is three fields
   (trace_id, span_id, sampled) and rides:
     - `ExecContext.trace` on the query thread,
     - a thread-local for worker threads (`use()` — exchange map pools,
       broadcast builds, the compile pool),
     - the serialized conf dict in cluster RPC task frames
       (`inject_into_conf` / `adopt_from_conf`), so executor-side spans
       parent correctly under the driver's stage span and come home
       with task metrics (cluster/task_metrics.py side channel).
3. CLOCKS. start/end are `time.time_ns()` — CLOCK_REALTIME, comparable
   across the driver and executor processes of one host (the cluster
   runner is single-host by construction). Durations inside one
   process additionally carry the monotonic-derived `dur_ms` so a
   clock step cannot corrupt a span's own length.

Span records are plain dicts (JSON-able, picklable for the task-metric
side channel):

  {trace_id, span_id, parent_id, name, kind, start_ns, end_ns,
   dur_ms, proc, attrs?}

Every engine span MUST be closed via `with span(...)` or a
try/finally around `open_span`/`Span.end` — the tpulint `span-leak`
rule (analysis/lint_rules.py) audits the tree for leaks.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
import zlib
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

__all__ = ["TraceContext", "Span", "PROFILER_ONLY", "PREFIX",
           "start_trace", "current", "use",
           "span", "open_span", "record_span", "drain_trace",
           "record_queue_span", "record_wait_span", "finish",
           "to_wire", "from_wire",
           "inject_into_conf", "adopt_from_conf", "absorb_spans",
           "TRACE_CONF_KEY"]

#: conf-dict key the distributed runner injects the wire context under:
#: executor task functions rebuild TpuSession(conf) from this very dict,
#: so the context crosses the RPC boundary with zero frame changes
TRACE_CONF_KEY = "spark.rapids.tpu.sql.trace.context"

#: every span's name on the profiler's clock is PREFIX + name
PREFIX = "srt."
#: kinds that go to the profiler only and never write a dict record
PROFILER_ONLY = frozenset({"launch", "d2h", "op", "io"})

_SEQ = itertools.count(1)
_TLS = threading.local()

_LOCK = threading.Lock()
_TRACES: Dict[str, List[dict]] = {}     # trace_id -> finished span dicts
#: cap per trace: a runaway span producer must not grow memory without
#: bound; overflow increments the dropped counter instead (the
#: telemetry registry surfaces it)
_MAX_SPANS_PER_TRACE = 4096
_DROPPED = [0]
#: traces already finished on the DRIVER: a straggler span (a
#: background compile outliving its query) must not re-create the
#: trace's buffer — that entry would never be drained again. Bounded
#: ring of recent trace ids; membership drops the span (counted).
_CLOSED: "OrderedDict[str, bool]" = OrderedDict()
_MAX_CLOSED = 512


def _new_span_id() -> str:
    # pid-prefixed so driver and executor processes never collide
    return f"{os.getpid():x}.{next(_SEQ):x}"


class TraceContext:
    """The three propagated fields; immutable by convention."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: Optional[str],
                 sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def __repr__(self):
        return (f"TraceContext({self.trace_id!r}, {self.span_id!r}, "
                f"sampled={self.sampled})")


class Span:
    """One open span. End it exactly once (with-statement or finally);
    ending records the finished dict into the per-trace buffer. As a
    context manager it also points the thread-local context at itself
    for the block."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "kind",
                 "start_ns", "attrs", "_t0", "_done", "_ann", "_restore")

    def __init__(self, trace_id, span_id, parent_id, name, kind, attrs):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self._ann = _Annotation(PREFIX + name, query=trace_id)
        self._ann.__enter__()
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter()
        self._done = False
        self._restore = None

    def __enter__(self):
        self._restore = getattr(_TLS, "ctx", None)
        _TLS.ctx = TraceContext(self.trace_id, self.span_id, True)
        return self

    def __exit__(self, *exc):
        _TLS.ctx = self._restore
        self.end()
        return False

    def set(self, key: str, value) -> None:
        """Attach one attribute (retry counts, byte sizes, fault tags)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        rec = {"trace_id": self.trace_id, "span_id": self.span_id,
               "parent_id": self.parent_id, "name": self.name,
               "kind": self.kind, "start_ns": self.start_ns,
               "end_ns": self.start_ns + int(dur * 1e9),
               "dur_ms": round(dur * 1e3, 4),
               "proc": os.getpid()}
        if self.attrs:
            rec["attrs"] = self.attrs
        record_span(rec)


class _NoopSpan:
    __slots__ = ()

    def set(self, key, value):
        pass

    def end(self):
        pass


_NOOP = _NoopSpan()


class _ProfilerSpan(_NoopSpan):
    """A span that is off-trace or of a PROFILER_ONLY kind: no dict
    record, only the profiler's annotation to end."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann
        ann.__enter__()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def end(self):
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)


# ---------------------------------------------------------------------
# context resolution
# ---------------------------------------------------------------------
def start_trace(query_id: str, conf) -> Optional[TraceContext]:
    """Root TraceContext for a query, or None when tracing is off or
    this query is sampled out. Sampling is DETERMINISTIC on the query
    id (crc32 bucket vs sql.trace.sampleRate) so a retried query and
    its executor fragments agree on the sampling decision without any
    extra coordination."""
    from ..config import TRACE_ENABLED, TRACE_SAMPLE_RATE
    if not conf.get(TRACE_ENABLED):
        return None
    rate = float(conf.get(TRACE_SAMPLE_RATE))
    if rate <= 0.0:
        return None
    if rate < 1.0:
        bucket = zlib.crc32(query_id.encode("utf-8")) % 10000
        if bucket >= rate * 10000:
            return None
    return TraceContext(query_id, None, True)


def current() -> Optional[TraceContext]:
    """The thread's active TraceContext (None off-trace)."""
    return getattr(_TLS, "ctx", None)


@contextmanager
def use(tc: Optional[TraceContext]):
    """Install `tc` as this thread's context for the duration — the
    bridge onto worker threads (pool map tasks, broadcast builds) that
    have no ExecContext of their own."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = tc
    try:
        yield tc
    finally:
        _TLS.ctx = prev


def _resolve(ctx) -> Optional[TraceContext]:
    """TraceContext from an explicit TraceContext / ExecContext-like
    carrier, falling back to the thread-local."""
    if ctx is not None:
        if isinstance(ctx, TraceContext):
            return ctx if ctx.sampled else None
        tc = getattr(ctx, "trace", None)
        if tc is not None:
            return tc if tc.sampled else None
    return getattr(_TLS, "ctx", None)


# ---------------------------------------------------------------------
# span lifecycle
# ---------------------------------------------------------------------
def open_span(name: str, kind: str, ctx=None, **attrs):
    """Open a span without the with-statement (callers that must end it
    in an async callback). MUST be paired with `.end()` in a finally —
    the span-leak lint rule flags anything else. Off-trace (and for a
    PROFILER_ONLY kind) the span records nothing and only ends its
    annotation."""
    if kind in PROFILER_ONLY:
        return _ProfilerSpan(_Annotation(PREFIX + name, **attrs))
    tc = _resolve(ctx)
    if tc is None:
        return _ProfilerSpan(_Annotation(PREFIX + name))
    return Span(tc.trace_id, _new_span_id(), tc.span_id, name, kind,
                attrs or None)


def span(name: str, kind: str, ctx=None, **attrs):
    """`with span(...)`: open/close one span around a block. While the
    block runs, the thread-local context points at this span, so nested
    `span()` calls (and worker threads seeded via `use(current())`)
    parent under it. Off-trace only the profiler's annotation is
    entered. A PROFILER_ONLY kind gets the bare annotation (`attrs`
    become its stats; there is no `.set()`): these are the spans that
    are many per query, and this is a third of the wrapper's cost."""
    if kind in PROFILER_ONLY:
        return _Annotation(PREFIX + name, **attrs)
    return open_span(name, kind, ctx, **attrs)


def record_span(rec: dict) -> None:
    """Append one finished span to its trace buffer (bounded)."""
    with _LOCK:
        if rec["trace_id"] in _CLOSED:
            _DROPPED[0] += 1          # straggler after the query ended
            return
        buf = _TRACES.setdefault(rec["trace_id"], [])
        if len(buf) >= _MAX_SPANS_PER_TRACE:
            _DROPPED[0] += 1
            return
        buf.append(rec)


def record_wait_span(name: str, kind: str, wait_ms, ctx=None,
                     **attrs) -> None:
    """Synthesize a back-dated span for a wait that already happened —
    admission queues, pool-permit waits, retry backoffs measured after
    the fact. One TLS read and out when off-trace."""
    tc = _resolve(ctx)
    if tc is None or not wait_ms or wait_ms <= 0:
        return
    now = time.time_ns()
    rec = {"trace_id": tc.trace_id, "span_id": _new_span_id(),
           "parent_id": tc.span_id, "name": name, "kind": kind,
           "start_ns": now - int(wait_ms * 1e6), "end_ns": now,
           "dur_ms": round(float(wait_ms), 4), "proc": os.getpid()}
    if attrs:
        rec["attrs"] = attrs
    record_span(rec)


def record_queue_span(tc: Optional[TraceContext], wait_ms,
                      pool: Optional[str] = None) -> None:
    """The admission/queue-wait span: by the time the admitted query
    thread runs, the wait already happened, so it is back-dated from
    the handle's measured queue_wait_ms."""
    if tc is None or not tc.sampled:
        return
    kw = {"pool": pool} if pool else {}
    record_wait_span("admission.queue", "queue", wait_ms, ctx=tc, **kw)


def absorb_spans(recs) -> None:
    """Driver-side entry for executor span records that rode home on
    the task-metric side channel: re-buffer them under their trace so
    drain_trace() assembles ONE per-query trace."""
    for rec in recs or ():
        if isinstance(rec, dict) and rec.get("trace_id"):
            record_span(rec)


def drain_trace(trace_id: str, close: bool = True) -> List[dict]:
    """Remove and return the trace's finished spans, start-ordered.

    `close=True` (the driver, at query end) additionally marks the
    trace finished so stragglers are dropped instead of re-creating an
    undrainable buffer. Executors drain with `close=False` — the same
    trace_id keeps accumulating across that query's later tasks."""
    with _LOCK:
        spans = _TRACES.pop(trace_id, [])
        if close:
            _CLOSED[trace_id] = True
            _CLOSED.move_to_end(trace_id)
            while len(_CLOSED) > _MAX_CLOSED:
                _CLOSED.popitem(last=False)
    spans.sort(key=lambda s: s.get("start_ns", 0))
    return spans


def finish(ctx, wall_s=None) -> List[dict]:
    """Close out a query's trace from its ExecContext: end the root
    span, drain the assembled spans, store the critical-path summary on
    `ctx.trace_summary` and feed the per-category share histograms of
    the live telemetry registry. Idempotent; returns the drained spans
    (empty on a later call, off-trace, or for a nested action that has
    no root span of its own)."""
    tc = getattr(ctx, "trace", None)
    rsp = getattr(ctx, "_root_span", None)
    if rsp is None:
        return []
    rsp.end()           # off-trace this ends the annotation alone
    if tc is None:
        return []
    spans = drain_trace(tc.trace_id)
    if not spans:
        return []
    from . import critical_path
    summ = critical_path.summarize(spans, wall_s)
    ctx.trace_summary = summ
    if summ is not None:
        try:
            from . import telemetry
            for c, pct in summ["share_pct"].items():
                telemetry.histogram(
                    f"critical_path_share_pct_{c}").observe(pct)
        except Exception:
            pass
    return spans


def dropped_spans() -> int:
    with _LOCK:
        return _DROPPED[0]


# ---------------------------------------------------------------------
# propagation across the RPC boundary
# ---------------------------------------------------------------------
def to_wire(tc: Optional[TraceContext]) -> Optional[str]:
    if tc is None or not tc.sampled:
        return None
    return f"{tc.trace_id}|{tc.span_id or ''}"


def from_wire(s: Optional[str]) -> Optional[TraceContext]:
    if not s or "|" not in s:
        return None
    trace_id, _, span_id = s.partition("|")
    return TraceContext(trace_id, span_id or None, True)


def inject_into_conf(settings: dict, tc: Optional[TraceContext]) -> dict:
    """Copy of a conf-settings dict with the wire context injected —
    the dict the distributed runner already ships in every task frame.
    Identity when off-trace (no copy, no key)."""
    wire = to_wire(tc)
    if wire is None:
        return settings
    out = dict(settings)
    out[TRACE_CONF_KEY] = wire
    return out


def adopt_from_conf(conf) -> Optional[TraceContext]:
    """Executor-side: rebuild the TraceContext a task frame carried
    (None when the driver ran untraced). Accepts a TpuConf or a plain
    settings dict."""
    d = conf if isinstance(conf, dict) \
        else getattr(conf, "_settings", None)
    if not isinstance(d, dict):
        return None
    return from_wire(d.get(TRACE_CONF_KEY))
