"""Per-query lifecycle: handles, cooperative cancellation, deadlines.

The multi-tenant serving layer the reference gets from Spark itself
(SparkContext job groups + the Thrift server's session/operation
lifecycle): every action becomes a `QueryHandle` walking
QUEUED -> ADMITTED -> RUNNING -> {FINISHED, FAILED, CANCELLED,
TIMED_OUT}, admission is arbitrated by the fair-share scheduler
(service/scheduler.py), and interruption is COOPERATIVE — a
`CancelToken` rides the query's `ExecContext` and every batch loop,
fragment dispatch, and semaphore wait polls it (`ctx.check_cancel()`,
enforced by the `ctx-cancel` lint rule), so a cancel lands at the next
batch boundary instead of killing threads mid-kernel.

Wall-clock deadlines (`sql.service.queryTimeoutSecs`) are just a
pre-armed cancel: the token carries an absolute monotonic deadline and
`check()` trips it exactly like an explicit `cancel()`, including while
the query is still queued.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

__all__ = ["QueryState", "QueryCancelled", "QueryTimedOut", "CancelToken",
           "QueryHandle", "QueryManager", "current_query_id"]


class QueryState:
    QUEUED = "QUEUED"
    ADMITTED = "ADMITTED"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"
    TERMINAL = frozenset({FINISHED, FAILED, CANCELLED, TIMED_OUT})


class QueryCancelled(RuntimeError):
    """Raised at a cooperative checkpoint after CancelToken.cancel()."""

    def __init__(self, query_id: str = "?", reason: str = "cancelled"):
        super().__init__(f"query {query_id} {reason}")
        self.query_id = query_id
        self.reason = reason


class QueryTimedOut(QueryCancelled):
    """The query's wall-clock deadline passed (queue time included)."""

    def __init__(self, query_id: str = "?", timeout_secs: float = 0.0):
        super().__init__(query_id,
                         f"exceeded deadline ({timeout_secs:g}s)")
        self.timeout_secs = timeout_secs


class CancelToken:
    """Cheap cooperative interruption flag + optional deadline.

    `check()` is called per batch in hot loops, so the fast path is one
    attribute read; the deadline compare only runs while a deadline is
    armed."""

    __slots__ = ("query_id", "deadline", "timeout_secs", "_cancelled",
                 "_reason")

    def __init__(self, query_id: str = "?",
                 timeout_secs: Optional[float] = None):
        self.query_id = query_id
        self.timeout_secs = timeout_secs or 0.0
        self.deadline = (time.monotonic() + timeout_secs
                         if timeout_secs else None)
        self._cancelled = False
        self._reason = "cancelled"

    def cancel(self, reason: str = "cancelled"):
        self._reason = reason
        # tpulint: allow[unlocked-shared-write] monotonic flag set before read by design: check() runs per batch and must stay one attr read
        self._cancelled = True

    def cancelled(self) -> bool:
        if self._cancelled:
            return True
        if self.deadline is not None and time.monotonic() > self.deadline:
            return True
        return False

    def check(self):
        """Raise QueryCancelled/QueryTimedOut when tripped; else no-op."""
        if self._cancelled:
            raise QueryCancelled(self.query_id, self._reason)
        if self.deadline is not None and time.monotonic() > self.deadline:
            e = QueryTimedOut(self.query_id, self.timeout_secs)
            # a deadline kill is where PR 8's deadlocks used to surface
            # as bare timeouts: attach the all-threads held-resource
            # dump so the exception (and event log) says WHO was stuck,
            # plus the resource ledger's outstanding-holders table (who
            # still holds leases/permits/handles, on which thread)
            from ..runtime import ledger, lockdep
            lockdep.attach_dump(e)
            ledger.attach_dump(e)
            raise e


class QueryHandle:
    """One submitted query: identity, lifecycle state, result rendezvous."""

    def __init__(self, query_id: str, pool: str, token: CancelToken,
                 action: str = "", estimate=(0, 0)):
        self.query_id = query_id
        self.pool = pool
        self.token = token
        self.action = action
        # (device_bytes, host_bytes) admission estimate from the plan
        self.estimate = estimate
        self.state = QueryState.QUEUED
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._result = None
        self._done = threading.Event()
        self._admitted = threading.Event()
        # scheduler bookkeeping: FIFO sequence within the pool
        self._seq = 0
        self._manager: Optional["QueryManager"] = None

    # -- caller surface -------------------------------------------------
    @property
    def queue_wait_ms(self) -> float:
        """Milliseconds spent QUEUED before admission (or until now /
        until death-in-queue)."""
        end = self.admitted_at
        if end is None:
            end = self.finished_at if self.finished_at is not None \
                else time.monotonic()
        return max(0.0, (end - self.submitted_at) * 1e3)

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(f"query {self.query_id} still "
                               f"{self.state} after {timeout}s")
        if self.error is not None:
            raise self.error
        return self._result

    def cancel(self, reason: str = "cancelled") -> bool:
        mgr = self._manager
        if mgr is not None:
            return mgr.cancel(self, reason)
        self.token.cancel(reason)
        return True

    def status(self) -> dict:
        return {"query_id": self.query_id, "pool": self.pool,
                "state": self.state, "action": self.action,
                "queue_wait_ms": round(self.queue_wait_ms, 3),
                "error": (f"{type(self.error).__name__}: {self.error}"
                          if self.error is not None else None)}

    def __repr__(self):
        return f"QueryHandle({self.query_id}, {self.state})"


# query-id attribution for memory managers: reserve()/release() read
# this to tag reservations without threading a ctx through every call
# site (see memory/diagnostics.py query attribution)
_TLS = threading.local()


def current_query_id() -> Optional[str]:
    return getattr(_TLS, "query_id", None)


class _query_scope:
    """Tags the dynamic extent of a query's execution on this thread."""

    def __init__(self, query_id: str):
        self.query_id = query_id

    def __enter__(self):
        self._prev = getattr(_TLS, "query_id", None)
        _TLS.query_id = self.query_id
        return self

    def __exit__(self, *exc):
        _TLS.query_id = self._prev
        return False


class QueryManager:
    """Admission + lifecycle arbiter for one engine process.

    Synchronous actions (`DataFrame.to_arrow` etc.) run on the CALLER's
    thread: `open_query()` blocks until the scheduler grants admission,
    the caller executes, then `close_query()` releases the grant. Async
    submissions (`submit()`, used by the gateway) get a thread that walks the same path. Either way the
    scheduler fully decides who runs: grants are handed out in `_pump()`
    under one lock whenever a slot or admitted memory frees up."""

    def __init__(self, conf=None):
        from ..config import (SERVICE_MAX_CONCURRENT, TpuConf)
        self.conf = conf or TpuConf()
        from .scheduler import FairScheduler
        from ..runtime import lockdep
        self._lock = lockdep.lock("QueryManager._lock")
        self._cond = threading.Condition(self._lock)
        self.scheduler = FairScheduler(self.conf)
        self.max_concurrent = max(1, int(
            self.conf.get(SERVICE_MAX_CONCURRENT)))
        self._running = 0
        self._seq = 0
        self._queries = {}  # query_id -> handle (bounded: pruned on close)
        self.stats = {"submitted": 0, "admitted": 0, "finished": 0,
                      "failed": 0, "cancelled": 0, "timed_out": 0,
                      "queued_peak": 0, "cache_fast_path": 0}
        # live-telemetry pull gauges: sampled at scrape time, so the
        # admission path itself carries zero instrumentation cost
        try:
            from ..profiler import telemetry
            telemetry.register_gauge_fn(
                "service",
                lambda: {"running": self._running,
                         "queued": self.scheduler.queued_count()})
        except Exception:
            pass

    # -- submission -----------------------------------------------------
    def _new_handle(self, plan=None, conf=None, action: str = "",
                    pool: Optional[str] = None,
                    timeout: Optional[float] = None,
                    estimate=None) -> QueryHandle:
        from ..config import SERVICE_POOL, SERVICE_QUERY_TIMEOUT_SECS
        from ..profiler.event_log import next_query_id
        conf = conf or self.conf
        if timeout is None:
            timeout = float(conf.get(SERVICE_QUERY_TIMEOUT_SECS)) or None
        if pool is None:
            pool = str(conf.get(SERVICE_POOL))
        qid = next_query_id()
        if estimate is None:
            from .scheduler import estimate_plan_memory
            estimate = estimate_plan_memory(plan, conf)
        h = QueryHandle(qid, pool, CancelToken(qid, timeout),
                        action=action, estimate=estimate)
        h._manager = self
        return h

    def open_query(self, plan=None, conf=None, action: str = "",
                   pool: Optional[str] = None,
                   timeout: Optional[float] = None,
                   estimate=None) -> QueryHandle:
        """Enqueue and BLOCK until admitted. Returns the handle in
        RUNNING state; the caller must pair with close_query(). Raises
        QueryCancelled/QueryTimedOut when the query dies in the queue."""
        h = self._new_handle(plan, conf, action, pool, timeout, estimate)
        self._enqueue(h)
        self._await_admission(h)
        return h

    def submit(self, fn, plan=None, conf=None, action: str = "",
               pool: Optional[str] = None,
               timeout: Optional[float] = None,
               estimate=None) -> QueryHandle:
        """Async submission: `fn(handle)` runs on a service thread once
        admitted; the result/exception lands on the returned handle."""
        h = self._new_handle(plan, conf, action, pool, timeout, estimate)
        self._enqueue(h)

        def _worker():
            try:
                self._await_admission(h)
            except QueryCancelled:
                return  # closed out by the queue sweep already
            try:
                out = fn(h)
            except BaseException as e:  # noqa: BLE001 — recorded on handle
                self.close_query(h, error=e)
            else:
                self.close_query(h, result=out)

        t = threading.Thread(target=_worker, daemon=True,
                             name=f"tpu-svc-query-{h.query_id}")
        t.start()
        return h

    def _enqueue(self, h: QueryHandle):
        with self._cond:
            self._seq += 1
            h._seq = self._seq
            self._queries[h.query_id] = h
            self.scheduler.offer(h)
            self.stats["submitted"] += 1
            self.stats["queued_peak"] = max(self.stats["queued_peak"],
                                            self.scheduler.queued_count())
            self._pump_locked()

    def _await_admission(self, h: QueryHandle):
        """Block until the scheduler grants this handle (marking it
        RUNNING) or its token trips in the queue."""
        while True:
            if h._admitted.wait(timeout=0.05):
                with self._cond:
                    h.state = QueryState.RUNNING
                return
            if h.token.cancelled():
                with self._cond:
                    if h._admitted.is_set():
                        h.state = QueryState.RUNNING
                        return
                    self.scheduler.remove(h)
                try:
                    h.token.check()
                    raise QueryCancelled(h.query_id)  # pragma: no cover
                except QueryCancelled as e:
                    self._finalize(h, error=e)
                    raise

    def fast_path(self, plan=None, conf=None, action: str = "",
                  pool: Optional[str] = None, result=None) -> QueryHandle:
        """Answer a query from the result cache WITHOUT consuming an
        admission slot: no enqueue, no scheduler offer, no wait — the
        whole point of the cache fast path is that a hit must not sit
        behind admitted queries. Still metered: the handle counts in
        submitted/finished plus the cache_fast_path counter, and the
        caller still event-logs it (result_cache record)."""
        h = self._new_handle(plan, conf, action, pool, None,
                             estimate=(0, 0))
        with self._cond:
            self._seq += 1
            h._seq = self._seq
            self.stats["submitted"] += 1
            self.stats["cache_fast_path"] += 1
        h.admitted_at = h.submitted_at        # zero queue wait
        self._finalize(h, result=result)      # admitted=False: no slot
        return h

    # -- completion -----------------------------------------------------
    def close_query(self, h: QueryHandle, result=None, error=None):
        """Release the admission grant and publish the outcome."""
        self._finalize(h, result=result, error=error, admitted=True)

    def _finalize(self, h: QueryHandle, result=None, error=None,
                  admitted: bool = False):
        with self._cond:
            if h.state in QueryState.TERMINAL:
                return
            h.finished_at = time.monotonic()
            if error is None:
                h.state = QueryState.FINISHED
                self.stats["finished"] += 1
            elif isinstance(error, QueryTimedOut):
                h.state = QueryState.TIMED_OUT
                self.stats["timed_out"] += 1
            elif isinstance(error, QueryCancelled):
                h.state = QueryState.CANCELLED
                self.stats["cancelled"] += 1
            else:
                h.state = QueryState.FAILED
                self.stats["failed"] += 1
            h.error = error
            h._result = result
            if admitted:
                self._running -= 1
                self.scheduler.release(h)
            self._queries.pop(h.query_id, None)
            self._pump_locked()
            self._cond.notify_all()
        # live telemetry: latency by terminal state + queue wait (the
        # event log is per-query and post-hoc; the registry is what the
        # gateway's `metrics` verb scrapes while the service runs)
        try:
            from ..config import TELEMETRY_ENABLED
            if self.conf.get(TELEMETRY_ENABLED):
                from ..profiler import telemetry
                st_ = h.state.lower()
                telemetry.counter(f"queries_{st_}").inc()
                telemetry.histogram("queue_wait_ms").observe(
                    h.queue_wait_ms)
                if h.finished_at is not None:
                    telemetry.histogram(
                        f"query_latency_ms_{st_}").observe(
                        (h.finished_at - h.submitted_at) * 1e3)
        except Exception:
            pass
        # drop the query's memory-attribution record (bounded bookkeeping)
        try:
            from ..memory.diagnostics import reset_query_attribution
            reset_query_attribution(h.query_id)
        except Exception:
            pass
        h._done.set()
        # resource-ledger balance witness: EVERY terminal state —
        # FINISHED, CANCELLED, TIMED_OUT alike — must leave the query's
        # owner-scoped resources (leases, permits, ride slots) balanced.
        # A clean finish with a leak raises to the caller; on an error
        # path the finding is recorded but must not mask the original
        # error.
        from ..runtime import ledger
        try:
            ledger.note_query_end(h.query_id, h.state)
        except ledger.ResourceLeakError:
            if error is None:
                raise

    # -- cancellation ---------------------------------------------------
    def cancel(self, handle_or_id, reason: str = "cancelled") -> bool:
        """Cancel by handle or query_id. Queued queries die immediately;
        running queries get their token tripped and die at the next
        cooperative checkpoint."""
        h = handle_or_id
        if isinstance(handle_or_id, str):
            with self._lock:
                h = self._queries.get(handle_or_id)
            if h is None:
                return False
        if h.state in QueryState.TERMINAL:
            return False
        h.token.cancel(reason)
        with self._cond:
            queued = h.state == QueryState.QUEUED and \
                not h._admitted.is_set()
            if queued:
                self.scheduler.remove(h)
        if queued:
            self._finalize(h, error=QueryCancelled(h.query_id, reason))
        return True

    def get(self, query_id: str) -> Optional[QueryHandle]:
        with self._lock:
            return self._queries.get(query_id)

    # -- scheduling pump ------------------------------------------------
    def _pump_locked(self):
        """Grant admission while slots and admitted-memory budget allow
        (called under self._lock whenever the picture changes)."""
        while self._running < self.max_concurrent:
            # sweep queued queries whose deadline already passed: their
            # waiter thread will observe the tripped token and finalize
            h = self.scheduler.next_ready()
            if h is None:
                break
            self._running += 1
            h.admitted_at = time.monotonic()
            h.state = QueryState.ADMITTED
            self.stats["admitted"] += 1
            h._admitted.set()

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
            out["running"] = self._running
            out["queued"] = self.scheduler.queued_count()
            return out
