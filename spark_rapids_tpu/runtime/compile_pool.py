"""Background XLA compilation: a bounded pool that moves the compile
tail off the dispatch path.

A cold query compiles far longer than it runs (q4 needs 211 programs
for milliseconds of work). The programs are all known *before* they
are needed —
the planner fixes every stage's program key at launch, and a service
restart knows yesterday's whole key set (runtime/warm_pack.py) — so
compilation is an amortizable, pipelinable cost, not an inline one
(spark-rapids pre-builds cudf kernels per process; Theseus overlaps
every non-compute cost with the pipeline). This pool is the overlap
mechanism:

- **stage-ahead** tasks: at query launch the physical tree's
  `prewarm_programs()` hooks submit downstream stage programs; they
  compile on `tpu-compile-N` daemon threads while upstream stages
  execute (XLA's C++ compiler releases the GIL).
- **speculative** tasks: warm-pack preload at service startup. These
  are admission-aware — a busy hook (wired to the QueryManager's
  running count) defers them while any query is running, so a running
  query's dispatch never competes with speculative compilation. They
  are also per-topology: the pack fingerprint (warm_pack._fingerprint)
  includes the mesh identity, so an 8-device service process preloads
  sharded collective programs (SpmdStageExec / MeshExchangeExec, keyed
  on mesh_topology_key) recorded on the SAME topology, and a pack from
  a different mesh never spends this pool's budget.

The dispatch path NEVER waits on this pool: `CachedProgram.__call__`
compiles inline on a miss exactly as before — a duplicate compile is
accepted over a stall — and `CachedProgram.prewarm` stores only when
the key is still absent. Background failures (including injected
`xla.compile` faults, which fire in prewarm with `background=True`)
are swallowed here and counted
(`program_cache_background_failures`); the query that needed the
program falls back to the sync path and is never affected.

Cancellation is cooperative: tasks carry the submitting query's id,
`cancel_query()` drops its queued-not-started tasks (the service calls
it when a query dies), and `shutdown()` drains the queue and joins the
workers (tests, interpreter exit).
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Callable, List, Optional

from . import lockdep

__all__ = ["CompilePool", "get_pool", "current_pool", "shutdown_pool",
           "set_busy_hook"]


class _Task:
    __slots__ = ("prog", "args_thunk", "speculative", "query_id",
                 "cancelled", "trace")

    def __init__(self, prog, args_thunk, speculative, query_id,
                 trace=None):
        self.prog = prog
        self.args_thunk = args_thunk    # () -> example args (built lazily
        self.speculative = speculative  # on the worker, not the submitter)
        self.query_id = query_id
        self.cancelled = False
        # submitter's TraceContext: background compiles show up in the
        # submitting query's trace (profiler/tracing.py)
        self.trace = trace


class CompilePool:
    """Bounded background compile pool; one per process (get_pool)."""

    def __init__(self, threads: int = 2, queue_cap: int = 256):
        self._lock = lockdep.lock("CompilePool._lock")
        self._cv = threading.Condition(self._lock)
        self._queue: "deque[_Task]" = deque()
        self._queue_cap = max(8, int(queue_cap))
        self._stop = False
        self._busy_hook: Optional[Callable[[], bool]] = None
        self._idle = threading.Event()
        self._idle.set()
        self._active = 0
        self.stats = {"submitted": 0, "compiled": 0, "already_warm": 0,
                      "failed": 0, "cancelled": 0, "dropped_full": 0,
                      "deferred_busy": 0}
        self._threads: List[threading.Thread] = []
        for i in range(max(1, int(threads))):
            t = threading.Thread(target=self._worker, daemon=True,
                                 name=f"tpu-compile-{i}")
            t.start()
            self._threads.append(t)

    # ------------------------------------------------------------------
    def set_busy_hook(self, hook: Optional[Callable[[], bool]]) -> None:
        """`hook() == True` means queries are running: speculative
        tasks wait; stage-ahead tasks (for those very queries) run."""
        # tpulint: allow[unlocked-shared-write] single reference swap; _busy() snapshots into a local before calling
        self._busy_hook = hook

    def _busy(self) -> bool:
        hook = self._busy_hook
        if hook is None:
            return False
        try:
            return bool(hook())
        except Exception:
            return False

    # ------------------------------------------------------------------
    def submit(self, prog, args_thunk: Callable[[], tuple],
               speculative: bool = False,
               query_id: Optional[str] = None) -> bool:
        """Enqueue one prewarm. Never blocks: a full queue drops the
        task (the sync path compiles it later; counted dropped_full)."""
        from ..profiler import tracing
        task = _Task(prog, args_thunk, speculative, query_id,
                     trace=tracing.current())
        with self._cv:
            if self._stop or len(self._queue) >= self._queue_cap:
                self.stats["dropped_full"] += 1
                return False
            self._queue.append(task)
            self.stats["submitted"] += 1
            self._idle.clear()
            self._cv.notify()
        return True

    def cancel_query(self, query_id: Optional[str]) -> int:
        """Drop queued-not-started tasks submitted by `query_id`
        (cooperative: a task already compiling runs to completion —
        the result is cached for the retry)."""
        if query_id is None:
            return 0
        n = 0
        with self._cv:
            for t in self._queue:
                if t.query_id == query_id and not t.cancelled:
                    t.cancelled = True
                    n += 1
            if n:
                self.stats["cancelled"] += n
        return n

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until the queue is empty and workers are idle (tests).
        Returns False on timeout."""
        return self._idle.wait(timeout)

    def shutdown(self) -> None:
        with self._cv:
            self._stop = True
            n = sum(1 for t in self._queue if not t.cancelled)
            self.stats["cancelled"] += n
            self._queue.clear()
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

    # ------------------------------------------------------------------
    def _worker(self) -> None:
        from . import program_cache
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    if not self._active:
                        self._idle.set()
                    self._cv.wait(timeout=0.5)
                if self._stop:
                    if not self._active:
                        self._idle.set()
                    return
                task = self._queue[0]
                if task.speculative and not task.cancelled \
                        and self._busy():
                    # admission-aware: speculative work yields to
                    # running queries. Rotate it to the tail so
                    # stage-ahead tasks behind it still run, and park
                    # briefly so a long-running query cannot spin us
                    self.stats["deferred_busy"] += 1
                    self._queue.rotate(-1)
                    self._cv.wait(timeout=0.05)
                    continue
                self._queue.popleft()
                if task.cancelled:
                    continue
                self._active += 1
            try:
                args = task.args_thunk()
                if args is None:
                    with self._cv:
                        self.stats["already_warm"] += 1
                else:
                    # the span lands in the SUBMITTING query's trace
                    # (task.trace rode along from submit); no-op when
                    # that query ran untraced
                    from ..profiler import tracing
                    with tracing.span("xla.prewarm", "compile",
                                      task.trace, bg=1) as sp:
                        compiled = task.prog.prewarm(args)
                        sp.set("compiled", bool(compiled))
                    with self._cv:
                        self.stats["compiled" if compiled
                                   else "already_warm"] += 1
            except Exception:
                # swallowed by contract: background compilation must
                # never fail a query (the sync path recompiles);
                # injected xla.compile faults land here
                program_cache.note_background_failure()
                with self._cv:
                    self.stats["failed"] += 1
            finally:
                with self._cv:
                    self._active -= 1
                    if not self._queue and not self._active:
                        self._idle.set()


# ---------------------------------------------------------------------
# process-global pool
# ---------------------------------------------------------------------
_pool: Optional[CompilePool] = None
_pool_lock = threading.Lock()
_pending_busy_hook: Optional[Callable[[], bool]] = None


def get_pool(conf) -> Optional[CompilePool]:
    """The process pool, created on first use from `conf`'s thread
    count; None when sql.exec.compilePool.enabled is off (callers skip
    prewarming entirely)."""
    global _pool
    from ..config import COMPILE_POOL_ENABLED, COMPILE_POOL_THREADS
    if not bool(conf.get(COMPILE_POOL_ENABLED)):
        return None
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                _pool = CompilePool(
                    threads=int(conf.get(COMPILE_POOL_THREADS)))
                if _pending_busy_hook is not None:
                    _pool.set_busy_hook(_pending_busy_hook)
    return _pool


def current_pool() -> Optional[CompilePool]:
    """The live pool, if one was ever created — never creates (failure
    paths use this to cancel a dead query's queued prewarms)."""
    return _pool


def set_busy_hook(hook: Optional[Callable[[], bool]]) -> None:
    """Install the admission-awareness hook (the session wires the
    QueryManager's running count here); applies to the live pool and
    to one created later."""
    global _pending_busy_hook
    _pending_busy_hook = hook
    with _pool_lock:
        if _pool is not None:
            _pool.set_busy_hook(hook)


def shutdown_pool() -> None:
    """Tear down the process pool (tests)."""
    global _pool
    with _pool_lock:
        p, _pool = _pool, None
    if p is not None:
        p.shutdown()
