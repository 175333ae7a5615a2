"""Bounded worker pool machinery shared by the exchange map sides.

(reference: RapidsShuffleThreadedWriter — the multithreaded shuffle
writer runs map tasks on a bounded pool while the GpuSemaphore still
bounds DEVICE admission.) Two pieces live here:

- `resolve_map_threads`: `sql.exec.exchange.mapThreads` -> an actual
  pool width (0 = auto min(4, cores), clamped to the partition count).
- `PermitRider`: device-admission for map workers that does not
  deadlock against the caller's own TpuSemaphore permit.

The deadlock `PermitRider` exists to avoid: the thread that triggers
`_ensure_shuffled` usually already HOLDS a semaphore permit —
`nodes._collect.run_part` acquires around `next(it)`, and advancing
the iterator is exactly what materializes the shuffle. With
`sql.concurrentTpuTasks=1`, map workers blocking on `sem.acquire`
would wait forever on a permit their own caller holds. Worse, with
CHAINED exchanges every real permit can be pinned by other collect
threads that are themselves blocked on this exchange's
materialization lock, so even a pool that rides one permit deadlocks
if the remaining workers block inside `sem.acquire`. Instead, ONE
worker at a time "rides" the caller's already-granted permit and
every other worker polls: grab a real permit only when one is free
(`try_acquire`), otherwise wait briefly for the ride slot. Progress
is guaranteed (worst case the pool serializes on the ridden permit),
and device concurrency never exceeds the configured permits: the
rider slot spends admission the calling task already won.
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager

__all__ = ["resolve_map_threads", "PermitRider"]


def resolve_map_threads(ctx, nparts: int) -> int:
    """Pool width for an exchange map side: conf value, 0 = auto
    (min(4, cpu cores)), clamped to the partition count."""
    from ..config import EXCHANGE_MAP_THREADS
    t = ctx.conf.get(EXCHANGE_MAP_THREADS)
    if t is None or int(t) <= 0:
        t = min(4, os.cpu_count() or 1)
    return max(1, min(int(t), max(nparts, 1)))


class PermitRider:
    """Grants map workers device-step admission (see module docstring).

    Usage per device step (a jitted map program + its fetch):

        with rider.step():
            host = with_retry(batch, map_one)

    Waits on real permits accumulate in `waited_secs` for the
    `mapPoolWaitMs` metric.
    """

    # lockdep resource key for the ride slot: the witness sees it as a
    # distinct class-keyed resource so ride-then-lock vs lock-then-ride
    # inversions across map workers are observable
    RIDE = "PermitRider.ride"

    def __init__(self, sem, priority: int = 0, token=None):
        self._sem = sem
        self._priority = priority
        self._token = token
        self._rider = threading.Semaphore(1)
        self._lock = threading.Lock()
        self._waited = 0.0
        self._riding = None      # thread name currently on the ride slot

    @property
    def waited_secs(self) -> float:
        with self._lock:
            return self._waited

    def debug_state(self) -> dict:
        """Held-state introspection for the lockdep dump."""
        with self._lock:
            return {"riding": self._riding, "waitedSecs": self._waited}

    @contextmanager
    def step(self):
        # Admission loop. Never block indefinitely inside
        # `sem.acquire`: under chained exchanges every real permit can
        # be pinned by collect threads that are themselves blocked on
        # this exchange's materialization lock — waiting for one would
        # deadlock the pool. Instead alternate between the ride slot
        # (the caller's already-granted permit, guaranteed to free up
        # each time the riding worker finishes a step) and an
        # opportunistic non-blocking real permit, so the pool degrades
        # to serial-on-one-permit rather than hanging.
        import time
        t0 = time.perf_counter()

        def _record():
            waited = time.perf_counter() - t0
            with self._lock:
                self._waited += waited
            if waited > 1e-3:
                # admission wait that actually stalled this map step:
                # back-dated pool_wait span in the query's trace (the
                # worker thread was seeded via tracing.use)
                from ..profiler import tracing
                tracing.record_wait_span("exchange.pool_admission",
                                         "pool_wait", waited * 1e3)
            return waited

        from ..runtime import ledger, lockdep

        def _ride():
            with self._lock:
                self._riding = threading.current_thread().name
            lockdep.note_acquired(self.RIDE)
            ledger.note_acquire("ride", tag="PermitRider.step")

        def _unride():
            lockdep.note_released(self.RIDE)
            ledger.note_release("ride")
            with self._lock:
                self._riding = None

        while True:
            if self._rider.acquire(blocking=False):
                _ride()
                try:
                    yield _record()
                finally:
                    _unride()
                    self._rider.release()
                return
            if self._sem.try_acquire():
                try:
                    yield _record()
                finally:
                    self._sem.release()
                return
            if self._rider.acquire(timeout=0.05):
                _ride()
                try:
                    yield _record()
                finally:
                    _unride()
                    self._rider.release()
                return
            if self._token is not None:
                self._token.check()
