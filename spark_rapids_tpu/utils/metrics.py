"""Operator metrics with levels, analog of GpuMetric
(reference: sql-plugin/.../GpuMetrics.scala:377 ESSENTIAL/MODERATE/DEBUG).

Timer-skew caveat: jax dispatch is ASYNC — by default `timer` measures
the time to *enqueue* device work, not to execute it; execution lands on
whichever downstream operator first blocks (usually the D2H fetch at the
plan root). With `spark.rapids.tpu.sql.metrics.sync` on (ExecContext
passes `sync=True`), the timer joins the device stream before stopping:
it enqueues a trivial op and `block_until_ready`s it, which on an
in-order compute stream waits for everything the timed block dispatched.
That yields debug-grade per-operator execution times at the cost of
pipelining; see docs/observability.md.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

# after the levels: the profiler package, which both of these import,
# imports the levels from here
from ..profiler import tracing  # noqa: E402
from ..runtime import racedep  # noqa: E402

__all__ = ["MetricSet", "ESSENTIAL", "MODERATE", "DEBUG"]


def _stream_barrier():
    """Join the device stream: dispatch a trivial op and block on it.
    Device execution streams are in-order, so this returns only after
    every previously dispatched kernel completes."""
    try:
        import jax
        import jax.numpy as jnp
        # tpulint: allow[block-sync] this IS the sql.metrics.sync gate
        jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
    except Exception:
        pass


class MetricSet:
    """Thread-safe: partitions update operator metrics concurrently."""

    def __init__(self, sync: bool = False, op_id: str = ""):
        from ..runtime import lockdep
        self._values = {}
        self._levels = {}
        self._lock = lockdep.lock("MetricSet._lock")
        self._sync = sync
        # a timed region is a span on the profiler's clock, named by
        # the operator's class: "FusedStageExec@7f.." -> "FusedStageExec."
        self._op_id = op_id
        self._span_prefix = op_id.partition("@")[0] + "." if op_id else ""

    def add(self, name: str, amount, level: int = MODERATE):
        with self._lock:
            racedep.note_access("MetricSet._values", name, write=True)
            self._values[name] = self._values.get(name, 0) + amount
            self._levels[name] = level

    def set(self, name: str, value, level: int = MODERATE):
        with self._lock:
            racedep.note_access("MetricSet._values", name, write=True)
            self._values[name] = value
            self._levels[name] = level

    def get(self, name: str, default=0):
        with self._lock:
            racedep.note_access("MetricSet._values", name)
            return self._values.get(name, default)

    @contextmanager
    def timer(self, name: str, level: int = MODERATE):
        t0 = time.perf_counter()
        try:
            with (tracing.span(self._span_prefix + name, "op",
                               op=self._op_id)
                  if self._span_prefix else nullcontext()):
                yield
        finally:
            if self._sync:
                _stream_barrier()
            self.add(name, time.perf_counter() - t0, level)

    def snapshot(self, max_level: int = DEBUG):
        # iterating _values while a partition worker resizes it raises
        # RuntimeError; snapshot under the same lock add/set hold
        with self._lock:
            racedep.note_access("MetricSet._values")
            return {k: v for k, v in self._values.items()
                    if self._levels.get(k, MODERATE) <= max_level}

    def __repr__(self):
        return f"MetricSet({self._values})"
