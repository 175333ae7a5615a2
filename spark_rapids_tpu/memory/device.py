"""HBM budget manager — the RMM-pool analog.

XLA owns the real allocator and gives no alloc-failure callback
(SURVEY.md §7.3 item 2), so the design is *inverted* from the reference's
reactive RmmSpark interruption: the engine budgets HBM analytically.
Operators reserve estimated bytes before launching a kernel; a failed
reservation (or a caught RESOURCE_EXHAUSTED from XLA) triggers the spill
store, then the retry framework re-executes with spilled/split inputs
(reference: GpuDeviceManager.scala:182, DeviceMemoryEventHandler.scala:36).
"""
from __future__ import annotations

import threading
from typing import Callable, List, Optional

import jax

__all__ = ["DeviceManager", "BudgetExceeded", "device_manager"]


_CPU_BUDGET_BYTES = 12 * (1 << 30)


class BudgetExceeded(Exception):
    """Raised when an HBM reservation cannot be satisfied even after
    spilling everything spillable."""


class DeviceManager:
    def __init__(self, budget_bytes: Optional[int] = None,
                 alloc_fraction: float = 0.85):
        self._lock = threading.RLock()
        self._reserved = 0
        self._spill_hooks: List[Callable[[int], int]] = []
        if budget_bytes is None:
            budget_bytes = self._detect_budget(alloc_fraction)
        self.budget = budget_bytes

    @staticmethod
    def _detect_budget(fraction: float) -> int:
        dev = jax.devices()[0]
        stats = dev.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"] * fraction)
        if dev.platform != "cpu":
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                f"bytes_limit; pass budget_bytes explicitly")
        # XLA:CPU reports no limit: budget host memory as a 12 GiB device
        return int(_CPU_BUDGET_BYTES * fraction)

    # ------------------------------------------------------------------
    def register_spill_hook(self, hook: Callable[[int], int]):
        """hook(bytes_needed) -> bytes_freed; called under pressure."""
        self._spill_hooks.append(hook)

    @property
    def reserved(self) -> int:
        return self._reserved

    def try_reserve(self, nbytes: int, _record: bool = True) -> bool:
        if _record:
            from .diagnostics import record_allocation
            record_allocation()
        with self._lock:
            if self._reserved + nbytes <= self.budget:
                self._reserved += nbytes
                cur = self._reserved
            else:
                return False
        from ..runtime import ledger
        from .diagnostics import record_device_watermark, \
            record_query_bytes
        record_device_watermark(cur)
        record_query_bytes("device", nbytes)
        ledger.note_acquire("device_bytes", nbytes,
                            tag="DeviceManager.try_reserve")
        return True

    def reserve(self, nbytes: int):
        """Reserve, spilling as needed; raises BudgetExceeded if the spill
        store cannot free enough. Coverage records ONCE per logical
        allocation: here at entry, with the spill-retry loop's repeat
        try_reserve attempts unrecorded."""
        from .diagnostics import record_allocation
        record_allocation()
        if self.try_reserve(nbytes, _record=False):
            return
        for hook in self._spill_hooks:
            # recompute the shortfall under the lock on every attempt:
            # concurrent reservations move _reserved between hook calls
            with self._lock:
                needed = nbytes - (self.budget - self._reserved)
            if needed > 0:
                from .diagnostics import record_query_spill
                record_query_spill(needed)
                hook(needed)
            if self.try_reserve(nbytes, _record=False):
                return
        exc = BudgetExceeded(
            f"need {nbytes} bytes, reserved {self._reserved} of "
            f"{self.budget} and spill store exhausted")
        from ..runtime import ledger
        ledger.attach_dump(exc)   # who holds the budget, by thread/query
        raise exc

    def release(self, nbytes: int):
        with self._lock:
            self._reserved = max(0, self._reserved - nbytes)
        from ..runtime import ledger
        from .diagnostics import record_query_bytes
        record_query_bytes("device", -nbytes)
        ledger.note_release("device_bytes", nbytes)

    def trigger_spill(self, nbytes: Optional[int] = None):
        """Ask the spill store to free memory proactively (the retry
        framework's pressure valve between attempts)."""
        need = nbytes if nbytes is not None else max(self.budget // 4, 1)
        for hook in self._spill_hooks:
            hook(need)


_GLOBAL: Optional[DeviceManager] = None
_GLOBAL_LOCK = threading.Lock()


def device_manager(conf=None) -> DeviceManager:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            budget = None
            frac = 0.85
            if conf is not None:
                from ..config import HBM_POOL_BYTES, HBM_POOL_FRACTION
                budget = conf.get(HBM_POOL_BYTES)
                frac = conf.get(HBM_POOL_FRACTION)
            _GLOBAL = DeviceManager(budget, frac)
        return _GLOBAL
