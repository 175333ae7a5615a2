"""Deterministic, seeded fault injection for the engine's own failure
paths.

The reference ships a CUDA fault-injection tool (spark-rapids-jni) so
the plugin's OOM-retry / shuffle-refetch machinery is *exercised*, not
hoped-for. Same idea here, engine-native: named fault points are
instrumented across cluster/, shuffle/, exec/, memory/ and service/
(`block.fetch`, `rpc.send`, `executor.task`, `device.dispatch`,
`exchange.map`, `spill.write`, `xla.compile`, `mesh.collective`,
`peer.fetch`), and a fault PLAN selects which calls fail and how.
`peer.fetch` fires on every fleet peer-cache request (fetch,
invalidation delivery, warm-state pull — fleet/peer_cache.py; the verb
arrives as op=), so peer failures, slow peers, and delayed/lost
invalidation broadcasts are all injectable; every one must degrade to
local recompute, byte-identically. `mesh.collective` fires in
the SPMD stage launch path (exec/spmd_stage.py): live hits
(background=0) fail the fused collective program and must degrade the
stage to the round-based exchange (counted `spmdDegraded`); bg=1 hits
fire in the prewarm walk, which is best-effort and swallows them.

Plan grammar (conf `spark.rapids.tpu.sql.debug.faults.plan` or env
`SRTPU_FAULTS`), rules separated by `;`:

    point[:selector]*[:action]

    selectors   nth=N       fire on exactly the Nth call of the point
                            (1-based; implies times=1 unless overridden)
                prob=P      fire each call with probability P, drawn
                            from this rule's own seeded PRNG
                seed=S      PRNG seed for prob= (default 0 — the SAME
                            plan always injects the SAME failures)
                times=K     stop after K injections from this rule
                query=SUB   only calls whose query_id contains SUB
                op=NAME     only calls whose operator class == NAME
    actions     raise=NAME  raise a typed error: FetchFailed and
                            ExecutorLost map to the engine's structured
                            exceptions; anything else raises
                            InjectedFault with NAME as the message head
                            (so `raise=RESOURCE_EXHAUSTED` routes
                            through the OOM classifier)
                delay=MS    sleep MS milliseconds (deadline/backoff
                            paths), then continue normally
                kill        os._exit(1) — executor-kill at
                            `executor.task`

    block.fetch:nth=3:raise=FetchFailed
    device.dispatch:prob=0.05:seed=7:raise=RESOURCE_EXHAUSTED
    executor.task:nth=2:kill

Determinism: per-rule `random.Random(seed)` plus per-point call
counters, both under one lock; `injection_trace()` returns the ordered
(point, call, action) list so a test can assert that the same plan +
seed reproduces the identical trace. Executor processes inherit the
driver's environment (cluster/driver.py ships os.environ), so an
`SRTPU_FAULTS` plan is live in every executor too; conf-shipped plans
activate in `TpuSession.__init__` via `install_from_conf`.

Zero overhead disabled: every call site guards with the module-level
bool `if faults.ACTIVE: faults.hit(...)` — one dict-free attribute
read on the hot path, nothing else.
"""
from __future__ import annotations

import os
import threading
import time
from random import Random
from typing import Dict, List, Optional

__all__ = ["ACTIVE", "POINTS", "InjectedFault", "install_plan",
           "clear_plan", "install_from_conf", "hit", "injection_trace",
           "injection_counts", "current_plan", "is_transient_error",
           "note_recovery", "recovery_stats", "reset_recovery_stats"]

#: the zero-overhead guard: call sites read this bool and skip hit()
#: entirely when no plan is installed
ACTIVE = False

#: the instrumented fault-point inventory (docs/robustness.md and the
#: plan generator of tests/test_soak.py both derive from this tuple)
POINTS = ("block.fetch", "device.dispatch", "executor.task",
          "spill.write", "xla.compile", "exchange.map", "rpc.send",
          "mesh.collective", "peer.fetch")

_lock = threading.Lock()
_spec: Optional[str] = None
_rules: List["_Rule"] = []
_calls: Dict[str, int] = {}          # point -> total calls observed
_trace: List[dict] = []              # ordered injections (determinism)
_counts: Dict[str, int] = {}         # action kind -> injections


class InjectedFault(RuntimeError):
    """An error raised by the fault-injection harness (classified
    transient by `is_transient_error` — recovery paths must absorb
    it)."""

    def __init__(self, msg: str, point: str = None):
        super().__init__(msg)
        self.point = point


class _Rule:
    __slots__ = ("point", "nth", "prob", "seed", "times", "query", "op",
                 "action", "arg", "bg", "_rng", "_fired")

    def __init__(self, point: str):
        self.point = point
        self.bg: Optional[bool] = None  # None matches either path
        self.nth: Optional[int] = None
        self.prob: Optional[float] = None
        self.seed: int = 0
        self.times: Optional[int] = None
        self.query: Optional[str] = None
        self.op: Optional[str] = None
        self.action: str = "raise"
        self.arg: Optional[str] = None
        self._rng: Optional[Random] = None
        self._fired: int = 0


def _parse_rule(text: str) -> _Rule:
    fields = [f.strip() for f in text.split(":") if f.strip()]
    if not fields:
        raise ValueError(f"empty fault rule in {text!r}")
    r = _Rule(fields[0])
    for f in fields[1:]:
        if f == "kill":
            r.action = "kill"
            continue
        if "=" not in f:
            raise ValueError(f"bad fault rule field {f!r} (rule {text!r})")
        k, v = f.split("=", 1)
        if k == "nth":
            r.nth = int(v)
        elif k == "prob":
            r.prob = float(v)
        elif k == "seed":
            r.seed = int(v)
        elif k == "times":
            r.times = int(v)
        elif k == "query":
            r.query = v
        elif k == "op":
            r.op = v
        elif k == "bg":
            # background-path selector: bg=1 matches only compile-pool
            # prewarms, bg=0 only the sync dispatch path (xla.compile)
            r.bg = bool(int(v))
        elif k == "raise":
            r.action, r.arg = "raise", v
        elif k == "delay":
            r.action, r.arg = "delay", v
        else:
            raise ValueError(f"unknown fault rule field {k!r} "
                             f"(rule {text!r})")
    # an nth= rule is a single shot unless an explicit times= widens it
    if r.nth is not None and r.times is None:
        r.times = 1
    r._rng = Random(r.seed)
    return r


def install_plan(spec: str) -> int:
    """Parse and install a fault plan, resetting counters, PRNGs and
    the injection trace (same plan ⇒ same injections). Returns the
    number of rules installed."""
    global ACTIVE, _spec
    rules = [_parse_rule(part)
             for part in spec.replace(",", ";").split(";")
             if part.strip()]
    with _lock:
        _rules[:] = rules
        _spec = spec
        _calls.clear()
        _trace.clear()
        _counts.clear()
        ACTIVE = bool(rules)
    return len(rules)


def clear_plan() -> None:
    global ACTIVE, _spec
    with _lock:
        _rules.clear()
        _spec = None
        _calls.clear()
        _trace.clear()
        _counts.clear()
        ACTIVE = False


def current_plan() -> Optional[str]:
    with _lock:
        return _spec


def install_from_conf(conf) -> None:
    """Adopt a conf-carried plan (`sql.debug.faults.plan`). Idempotent
    by spec equality so per-fragment TpuSession construction in
    executors does not reset mid-query call counters."""
    try:
        from ..config import FAULTS_PLAN
        spec = conf.get(FAULTS_PLAN)
    except Exception:
        return
    if spec and spec != current_plan():
        install_plan(spec)


def hit(point: str, query_id: str = None, op: str = None,
        background: bool = False) -> None:
    """The fault point entry: count this call, match it against the
    installed rules, and perform the first matching rule's action.
    Call sites guard with `if faults.ACTIVE:` so this never runs while
    injection is disabled. `background=True` marks the compile pool's
    prewarm path (rules select it with bg=1)."""
    with _lock:
        _calls[point] = call = _calls.get(point, 0) + 1
        fired = None
        for r in _rules:
            if r.point != point:
                continue
            if r.times is not None and r._fired >= r.times:
                continue
            if r.query is not None and (query_id is None
                                        or r.query not in query_id):
                continue
            if r.op is not None and r.op != op:
                continue
            if r.bg is not None and r.bg != bool(background):
                continue
            if r.nth is not None:
                if call != r.nth:
                    continue
            elif r.prob is not None:
                if r._rng.random() >= r.prob:
                    continue
            r._fired += 1
            _counts["injected"] = _counts.get("injected", 0) + 1
            _counts[r.action] = _counts.get(r.action, 0) + 1
            _trace.append({"point": point, "call": call,
                           "action": r.action, "arg": r.arg})
            fired = r
            break
    if fired is None:
        return
    if fired.action == "delay":
        time.sleep(float(fired.arg) / 1000.0)
        return
    if fired.action == "kill":
        os._exit(1)
    _raise_named(fired.arg or "InjectedFault", point)


def _raise_named(name: str, point: str) -> None:
    if name == "FetchFailed":
        from ..cluster.blocks import FetchFailed
        raise FetchFailed(f"injected fault at {point}")
    if name == "ExecutorLost":
        from ..cluster.driver import ExecutorLostError
        raise ExecutorLostError(f"injected fault at {point}")
    # the name leads the message HEAD so classifier routing works
    # (raise=RESOURCE_EXHAUSTED is seen as OOM by memory/retry.py)
    raise InjectedFault(f"{name}: injected fault at {point}", point=point)


def injection_trace() -> List[dict]:
    """Ordered record of every injection since install_plan() — the
    determinism witness (same plan + seed ⇒ identical trace)."""
    with _lock:
        return [dict(t) for t in _trace]


def injection_counts() -> Dict[str, int]:
    with _lock:
        return dict(_counts)


# -- transient-error classification (service-level retry) ---------------

def is_transient_error(e: BaseException) -> bool:
    """True when a query failure is worth a transparent re-admission:
    injected faults, shuffle fetch failures, executor loss, connection
    resets. CONSERVATIVE by contract: cancellation, deadline,
    KeyboardInterrupt and user/plan errors are NEVER transient — a
    retry there would override an explicit decision or re-fail
    identically."""
    if isinstance(e, (KeyboardInterrupt, SystemExit, GeneratorExit)):
        return False
    try:
        from ..service.query_manager import QueryCancelled
        if isinstance(e, QueryCancelled):   # QueryTimedOut subclasses it
            return False
    except ImportError:                      # pragma: no cover
        pass
    if isinstance(e, InjectedFault):
        return True
    try:
        from ..cluster.blocks import FetchFailed
        from ..cluster.driver import ExecutorLostError
        if isinstance(e, (FetchFailed, ExecutorLostError)):
            return True
    except ImportError:                      # pragma: no cover
        pass
    return isinstance(e, ConnectionError)


# -- recovery accounting (read by the chaos soak) -----------------------

_recovery_lock = threading.Lock()
_recovery: Dict[str, int] = {}


def note_recovery(kind: str, n: int = 1) -> None:
    """Count one recovery-path activation (`regenerations`,
    `query_retries`, `fetch_retries`, `rpc_retries`, `degradations`).
    Cheap and unconditional — recovery paths are rare by definition."""
    with _recovery_lock:
        _recovery[kind] = _recovery.get(kind, 0) + n


def recovery_stats() -> Dict[str, int]:
    with _recovery_lock:
        return dict(_recovery)


def reset_recovery_stats() -> None:
    with _recovery_lock:
        _recovery.clear()


# env activation: executors inherit the driver's environment, so one
# SRTPU_FAULTS= covers every process of a cluster run
_env_spec = os.environ.get("SRTPU_FAULTS")
if _env_spec:
    install_plan(_env_spec)
del _env_spec
