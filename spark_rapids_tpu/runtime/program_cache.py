"""Process-global XLA program cache: compile once, run many.

Every per-exec-instance `jax.jit` made the compile-once property
per-DataFrame: a fresh q4 tree re-traced and re-lowered ~every operator
program even though an identical-shaped tree ran seconds earlier in the
same process. The reference engine compiles nothing per query — cuDF
kernels are pre-built — and Eiger/Theseus (PAPERS.md) both key reusable
pre-compiled operator kernels by type signature. This module retrofits
that property: a thread-safe, LRU-bounded, process-global table of
jitted programs keyed by

    (operator class, program tag, site key [expression fingerprints,
     chunk counts, capacities...], donate/static argnums, backend,
     jit-relevant conf fingerprint, input avals signature
     [pytree structure + dtypes + bucketed capacities])

Exec nodes call `cached_program(builder_fn, cls=..., tag=..., key=...)`
instead of `jax.jit(builder_fn)`. The builder must be parameterized on
the key — it may close over plan configuration (bound expressions,
dtypes, bucketed capacities) but never over per-run device state or
large buffers: on a hit the FIRST-seen builder's trace runs, so any
instance state not captured by the key would silently leak into other
instances' results. Capacities are already power-of-two bucketed
(`columnar.column.bucket_capacity`), which is what bounds the avals-
signature cardinality and keeps this table small.

Counters (hits/misses/evictions) surface through
`profiler/xla_stats.snapshot()` into EXPLAIN ANALYZE
(`programCacheHits=`/`programCacheMisses=` at the root), the
`xla_compile` event-log record, and `tools/profile_report.py`. A miss
is (at most) one fresh trace; on a warm process a same-shaped fresh
query tree performs zero new XLA compiles.
"""
from __future__ import annotations

import threading
import time as _time
import weakref
from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ..profiler import tracing
from . import lockdep, racedep

__all__ = ["cached_program", "CachedProgram", "stats", "clear",
           "set_active_conf", "expr_fp", "exprs_fp", "conf_fingerprint",
           "drain_compile_events", "observed_programs",
           "lookup_program", "example_args_from_spec", "key_stable",
           "observed_for", "seed_observed", "prewarm_thunk"]

_lock = lockdep.rlock("program_cache._lock")
_cache: "OrderedDict[tuple, Any]" = OrderedDict()
_stats = {"program_cache_hits": 0, "program_cache_misses": 0,
          "program_cache_evictions": 0,
          "program_cache_background_compiles": 0,
          "program_cache_background_failures": 0,
          "program_cache_compile_ms": 0.0}
_enabled = True
_max_entries = 512
_active_conf_fp: tuple = ()

# base_key -> a live CachedProgram for that site (weak: dies with the
# last exec instance). Warm-pack preload re-plans recorded queries —
# reconstructing the builders and repopulating this registry — then
# prewarms the recorded signatures through whichever instance is live.
_registry: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
# full cache key -> prewarmable spec (leaf specs + pickled-able
# treedefs per arg) observed on a sync miss; the warm-pack manifest is
# written from this table. Bounded like the cache itself.
_observed: "OrderedDict[tuple, dict]" = OrderedDict()
# base_key -> [observed keys]: stage-ahead prewarm resolves every
# program in a launching query's tree, so the per-site lookup must not
# scan the whole table under the dispatch lock
_observed_by_base: Dict[tuple, List[tuple]] = {}
_OBSERVED_CAP = 2048
# per-compile events (program key, wall ms, sync|background) drained by
# the profiler wrapper into the query event log; bounded so an unlogged
# session cannot grow it
_events: List[dict] = []
_EVENTS_CAP = 1024

# conf entries whose values change the shape or contents of traced
# programs (plan-affecting knobs); everything else — metric levels,
# event-log paths, memory thresholds — only steers host-side control
# flow and must NOT split the cache
_JIT_RELEVANT_CONF_KEYS = (
    "spark.rapids.tpu.sql.exec.stageFusion.enabled",
    "spark.rapids.tpu.sql.exec.stageFusion.maxOps",
)


def conf_fingerprint(conf) -> tuple:
    """Fingerprint of the jit-relevant conf subset (part of every cache
    key, so two sessions with different program-shaping confs never
    share a trace)."""
    out = []
    for key in _JIT_RELEVANT_CONF_KEYS:
        try:
            from ..config import REGISTRY
            entry = REGISTRY.get(key)
            out.append((key, conf.get(entry) if entry is not None
                        else None))
        except Exception:
            out.append((key, None))
    return tuple(out)


def set_active_conf(conf) -> None:
    """Adopt a session conf: enable/size the cache, record the
    jit-relevant conf fingerprint mixed into every key, and install the
    shape-bucket policy (sql.exec.shapeBuckets.*) that canonicalizes
    every capacity and chunk-count feeding the keys. Called by
    ExecContext at query start; process-global by design (the cache
    itself is process-global), so the fingerprint-in-key is what keeps
    concurrently active sessions with different program-shaping confs
    from sharing traces — and shapes self-describe in the avals
    signature, so two bucket policies never share a trace either."""
    global _enabled, _max_entries, _active_conf_fp
    from ..config import (PROGRAM_CACHE_ENABLED,
                          PROGRAM_CACHE_MAX_ENTRIES,
                          SHAPE_BUCKET_GROWTH, SHAPE_BUCKET_MIN_ROWS)
    from ..columnar.column import set_bucket_policy
    try:
        set_bucket_policy(int(conf.get(SHAPE_BUCKET_MIN_ROWS)),
                          int(conf.get(SHAPE_BUCKET_GROWTH)))
    except Exception:
        pass
    fp = conf_fingerprint(conf)
    with _lock:
        _enabled = bool(conf.get(PROGRAM_CACHE_ENABLED))
        _max_entries = max(1, int(conf.get(PROGRAM_CACHE_MAX_ENTRIES)))
        _active_conf_fp = fp
        while len(_cache) > _max_entries:
            _release(_cache.popitem(last=False)[1])
            _stats["program_cache_evictions"] += 1


def _release(prog) -> None:
    """Drop a program's compiled executables NOW instead of waiting for
    GC. Each live XLA:CPU executable holds ~10-20 mmap'd segments;
    a process that merely *retains* a few thousand compiled programs
    walks into vm.max_map_count (default 65530), at which point the
    next LLVM JIT mmap fails and the compiler segfaults. Eviction and
    clear() therefore free eagerly — reference cycles through jit
    closures must not delay the unmap."""
    try:
        prog.clear_cache()
    except Exception:
        pass


def stats() -> Dict[str, int]:
    with _lock:
        out = dict(_stats)
        out["program_cache_entries"] = len(_cache)
        return out


def clear() -> None:
    """Drop every entry (releasing compiled executables eagerly) and
    zero the counters (tests, module teardown)."""
    with _lock:
        for prog in _cache.values():
            _release(prog)
        _cache.clear()
        _observed.clear()
        _observed_by_base.clear()
        del _events[:]
        for k in _stats:
            _stats[k] = 0


# ---------------------------------------------------------------------
# compile events + warm-pack observation tables
# ---------------------------------------------------------------------
def _note_compile(base_key: tuple, wall_ms: float, mode: str) -> None:
    """Record one compile (sync miss or background prewarm) for the
    event log: site name, stable key hash, wall ms, mode."""
    import hashlib
    cls = base_key[1] if len(base_key) > 2 else "?"
    tag = base_key[2] if len(base_key) > 2 else "?"
    kh = hashlib.sha256(repr(base_key).encode()).hexdigest()[:12]
    ev = {"program": f"{cls}.{tag}", "key_hash": kh,
          "wall_ms": round(float(wall_ms), 3), "mode": mode}
    with _lock:
        _stats["program_cache_compile_ms"] = round(
            _stats["program_cache_compile_ms"] + float(wall_ms), 3)
        if mode == "background":
            _stats["program_cache_background_compiles"] += 1
        _events.append(ev)
        if len(_events) > _EVENTS_CAP:
            del _events[:len(_events) - _EVENTS_CAP]


def note_background_failure() -> None:
    """Counted by the compile pool when a background task dies (fault
    injection included): swallowed there, visible here."""
    with _lock:
        _stats["program_cache_background_failures"] += 1


def drain_compile_events() -> List[dict]:
    """Return-and-clear the compile events since the last drain (the
    profiler wrapper folds them into the query event log). Global, not
    per-query: concurrent queries' compiles interleave, like every
    other process-global counter here."""
    with _lock:
        out = list(_events)
        del _events[:]
    return out


def _leaf_spec(x):
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("arr", tuple(int(s) for s in shape), str(dtype))
    if isinstance(x, bool):
        return ("py", "b")
    if isinstance(x, int):
        return ("py", "i")
    if isinstance(x, float):
        return ("py", "f")
    return None


def _args_spec(args: tuple, static_argnums: Tuple[int, ...]):
    """A picklable recipe to rebuild example arguments with the same
    avals signature: per arg, (leaf specs, treedef) — or, for static
    args, the value itself when it is a picklable scalar. None when any
    leaf cannot be described (such a program cannot be prewarmed)."""
    import jax
    static = set(static_argnums)
    spec = []
    for i, a in enumerate(args):
        if i in static:
            if isinstance(a, (str, bytes, int, float, bool, type(None))):
                spec.append(("static", a))
                continue
            return None
        leaves, treedef = jax.tree_util.tree_flatten(a)
        ls = tuple(_leaf_spec(x) for x in leaves)
        if any(s is None for s in ls):
            return None
        spec.append(("tree", ls, treedef))
    return tuple(spec)


def example_args_from_spec(spec) -> tuple:
    """Zero-filled concrete arguments matching a recorded spec: the
    prewarm call traces and compiles exactly the program a real call
    with that signature would."""
    import jax
    import jax.numpy as jnp
    args = []
    for part in spec:
        if part[0] == "static":
            args.append(part[1])
            continue
        _, leaf_specs, treedef = part
        leaves = []
        for s in leaf_specs:
            if s[0] == "arr":
                leaves.append(jnp.zeros(s[1], dtype=s[2]))
            else:
                leaves.append({"b": False, "i": 0, "f": 0.0}[s[1]])
        args.append(jax.tree_util.tree_unflatten(treedef, leaves))
    return tuple(args)


def key_stable(base_key) -> bool:
    """False when the key carries an identity fallback (('id', N) /
    ('inst', N) / ('cyc', ...)): correct in-process but meaningless in
    a warm-pack manifest — the same site can never match after a
    restart (the unstable-program-key lint rule polices the sources)."""
    if isinstance(base_key, tuple):
        if len(base_key) == 2 and base_key[0] in ("id", "inst") \
                and isinstance(base_key[1], int):
            return False
        return all(key_stable(x) for x in base_key)
    return True


def _note_observed(key: tuple, base_key: tuple, donate, static,
                   args: tuple) -> None:
    if not key_stable(base_key):
        return
    spec = _args_spec(args, static)
    if spec is None:
        return
    with _lock:
        racedep.note_access("program_cache._observed", key, write=True)
        _observed_insert(key, {"base_key": base_key,
                               "donate": tuple(donate),
                               "static": tuple(static), "spec": spec})


def _observed_insert(key: tuple, entry: dict) -> None:
    """Insert under _lock, maintaining the by-base_key index and the
    LRU cap."""
    if key not in _observed:
        _observed_by_base.setdefault(entry["base_key"], []).append(key)
    _observed[key] = entry
    _observed.move_to_end(key)
    while len(_observed) > _OBSERVED_CAP:
        old_key, old = _observed.popitem(last=False)
        keys = _observed_by_base.get(old["base_key"])
        if keys is not None:
            try:
                keys.remove(old_key)
            except ValueError:
                pass
            if not keys:
                _observed_by_base.pop(old["base_key"], None)


def observed_programs() -> List[dict]:
    """Snapshot of the observed program table (warm-pack record)."""
    with _lock:
        racedep.note_access("program_cache._observed")
        return [dict(v) for v in _observed.values()]


def lookup_program(base_key) -> Optional["CachedProgram"]:
    """A live CachedProgram registered for `base_key`, if any exec
    instance holding one is still alive (warm-pack preload resolves
    manifest entries through this after re-planning)."""
    return _registry.get(base_key)


def observed_for(base_key) -> List[dict]:
    """Every observed spec entry for one program site (stage-ahead
    prewarm at query launch looks up the signatures a structurally
    identical tree compiled before — earlier in this process, or seeded
    from a warm-pack manifest)."""
    with _lock:
        racedep.note_access("program_cache._observed", base_key)
        return [dict(_observed[k])
                for k in _observed_by_base.get(base_key, ())]


def seed_observed(entries: Iterable) -> int:
    """Merge warm-pack manifest entries into the observed table so
    launch-time stage-ahead prewarm can find recorded signatures even
    for sites the preload re-plan could not resolve to a live program.
    Returns the number of new entries."""
    n = 0
    with _lock:
        racedep.note_access("program_cache._observed", write=True)
        for e in entries:
            try:
                k = ("seed", e["base_key"], tuple(e["donate"]),
                     tuple(e["static"]), e["spec"])
                if k in _observed:
                    continue
                _observed_insert(k, dict(e))
            except (TypeError, KeyError):
                continue
            n += 1
    return n


def spec_signature(spec) -> tuple:
    """The avals signature `example_args_from_spec(spec)` would
    produce, computed without allocating the arrays (cheap warm check
    before a prewarm allocates zero buffers)."""
    parts = []
    for part in spec:
        if part[0] == "static":
            v = part[1]
            parts.append(("s", v if _hashable(v) else ("id", id(v))))
            continue
        _, leaf_specs, treedef = part
        sigs = []
        for s in leaf_specs:
            if s[0] == "arr":
                sigs.append(("a", tuple(s[1]), s[2]))
            else:
                sigs.append({"b": ("pyb",), "i": ("pyi",),
                             "f": ("pyf",)}[s[1]])
        parts.append((treedef, tuple(sigs)))
    return tuple(parts)


def prewarm_needed(prog: "CachedProgram", spec) -> bool:
    """True when the spec's full cache key is cold. Caller-side filter
    for prewarm_tree: in steady state every observed spec is already
    warm, and checking here keeps the launch path from paying a pool
    submit + worker wakeup per program just to find that out."""
    import jax
    key = (prog._base_key, prog._donate, prog._static,
           jax.default_backend(), _active_conf_fp,
           spec_signature(spec))
    with _lock:
        return key not in _cache


def prewarm_thunk(prog: "CachedProgram", spec):
    """The compile pool's lazy-args contract for one recorded spec:
    the returned thunk runs on a worker thread and yields example args,
    or None when the spec's cache key is already warm — skipping the
    zero-buffer allocation on every repeat query."""
    def thunk():
        import jax
        key = (prog._base_key, prog._donate, prog._static,
               jax.default_backend(), _active_conf_fp,
               spec_signature(spec))
        with _lock:
            if key in _cache:
                return None
        return example_args_from_spec(spec)
    return thunk


# ---------------------------------------------------------------------
# fingerprints: structural identity for bound expression trees (and any
# package config object — SortOrder, WindowSpec, AggExpr reductions...)
# ---------------------------------------------------------------------
_SCALARS = (str, bytes, int, float, bool, complex, type(None))

# the join-rename machinery (session.py) gensyms hidden key columns
# from a process-global counter (`__join_r<N>_x`): two identical fresh
# query trees carry different counters in otherwise identical bound
# expressions. Post-binding, column NAMES are cosmetic — emit works on
# ordinals — so the fingerprint normalizes the counter away; ordinals
# and dtypes still distinguish genuinely different columns.
import re as _re

_GENSYM_RE = _re.compile(r"__join_r\d+_")


def expr_fp(obj, _memo: Optional[dict] = None):
    """Structural fingerprint of a bound expression tree (or any plan
    config object): class name + dtype + scalar attributes, preorder —
    the same stability property as the preorder lore ids, so two
    semantically identical trees built by different DataFrames collide
    correctly. Unhashable or callable attribute values fall back to
    `("id", id(v))` — correct (never falsely shared) but unshared."""
    if isinstance(obj, str):
        return _GENSYM_RE.sub("__join_r?_", obj)
    if isinstance(obj, _SCALARS):
        return obj
    if _memo is None:
        _memo = {}
    oid = id(obj)
    if oid in _memo:
        return _memo[oid]
    if isinstance(obj, (list, tuple)):
        return ("seq",) + tuple(expr_fp(x, _memo) for x in obj)
    if isinstance(obj, (set, frozenset)):
        return ("set",) + tuple(sorted(
            (repr(expr_fp(x, _memo)) for x in obj)))
    if isinstance(obj, dict):
        return ("map",) + tuple(sorted(
            ((str(k), expr_fp(v, _memo)) for k, v in obj.items())))
    mod = type(obj).__module__ or ""
    if mod.startswith("spark_rapids_tpu") and hasattr(obj, "__dict__") \
            and not callable(obj):
        _memo[oid] = ("cyc", type(obj).__qualname__)  # cycle guard
        parts: list = [type(obj).__qualname__]
        for k, v in sorted(vars(obj).items()):
            # skip obvious runtime attachments (jitted wrappers,
            # lore/op ids assigned post-construction don't change
            # semantics and would split the key per instance).
            # Private `_*_cache` attrs are derived memos by convention
            # (_ndv_cache, _est_rows_cache, ...): planning another
            # query lazily sets them on shared plan nodes, which would
            # destabilize every later fingerprint of those nodes.
            if k.startswith("_jit") \
                    or (k.startswith("_") and k.endswith("_cache")) \
                    or k in ("_op_id", "lore_id", "_cached"):
                continue
            parts.append((k, expr_fp(v, _memo)))
        fp = tuple(parts)
        _memo[oid] = fp
        return fp
    if callable(obj):
        return ("id", oid)
    try:
        hash(obj)
    except TypeError:
        return ("id", oid)
    # hashable foreign value (numpy scalar, Decimal, date, dtype...):
    # identity-hashed objects stay distinct (unshared but correct)
    return obj


def exprs_fp(exprs: Iterable) -> tuple:
    return tuple(expr_fp(e) for e in exprs)


# ---------------------------------------------------------------------
# avals signature: pytree structure + (shape, dtype) per array leaf
# ---------------------------------------------------------------------
def _leaf_sig(x):
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype))
    # python scalars trace as weak-typed 0-d values: the aval depends on
    # the python type, never the value
    if isinstance(x, bool):
        return ("pyb",)
    if isinstance(x, int):
        return ("pyi",)
    if isinstance(x, float):
        return ("pyf",)
    return ("o", type(x).__name__)


def avals_signature(args: tuple,
                    static_argnums: Tuple[int, ...] = ()) -> tuple:
    import jax
    static = set(static_argnums)
    parts = []
    for i, a in enumerate(args):
        if i in static:
            parts.append(("s", a if _hashable(a) else ("id", id(a))))
        else:
            leaves, treedef = jax.tree_util.tree_flatten(a)
            parts.append((treedef, tuple(_leaf_sig(x) for x in leaves)))
    return tuple(parts)


def _hashable(v) -> bool:
    try:
        hash(v)
        return True
    except TypeError:
        return False


# ---------------------------------------------------------------------
# the cache proper
# ---------------------------------------------------------------------
class CachedProgram:
    """Callable wrapper over one builder function + site key. Each call
    computes the input avals signature and resolves the jitted program
    in the process-global table; a hit from a DIFFERENT exec instance
    reuses the first-seen builder's trace (that is the point)."""

    __slots__ = ("_fn", "_base_key", "_donate", "_static", "_local",
                 "_name", "_launch", "__weakref__")

    def __init__(self, fn, base_key: tuple,
                 donate_argnums: Tuple[int, ...] = (),
                 static_argnums: Tuple[int, ...] = ()):
        self._fn = fn
        self._base_key = base_key
        self._donate = tuple(donate_argnums)
        self._static = tuple(static_argnums)
        self._local = None  # fallback jit when the cache is disabled
        # the call site, "<cls>_<tag>" of ("prog", cls, tag, key): the
        # XLA module reads jit_<cls>_<tag> in a device trace and the
        # launch span srt.launch.<cls>.<tag> on the host's side
        cls, tag = (base_key[1:3] if len(base_key) > 2 else ("", ""))
        self._name = f"{cls}_{tag}" if cls else ""
        self._launch = f"launch.{cls}.{tag}" if cls else "launch"
        try:
            _registry[base_key] = self   # last-registered wins; weak
        except TypeError:
            pass                         # unhashable key: unregistered

    @property
    def base_key(self) -> tuple:
        return self._base_key

    def _jit(self):
        import jax
        kw = {}
        if self._donate:
            kw["donate_argnums"] = self._donate
        if self._static:
            kw["static_argnums"] = self._static
        fn = self._fn
        if self._name:
            # builders are closures (`fn`, `_run`, bound methods): jit
            # names the module after the function it is handed
            def call(*args):
                return fn(*args)
            call.__name__ = call.__qualname__ = self._name
            return jax.jit(call, **kw)
        return jax.jit(fn, **kw)

    def _key_for(self, args: tuple):
        import jax
        sig = avals_signature(args, self._static)
        return (self._base_key, self._donate, self._static,
                jax.default_backend(), _active_conf_fp, sig)

    def __call__(self, *args):
        launch = self._launch
        if not _enabled:
            if self._local is None:
                self._local = self._jit()
            with tracing.span(launch, "launch"):
                return self._local(*args)
        # the signature walks every leaf of `args` (a whole cached table
        # for a single-dispatch stage): host time of the launch that is
        # not the launch
        with tracing.span("cache.key", "launch"):
            key = self._key_for(args)
        miss = False
        with _lock:
            prog = _cache.get(key)
            if prog is not None:
                _cache.move_to_end(key)
                _stats["program_cache_hits"] += 1
            else:
                from . import faults
                if faults.ACTIVE:
                    # compile-on-miss is the xla.compile fault point: a
                    # raise here fails the query before any dispatch (a
                    # service-level retry re-enters and recompiles)
                    faults.hit("xla.compile", op=self._base_key[0]
                               if self._base_key else None)
                prog = self._jit()
                _cache[key] = prog
                _stats["program_cache_misses"] += 1
                miss = True
                while len(_cache) > _max_entries:
                    _release(_cache.popitem(last=False)[1])
                    _stats["program_cache_evictions"] += 1
        if not miss:
            with tracing.span(launch, "launch"):
                return prog(*args)
        # sync miss: the actual trace+compile happens on this first
        # call (outside the lock). The timed wall includes one
        # dispatch — the event log documents it as such. The spec is
        # recorded BEFORE the call: donated arg buffers are dead after.
        _note_observed(key, self._base_key, self._donate, self._static,
                       args)
        t0 = _time.perf_counter()
        # sync compile ON the dispatch path: exactly the latency the
        # critical path must blame on 'compile' (thread-local context —
        # the query thread runs under tracing.use)
        with tracing.span("xla.compile", "compile",
                          op=self._base_key[0] if self._base_key
                          else None), \
                tracing.span(launch, "launch"):
            out = prog(*args)
        _note_compile(self._base_key,
                      (_time.perf_counter() - t0) * 1e3, "sync")
        return out

    def prewarm(self, args: tuple) -> bool:
        """Compile this program for `args`' signature ahead of first
        dispatch (compile-pool workers call this with zero-filled
        example args). Returns True when a program was compiled, False
        when the key was already warm or the cache is disabled. Runs
        the compiled program once on the example args — engine builder
        functions are pure batch transforms, so the throwaway execution
        is safe and leaves jax's tracing cache hot. Never called on the
        dispatch path: a concurrent sync miss for the same key compiles
        a duplicate rather than waiting."""
        if not _enabled:
            return False
        key = self._key_for(args)
        with _lock:
            if key in _cache:
                return False
        from . import faults
        if faults.ACTIVE:
            # the background half of the xla.compile fault point: the
            # compile pool swallows + counts the raise, and the query
            # falls back to the sync compile path
            faults.hit("xla.compile", op=self._base_key[0]
                       if self._base_key else None, background=True)
        prog = self._jit()
        t0 = _time.perf_counter()
        prog(*args)
        wall_ms = (_time.perf_counter() - t0) * 1e3
        stored = False
        with _lock:
            if key not in _cache:
                _cache[key] = prog
                stored = True
                while len(_cache) > _max_entries:
                    _release(_cache.popitem(last=False)[1])
                    _stats["program_cache_evictions"] += 1
        if stored:
            _note_observed(key, self._base_key, self._donate,
                           self._static, args)
            _note_compile(self._base_key, wall_ms, "background")
        else:
            _release(prog)
        return stored


def cached_program(fn, *, cls: str, tag: str, key: tuple = (),
                   donate_argnums: Tuple[int, ...] = (),
                   static_argnums: Tuple[int, ...] = ()) -> CachedProgram:
    """Process-global replacement for a per-instance `jax.jit(fn)`.

    `cls`/`tag` name the call site (operator class + which of its
    programs); `key` carries everything instance-specific the traced
    program depends on — expression fingerprints (`expr_fp`), chunk
    counts, capacities, flags. `fn` may close over exactly that keyed
    state and nothing else. A site whose program genuinely depends on
    unkeyable instance state must key on `("id", id(self))` — correct
    but unshared — rather than omit it."""
    return CachedProgram(fn, ("prog", cls, tag, key),
                         donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
