"""Physical operator base: columnar, pull-based, jit-compiled per shape.

Analog of the reference's GpuExec (reference: GpuExec.scala:107): every
operator is columnar-only, produces an iterator of DeviceBatch per
partition, and registers metrics. TPU-first difference: each operator owns
jitted kernels (traced once per capacity bucket, cached by jax), and entire
project/filter/agg-update chains are fused by XLA rather than being separate
kernel launches.
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional

from ..columnar.table import Schema
from ..utils.metrics import MetricSet
from .batch import DeviceBatch

__all__ = ["TpuExec", "ExecContext", "prewarm_tree"]


class ExecContext:
    """Per-query execution context: conf snapshot, metrics, memory runtime."""

    def __init__(self, conf=None, session=None, planning: bool = False):
        import threading
        from ..config import METRICS_LEVEL, METRICS_SYNC, TpuConf
        from ..utils.metrics import DEBUG, ESSENTIAL, MODERATE
        self.conf = conf or TpuConf()
        self.session = session
        # planning probes (num_partitions during plan construction) must
        # not trigger stage materialization (AQE readers check this)
        self.planning = planning
        self.metrics: Dict[str, MetricSet] = {}
        self._metrics_lock = threading.Lock()
        # metric verbosity + the conf-gated stream-sync timers (see
        # utils/metrics.py on async-dispatch timer skew)
        self.metrics_level = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE,
                              "DEBUG": DEBUG}.get(
            str(self.conf.get(METRICS_LEVEL)).upper(), MODERATE)
        self.metrics_sync = bool(self.conf.get(METRICS_SYNC))
        # query-service identity + cooperative interruption: the
        # QueryManager threads its CancelToken through here and every
        # batch loop polls check_cancel() (lint rule ctx-cancel);
        # sem_priority is the pool-weight-derived TpuSemaphore priority
        self.cancel = None
        self.query_id: Optional[str] = None
        self.sem_priority = 0
        # distributed-tracing context (profiler/tracing.py): set by the
        # session/runner once the query id is known; None when tracing
        # is off or this query sampled out. Operators open spans with
        # `tracing.span(name, kind, ctx)` — one attribute read when off
        self.trace = None
        # SharedBuildExec's per-run materialization cache:
        # {id(node): {pid: [spill handles]}} — closed by close()
        self.shared_handles: Dict[int, dict] = {}
        # graceful device->host degradation state (exec/degrade.py):
        # per-op device failure counts, the ops pinned to host for the
        # remainder of this query, and recovery events the profiler
        # wrapper drains into the query's event log
        self.device_failures: Dict[str, int] = {}
        self.degraded: Dict[str, bool] = {}
        self.pending_events: List[dict] = []
        # adopt this query's conf into the process-global program cache
        # (enable/size + jit-relevant conf fingerprint mixed into keys)
        if not planning:
            from ..runtime import program_cache
            program_cache.set_active_conf(self.conf)

    def close(self):
        """Release per-run resources (shared-build spill handles)."""
        for per_node in self.shared_handles.values():
            for handles in per_node.values():
                for h in handles:
                    try:
                        h.close()
                    except Exception:
                        pass
        self.shared_handles.clear()

    def metrics_for(self, op_id: str) -> MetricSet:
        with self._metrics_lock:
            if op_id not in self.metrics:
                self.metrics[op_id] = MetricSet(sync=self.metrics_sync,
                                                op_id=op_id)
            return self.metrics[op_id]

    def check_cancel(self):
        """Cooperative cancellation checkpoint: raises QueryCancelled/
        QueryTimedOut when this query's token tripped. One attribute
        read when no service is involved — cheap enough for per-batch
        polling."""
        tok = self.cancel
        if tok is not None:
            tok.check()


class TpuExec:
    """Base physical operator."""

    # whole-stage fusion hooks (plan/fusion.py): opt a node out of the
    # fusion pass; mark operators that collapse their own child chain
    # (collapse_fusable below) so the pass does not wrap it twice; and
    # whether that collapse stops at column-renumbering stages
    fusion_opt_out = False
    fuses_child_chain = False
    fusion_require_ordinals = False

    def __init__(self, children: List["TpuExec"], schema: Schema):
        self.children = children
        self._schema = schema
        self._op_id = f"{type(self).__name__}@{id(self):x}"

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self, ctx: ExecContext) -> int:
        if self.children:
            return self.children[0].num_partitions(ctx)
        return 1

    def execute_partition(self, ctx: ExecContext,
                          pid: int) -> Iterator[DeviceBatch]:
        raise NotImplementedError

    def execute_mesh(self, ctx: ExecContext, n: int):
        """Lockstep execution over an n-device mesh: an iterator of
        MeshBatch (exec/batch.py), every partition's next batch at once
        and each on its partition's device, so the consumer runs ONE
        program over the mesh where it would run one a partition. None
        where this operator has no lockstep form here; the caller then
        pulls execute_partition. Decided before any work is done."""
        return None

    def release(self):
        """Free long-lived resources held by this operator (spill
        handles parked for re-execution, cached device buffers).
        Recurses; called when the owning plan/DataFrame is dropped
        (ADVICE r3: exchange output handles must have a lifecycle hook
        or every mesh query leaks budget accounting + spill files)."""
        for c in self.children:
            c.release()

    def fusable_stage(self):
        """Pure per-batch device transform (cvs, mask) -> (cvs, mask) when
        this operator can fuse into its parent's jitted program (the
        whole-stage-fusion analog: XLA compiles the parent's kernel with
        this stage inlined, eliminating a dispatch + intermediate
        materialization per batch). None when not fusable."""
        return None

    def d128_exprs(self) -> int:
        """128-bit decimal expression nodes this operator's own program
        evaluates a row (expr.expressions.d128_nodes); the operators that
        hold bound expressions of their own override it."""
        return 0

    def preserves_ordinals(self) -> bool:
        """True when fusable_stage keeps the child's column ordinals
        (filters do; projections do not)."""
        return True

    def stage_fingerprint(self) -> tuple:
        """Structural identity of this node's fusable_stage() transform,
        used as program-cache key material when the stage is inlined
        into a parent's jitted program. The default is identity-based —
        correct but never shared; nodes whose stage is fully determined
        by bound expressions override it (Filter/Project/Limit/
        FusedStage) so same-shaped trees from different DataFrames
        share one trace."""
        return ("inst", id(self))

    def cached_programs(self) -> list:
        """The CachedPrograms this node holds at construction time
        (stage-ahead prewarm walks these at query launch). The default
        scans instance attributes, which covers every node that builds
        its programs in __init__ (Project/Filter/Limit/FusedStage/
        exchange/aggregate pre-stages); programs built lazily inside
        execute_partition are reachable only once observed."""
        from ..runtime.program_cache import CachedProgram
        return [v for v in vars(self).values()
                if isinstance(v, CachedProgram)]

    # ------------------------------------------------------------------
    def execute_all(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for pid in range(self.num_partitions(ctx)):
            for batch in self.execute_partition(ctx, pid):
                ctx.check_cancel()
                yield batch

    def node_name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.node_name()

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s


def prewarm_tree(root: TpuExec, pool, query_id: Optional[str] = None,
                 limit: int = 64) -> int:
    """Stage-ahead compilation: at query launch, submit every program
    in the physical tree whose signature has been observed before (an
    earlier structurally identical query, or a warm-pack manifest) to
    the background compile pool. Downstream stage programs then compile
    on `tpu-compile-N` threads while upstream stages execute; the first
    dispatch finds them warm instead of paying the trace inline.

    Never blocks and never raises: submissions are best-effort
    (`CompilePool.submit` drops on a full queue) and a program with no
    observed signature is simply skipped — it compiles sync on first
    dispatch exactly as before."""
    from ..runtime import program_cache
    n = 0
    stack = [root]
    seen = set()
    while stack and n < limit:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(node.children)
        try:
            progs = node.cached_programs()
        except Exception:
            continue
        for prog in progs:
            for entry in program_cache.observed_for(prog.base_key):
                if not program_cache.prewarm_needed(prog, entry["spec"]):
                    continue
                if pool.submit(
                        prog,
                        program_cache.prewarm_thunk(prog, entry["spec"]),
                        speculative=False, query_id=query_id):
                    n += 1
                if n >= limit:
                    return n
    return n


# what the tag of a cached program ends in where its body holds 128-bit
# decimal arithmetic (TpuExec.d128_exprs() > 0): the module then reads
# `jit_<cls>_<tag>_d128` in a device trace
D128_MARK = "_d128"


def report_d128(m, exprs: int, rows: int):
    """`d128Exprs` (the 128-bit decimal expression nodes in the
    operator's program) and `d128Rows` (the live rows its launches put
    through them); nothing for an operator whose program holds none."""
    if exprs:
        m.set("d128Exprs", exprs)
        m.add("d128Rows", rows)


def collapse_fusable(node: TpuExec, require_ordinals: bool = False):
    """Walk down a chain of fusable operators (filter/project) and return
    (base_child, composed_fn, n_stages). composed_fn applies the stages
    bottom-up inside the caller's jit; n_stages == 0 means nothing fused
    (composed_fn is identity and base_child is `node`).

    require_ordinals: stop at stages that renumber columns (projections) —
    for parents that inspect child batches by ordinal outside the jit.

    The composed closure carries `_stage_fp` — the tuple of member
    stage fingerprints — so callers that jit it (sort/join/agg
    pre-stages) can key the program-cache entry on chain structure
    instead of instance identity."""
    stages = []
    fps = []
    while True:
        fn = node.fusable_stage()
        if fn is None or (require_ordinals and not node.preserves_ordinals()):
            break
        stages.append(fn)
        fps.append(node.stage_fingerprint())
        node = node.children[0]
    stages.reverse()
    fps.reverse()

    def composed(cvs, mask):
        for fn in stages:
            cvs, mask = fn(cvs, mask)
        return cvs, mask

    composed._stage_fp = ("chain",) + tuple(fps)
    return node, composed, len(stages)
