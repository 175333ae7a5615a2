"""In-program SPMD stages: the exchange as a sharding annotation.

The round-based `MeshExchangeExec` (exec/mesh_exchange.py) still treats
the exchange as an OPERATOR BOUNDARY: every round hops through host
orchestration (dispatch, stats fetch, slice, park) and hands spill
handles to a *separate* consumer program. On a TPU mesh the native
formulation is the opposite — the exchange is a sharding annotation
inside one compiled program: each shard computes partition ids,
`jax.lax.all_to_all` moves row payloads and string bytes over ICI, and
the consumer (final hash-aggregate merge+finalize, or a fusable
filter/project chain) runs on the received shard INSIDE the same jitted
program. No per-round host sync, no park/unpark between exchange and
consumer (the operator-boundary materialization cost "Rethinking
Analytical Processing in the GPU Era" and Theseus both call out as
where accelerator engines lose integer factors).

`SpmdStageExec` is planted by `fuse_spmd_stages` (plan/fusion.py) over
a `MeshExchangeExec` + consumer pair. Three stage kinds:

  agg      — final-mode HashAggregateExec over the exchange: the fused
             program is emit-keys → partition_ids → all_to_all →
             in-trace merge (`_merge_body`) → `_finalize_fn`. One
             compiled program per stage.
  chain    — a fusable filter/project chain over the exchange: the
             chain's `fusable_stage()` transforms apply to the received
             shard in-program, then compact.
  exchange — a bare exchange (shuffled-join input): one single-round
             collective program (vs N host-orchestrated rounds), plus
             the `stage_bytes` stats hook AQE's mesh demote/re-shard
             rules read.

Memory model and fallbacks: the map side is drained ONCE into spillable
handles (exact byte accounting rides along). When the staged working
set exceeds `mesh.spmdStage.maxBytes` — or a transient fault (the
`mesh.collective` injection point) hits the fused launch — the stage
DEGRADES to the streaming round-based `MeshExchangeExec`, re-serving
the already-staged handles in original drain order so the fallback
output is byte-identical to a direct round-based run and the map side
never re-executes. The host/file shuffle remains the
heterogeneous-cluster path, untouched.

Program-cache discipline: the collective program's lowering bakes in
the mesh topology (replica groups, ICI routing), so the cache key
leads with `mesh_topology_key(n, axis)` — (n_devices, axis name,
device kind) — in addition to the stage's structural fingerprint. The
`mesh-program-key` tpulint rule (analysis/lint_rules.py) polices this
for every shard_map program under exec/.

AQE interplay (plan/aqe.py): `plan_reshard` is the mesh analog of
partition coalescing — exact staged bytes shrink the ACTIVE mesh axis
(partition ids drawn mod n_active < n_devices) so tiny stages don't
fan out over the full mesh; the mesh demote rule broadcasts a build
side that fits `autoBroadcastJoinThreshold` straight from its staged
handles, skipping both sides' collectives.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import bucket_capacity
from ..expr.expressions import EmitCtx
from ..ops.concat import concat_cvs, concat_masks, pad_mask
from ..ops.gather import compact
from ..ops.hash import partition_ids
from ..ops.kernel_utils import CV
from .base import ExecContext, TpuExec
from .batch import DeviceBatch, MeshBatch
from .mesh_exchange import MeshExchangeExec, _empty_cv, _pad_round_cv
from .nodes import make_table

__all__ = ["SpmdStageExec", "StagedSourceExec"]


class StagedSourceExec(TpuExec):
    """Re-serve already-staged map output to the round-based fallback
    exchange. One partition, batches in ORIGINAL drain order — the
    round-based exchange composes its rounds from arrival order, so the
    fallback's output is byte-identical to a direct round-based run.
    Handles stay open (owned by the SpmdStageExec that staged them)."""

    def __init__(self, handles: Sequence, schema, own: bool = False):
        super().__init__([], schema)
        self._handles = list(handles)
        self._own = own

    def num_partitions(self, ctx):
        return 1

    def describe(self):
        return f"StagedSourceExec[batches={len(self._handles)}]"

    def execute_partition(self, ctx: ExecContext, pid: int):
        for h in self._handles:
            ctx.check_cancel()
            yield h.materialize()

    def release(self):
        if self._own:
            for h in self._handles:
                h.close()
            self._handles = []


class SpmdStageExec(TpuExec):
    """One shard_map program per stage: exchange + consumer fused."""

    def __init__(self, exchange: MeshExchangeExec, consumer=None,
                 chain: Optional[Sequence[TpuExec]] = None,
                 kind: str = "agg"):
        if kind == "agg":
            schema = consumer.schema
        elif kind == "chain":
            schema = chain[0].schema
        else:
            schema = exchange.schema
        super().__init__(list(exchange.children), schema)
        self.exchange = exchange
        self.consumer = consumer
        self.chain = list(chain or [])
        self.kind = kind
        # explain/lore walks see the fused operators as members (the
        # FusedStageExec convention); the shared map subtree stays our
        # child so release()/AQE traversals reach it exactly once
        self.members = [exchange] + ([consumer] if consumer is not None
                                     else []) + self.chain
        from ..runtime import lockdep
        self._lock = lockdep.rlock("SpmdStageExec._lock")
        self._staged: Optional[List[Tuple]] = None  # [(handle, nbytes)]
        self._staged_bytes = 0
        self._groups: Optional[List[List[int]]] = None  # lockstep staging
        self._shard_of: List[int] = []
        self._out: Optional[List[List]] = None      # per shard: handles
        self._out_rows: List[int] = []              # per shard: live rows
        self._degraded = False
        self._fallback_src: Optional[StagedSourceExec] = None
        self._n_active = exchange.n
        self._reshard_decision = None
        self._jit_cache = {}

    def describe(self):
        inner = ", ".join(m.node_name() for m in self.members)
        extra = (f", active={self._n_active}"
                 if self._n_active != self.exchange.n else "")
        extra += ", degraded" if self._degraded else ""
        return (f"SpmdStageExec[{self.kind}, devices={self.exchange.n}"
                f"{extra}, fused=[{inner}]]")

    def num_partitions(self, ctx):
        return self.exchange.n

    def cached_programs(self) -> list:
        # the stage program is built lazily (key needs observed
        # nchunks), so surface the memoized cache for prewarm walks;
        # this IS the stage-launch background path, so it is also the
        # bg-selector site of the mesh.collective fault point
        from ..runtime import faults
        if faults.ACTIVE:
            try:
                faults.hit("mesh.collective", op=type(self).__name__,
                           background=True)
            except Exception:
                return []       # prewarm is best-effort by contract
        return [p._prog for p in self._jit_cache.values()]

    # -- staging -------------------------------------------------------
    def _ensure_staged(self, ctx: ExecContext):
        """Run the map side ONCE into spillable handles (priority 10)
        with exact per-batch byte accounting — the byte stats the AQE
        re-shard/demote rules and the working-set budget check read.
        Where the map side has a lockstep form (exec/lockstep.py) every
        shard's batches come at once, from one program over the mesh a
        batch position; otherwise its partitions are drained
        concurrently on the exchange map pool. Either way a batch is
        staged on the device it was produced on, and `_groups` says
        which shard that is."""
        with self._lock:
            if self._staged is not None:
                return
            from ..memory.retry import retry_no_split
            from ..memory.spill import spill_store
            from ..profiler import tracing
            from .lockstep import mesh_batches
            store = spill_store(ctx.conf)
            m = ctx.metrics_for(self._op_id)
            child = self.children[0]
            n = self.exchange.n
            staged: List[Tuple] = []
            groups: List[List[int]] = []    # lockstep: n indices a group
            shard_of: List[int] = []        # else: the shard of each

            def park(b):
                staged.append((retry_no_split(
                    lambda b=b: store.add_batch(b, priority=10)),
                    int(b.nbytes)))
                return len(staged) - 1

            try:
                with tracing.span("spmd.stage", "stage", ctx), \
                        m.timer("partitionTime"):
                    lock = mesh_batches(ctx, child, n,
                                        self.exchange.axis_name)
                    if lock is not None:
                        for mb in lock:
                            ctx.check_cancel()
                            groups.append([park(b) for b in mb.shards])
                    else:
                        nparts = child.num_partitions(ctx)
                        for cpid, bs in enumerate(
                                self._drain_partitions(ctx, child, nparts,
                                                       m)):
                            for b in bs:
                                park(b)
                                # co-partitioned input stays where it
                                # is; anything else is dealt round-robin
                                shard_of.append(cpid if nparts == n else
                                                (len(staged) - 1) % n)
            except BaseException:
                for h, _ in staged:
                    h.close()
                raise
            self._staged = staged
            self._groups = groups if lock is not None else None
            self._shard_of = shard_of
            self._staged_bytes = sum(nb for _, nb in staged)
            m.set("spmdStagedBytes", self._staged_bytes)

    @staticmethod
    def _drain_partitions(ctx, child, nparts, m):
        """Every partition of the map side, drained to a list: on the
        exchange map pool (exec/exchange_pool.py) where it gives more
        than one thread, so partitions on different chips run at the
        same time; device admission a batch goes through the rider."""
        from .exchange_pool import PermitRider, resolve_map_threads
        threads = resolve_map_threads(ctx, nparts)
        if threads <= 1 or nparts <= 1:
            out = []
            for cpid in range(nparts):
                bs = []
                for b in child.execute_partition(ctx, cpid):
                    ctx.check_cancel()
                    bs.append(b)
                out.append(bs)
            return out
        import concurrent.futures as cf
        from ..profiler import tracing
        from .nodes import _session_semaphore
        rider = PermitRider(_session_semaphore(ctx),
                            priority=getattr(ctx, "sem_priority", 0),
                            token=ctx.cancel)
        tc = tracing.current()

        def drain(cpid):
            bs = []
            with tracing.use(tc), tracing.span("spmd.map", "pool_task",
                                               cpid=cpid):
                it = child.execute_partition(ctx, cpid)
                while True:
                    ctx.check_cancel()
                    with rider.step():
                        b = next(it, None)
                    if b is None:
                        return bs
                    bs.append(b)

        with cf.ThreadPoolExecutor(
                threads, thread_name_prefix="tpu-spmd-map") as pool:
            futs = [pool.submit(drain, cpid) for cpid in range(nparts)]
            try:
                # tpulint: allow[wait-under-lock] map-pool join under the memoizing _lock, as in ShuffleExchangeExec: the rider guarantees worker progress and readers must wait for the stage anyway
                out = [f.result() for f in futs]
            except BaseException:
                for f in futs:
                    f.cancel()
                raise
        if rider.waited_secs > 0:
            m.add("mapPoolWaitMs", round(rider.waited_secs * 1e3, 3))
        return out

    def stage_bytes(self, ctx: ExecContext) -> int:
        """Materialize the map stage and return its staged device bytes
        (the MapOutputStatistics analog AQE's mesh rules consume)."""
        self._ensure_staged(ctx)
        return self._staged_bytes

    def staged_source(self, own: bool = False) -> StagedSourceExec:
        """The staged map output as a source node (AQE mesh demote
        broadcasts the build side straight from these handles — neither
        side's collective runs). With `own=True`, handle ownership
        TRANSFERS to the source (the demote drops this stage from the
        tree, so release() would never reach it)."""
        src = StagedSourceExec(
            [h for h, _ in (self._staged or [])],
            self.exchange.children[0].schema, own=own)
        if own:
            self._staged = []
            self._staged_bytes = 0
        return src

    # -- AQE hook ------------------------------------------------------
    def plan_reshard(self, ctx: ExecContext, conf):
        """Mesh analog of AQE partition coalescing: shrink the ACTIVE
        mesh axis while each remaining shard would stay under the
        per-shard byte floor. The collective still spans the full mesh
        (topology is baked into the program); only partition ids are
        drawn mod n_active, so small stages stop fanning out state over
        shards that would each hold a few rows. Returns the decision
        record (memoized — re-runs re-serve it) or None."""
        from ..config import SPMD_RESHARD_ENABLED, SPMD_RESHARD_MIN_BYTES
        with self._lock:
            if self._reshard_decision is not None:
                return self._reshard_decision
            # a bare exchange feeds a co-partitioned join: its sibling
            # stage would have to draw the same n_active, and each
            # decides from its own bytes — so neither is re-sharded
            if (not conf.get(SPMD_RESHARD_ENABLED) or self.kind == "exchange"
                    or self._out is not None or self._degraded):
                return None
            self._ensure_staged(ctx)
            n = self.exchange.n
            min_b = int(conf.get(SPMD_RESHARD_MIN_BYTES))
            k = n
            while k > 1 and self._staged_bytes < min_b * k:
                k = (k + 1) // 2
            if k >= n:
                return None
            self._n_active = k
            d = {"rule": "mesh_reshard",
                 "stage_lore": getattr(self, "lore_id", None),
                 "devices": n, "active": k,
                 "staged_bytes": int(self._staged_bytes),
                 "min_bytes_per_shard": min_b}
            self._reshard_decision = d
            ctx.metrics_for(self._op_id).set("spmdActiveShards", k)
            return d

    # -- execution -----------------------------------------------------
    def _ensure_executed(self, ctx: ExecContext):
        with self._lock:
            if self._out is not None or self._degraded:
                return
            from ..config import SPMD_STAGE_MAX_BYTES
            from ..runtime import faults
            self._ensure_staged(ctx)
            m = ctx.metrics_for(self._op_id)
            budget = int(ctx.conf.get(SPMD_STAGE_MAX_BYTES))
            if 0 <= budget < self._staged_bytes:
                self._degrade(ctx, "budget")
                return
            if not self._staged:
                self._out, self._out_rows = [], [0] * self.exchange.n
                return
            try:
                from ..profiler import tracing
                with tracing.span("spmd.collective", "collective", ctx,
                                  bytes=self._staged_bytes):
                    if faults.ACTIVE:
                        # the live stage-launch fault point (bg=0); the
                        # prewarm path hits with background=True
                        faults.hit("mesh.collective",
                                   query_id=ctx.query_id,
                                   op=type(self).__name__,
                                   background=False)
                    self._run_fused(ctx, m)
            except BaseException as e:
                if faults.is_transient_error(e):
                    # recovery contract: the stage falls back to the
                    # round-based exchange over the SAME staged handles
                    self._degrade(ctx, type(e).__name__)
                    faults.note_recovery("degradations")
                    return
                raise

    def _degrade(self, ctx: ExecContext, reason: str):
        """Swap the round-based exchange in over the staged handles.
        The exchange re-drains them in original order, so its output is
        byte-identical to a direct round-based run; the map side does
        NOT re-execute."""
        from ..profiler import tracing
        with tracing.span("spmd.degrade", "degrade", ctx, reason=reason):
            m = ctx.metrics_for(self._op_id)
            m.add("spmdDegraded", 1)
            self._fallback_src = self.staged_source()
            self.exchange.children = [self._fallback_src]
            self._degraded = True

    def _fallback_node(self) -> TpuExec:
        if self.kind == "agg":
            return self.consumer
        if self.kind == "chain":
            return self.chain[0]
        return self.exchange

    def execute_partition(self, ctx: ExecContext, pid: int):
        self._ensure_executed(ctx)
        if self._degraded:
            yield from self._fallback_node().execute_partition(ctx, pid)
            return
        if self._out_rows[pid]:
            yield self._out[pid].materialize()

    def execute_mesh(self, ctx: ExecContext, n: int):
        """The stage's output as it left the program: one batch a shard
        at one capacity, each on its shard's device. None once degraded
        (the round-based exchange serves partitions only)."""
        if n != self.exchange.n:
            return None
        self._ensure_executed(ctx)
        if self._degraded:
            return None
        if not self._out:
            return iter(())
        return iter([MeshBatch([h.materialize() for h in self._out])])

    # -- the fused program ---------------------------------------------
    def _string_keys(self) -> List[int]:
        from ..columnar import dtypes as dt
        return [ki for ki, k in enumerate(self.consumer.keys)
                if isinstance(k.dtype, (dt.StringType, dt.BinaryType))]

    def _agg_nchunks(self, groups) -> Tuple[int, ...]:
        """Static string-chunk counts for the consumer's keys, measured
        over the staged wire batches (per-row string LENGTH is exchange-
        invariant, so pre-exchange maxima bound the merge's chunks): one
        program over the mesh and ONE device fetch (the same
        live-rows-only rule as HashAggregateExec._nchunks_for)."""
        from ..ops import sortkeys as sk
        from ..parallel.mesh_program import MeshProgram
        from ..utils.transfer import fetch
        str_keys = self._string_keys()
        # string keys floor at the 1-byte chunk count even when every
        # staged value is null/empty (matches _nchunks_for)
        ncs = [0] * len(self.consumer.keys)
        for ki in str_keys:
            ncs[ki] = sk.nchunks_for_len(1)
        if not str_keys or not groups:
            return tuple(ncs)

        def maxlens(tree):
            out = []
            for ki in str_keys:
                mx = jnp.int32(0)
                for cvs, mask in tree:
                    kcv = cvs[ki]
                    lens = kcv.offsets[1:] - kcv.offsets[:-1]
                    mx = jnp.maximum(mx, jnp.max(
                        jnp.where(mask & kcv.validity, lens, 0)))
                out.append(mx)
            return jnp.stack(out)

        ex = self.exchange
        prog = MeshProgram(maxlens, ex.n, ex.axis_name,
                            cls="SpmdStageExec", tag="keylens",
                            key=(tuple(str_keys),))
        # tpulint: allow[sync-under-lock] one batched max-length fetch while building the memoized stage program; readers block on _lock until _out is set regardless
        got = fetch(prog(self._group_trees(groups)))
        for j, ki in enumerate(str_keys):
            ncs[ki] = max(ncs[ki], sk.nchunks_for_len(
                max(max(int(v[j]) for v in got), 1)))
        return tuple(ncs)

    @staticmethod
    def _group_trees(groups):
        """The argument of a program over the staged batches: a shard's
        tree is the tuple of its (cvs, mask), one a group."""
        n = len(groups[0])
        return [tuple((g[s].cvs(), g[s].row_mask) for g in groups)
                for s in range(n)]

    def _program(self, has_offsets, nchunks):
        """Build (or fetch) THE one compiled program for this stage:
        concatenate the shard's staged batches, partition ids +
        all_to_all + consumer, inside one shard_map. Keyed on the mesh
        topology first (MeshProgram) — collective lowering bakes in
        replica groups and ICI routing, so programs must never cross
        topologies (mesh-program-key lint rule)."""
        from ..parallel.collectives import exchange_cvs
        from ..parallel.mesh_program import MeshProgram
        from ..runtime.program_cache import exprs_fp

        ex = self.exchange
        n = ex.n
        axis = ex.axis_name
        n_active = self._n_active
        # close over bound exprs / member protocols, never self: a
        # cached entry pinning the builder must not pin staged output
        ex_keys = ex.keys
        ex_key_dtypes = [k.dtype for k in ex_keys]
        wire_dtypes = [f.dtype for f in ex.schema.fields]
        kind = self.kind
        consumer = self.consumer
        chain_fns = [nd.fusable_stage() for nd in reversed(self.chain)]

        if kind == "agg":
            ckey = consumer._fp + (nchunks,)
        elif kind == "chain":
            ckey = tuple(nd.stage_fingerprint() for nd in self.chain)
        else:
            ckey = ()

        def shard_fn(tree):
            if len(tree) == 1:
                cvs, mask = tree[0]
            else:
                cvs = [concat_cvs([t[0][ci] for t in tree], d)
                       for ci, d in enumerate(wire_dtypes)]
                mask = concat_masks([t[1] for t in tree])
            cap = mask.shape[0]
            ectx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ectx) for k in ex_keys]
            pids = partition_ids(key_cvs, ex_key_dtypes, n_active)
            # received rows are a live prefix: out_mask is arange < count
            out_cvs, out_mask, got = exchange_cvs(cvs, mask, pids, n, axis)
            got_rows = got.astype(jnp.int64)
            got_bytes = jnp.int64(0)
            for cv in out_cvs:      # live values and a validity byte a row
                if cv.offsets is None:
                    got_bytes += got_rows * (
                        cv.data.dtype.itemsize * cv.data[0].size + 1)
                else:
                    lens = cv.offsets[1:] - cv.offsets[:-1]
                    got_bytes += got_rows * 5 + jnp.sum(
                        jnp.where(out_mask, lens, 0).astype(jnp.int64))
            if kind == "agg":
                ocap = out_mask.shape[0]
                kctx = EmitCtx(out_cvs, ocap)
                mkeys = [k.emit(kctx) for k in consumer.keys]
                nkeys = len(consumer.keys)
                flat_states = [cv.data for cv in out_cvs[nkeys:]]
                mk, mflat, mlive = consumer._merge_body(
                    mkeys, flat_states, out_mask, nchunks)
                outs = consumer._finalize_fn(mk, mflat, mlive)
                count = jnp.sum(mlive.astype(jnp.int32))
            elif kind == "chain":
                for fn in chain_fns:
                    out_cvs, out_mask = fn(out_cvs, out_mask)
                outs, count = compact(out_cvs, out_mask)
            else:
                outs, count = out_cvs, got
            # [rows received, rows out, bytes received, bytes of each
            # var-width column out]
            stats = [got_rows, count.astype(jnp.int64), got_bytes]
            for cv in outs:
                if cv.offsets is not None:
                    stats.append(cv.offsets[count].astype(jnp.int64))
            return list(outs), jnp.stack(stats)

        return MeshProgram(
            shard_fn, n, axis, cls="SpmdStageExec", tag=kind,
            key=(n_active, exprs_fp(ex_keys), kind) + ckey
            + (tuple(has_offsets),))

    def _one_group(self, n):
        """Staged batches that did not come in lockstep, as ONE group:
        each shard's batches concatenated and padded to one capacity on
        the shard's own device (power-of-two bucketed rows/bytes, like
        the round path's bounce buffer)."""
        wire = self.exchange.schema
        has_offsets = [f.dtype.is_variable_width for f in wire.fields]
        per_shard: List[List[DeviceBatch]] = [[] for _ in range(n)]
        for (h, _), s in zip(self._staged, self._shard_of):
            per_shard[s].append(h.materialize())
        row_cap = bucket_capacity(max(1, max(
            (sum(b.capacity for b in bs) for bs in per_shard if bs),
            default=1)))
        bcaps = []
        for ci in range(len(wire.fields)):
            if has_offsets[ci]:
                mx = max((sum(b.cvs()[ci].data.shape[0] for b in bs)
                          for bs in per_shard if bs), default=1)
                bcaps.append(bucket_capacity(max(mx, 1)))
            else:
                bcaps.append(0)
        group = []
        for bs in per_shard:
            if bs:
                cvs = [concat_cvs([b.cvs()[ci] for b in bs], f.dtype)
                       for ci, f in enumerate(wire.fields)]
                msk = concat_masks([b.row_mask for b in bs])
                cvs = [_pad_round_cv(cv, row_cap, bcaps[ci])
                       for ci, cv in enumerate(cvs)]
                msk = pad_mask(msk, row_cap)
            else:
                cvs = [_empty_cv(f.dtype, row_cap, bcaps[ci])
                       for ci, f in enumerate(wire.fields)]
                msk = jnp.zeros(row_cap, jnp.bool_)
            group.append(DeviceBatch(make_table(wire, cvs, row_cap),
                                     row_cap, msk, row_cap))
        return [group]

    def _run_fused(self, ctx: ExecContext, m):
        """Run THE stage program over the staged batches (each already
        on its shard's device), cut every shard's result to one bucketed
        capacity with a second small program over the mesh, park the
        results. One stats fetch; zero intermediate park/unpark."""
        from ..memory.retry import retry_no_split
        from ..memory.spill import spill_store
        from ..utils.transfer import fetch

        ex = self.exchange
        n = ex.n
        store = spill_store(ctx.conf)
        has_offsets = [f.dtype.is_variable_width for f in ex.schema.fields]
        out_has = [f.dtype.is_variable_width for f in self.schema.fields]

        with m.timer("partitionTime"):
            if self._groups is not None:
                groups = [[self._staged[i][0].materialize() for i in g]
                          for g in self._groups]
            else:
                groups = self._one_group(n)
            trees = self._group_trees(groups)
            # what crosses the mesh: every staged buffer as it is sent
            m.add("collectiveBytes", sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(trees)))

        nchunks = self._agg_nchunks(groups) if self.kind == "agg" else ()
        key = (tuple(has_offsets), nchunks, self._n_active)
        prog = self._jit_cache.get(key)
        if prog is None:
            prog = self._program(has_offsets, nchunks)
            self._jit_cache[key] = prog

        with m.timer("exchangeTime"):
            outs = prog(trees)
            # tpulint: allow[sync-under-lock] ONE stats fetch for the whole fused stage (the round path pays this per round); readers block on _lock until _out is set regardless
            fetched = fetch([st for _, st in outs])
            stats_h = [[int(v) for v in st] for st in fetched]

        # one capacity for every shard's result, so that the next
        # lockstep operator takes the n of them into one program
        rows = [st[1] for st in stats_h]
        vcap = outs[0][0][0].validity.shape[0]
        new_cap = min(bucket_capacity(max(max(rows), 1)), vcap)
        bcaps, si = [], 3
        for ci, cv in enumerate(outs[0][0]):
            if out_has[ci]:
                bcaps.append(min(bucket_capacity(max(
                    max(st[si] for st in stats_h), 1)), cv.data.shape[0]))
                si += 1
            else:
                bcaps.append(0)

        from .lockstep import cut_program
        cutp = cut_program(n, ex.axis_name, new_cap, tuple(bcaps), 1)
        out = []
        try:
            for s, (cvs, mask) in enumerate(cutp(outs)):
                batch = DeviceBatch(make_table(self.schema, cvs, rows[s]),
                                    rows[s], mask, new_cap)
                out.append(retry_no_split(
                    lambda b=batch: store.add_batch(b, priority=5)))
        except BaseException:
            for h in out:
                h.close()
            raise
        self._out = out
        self._out_rows = rows
        m.add("spmdStages", 1)
        m.add("numOutputRows", sum(rows))
        m.add("numOutputBatches", sum(1 for r in rows if r))
        # what each shard received (the skew of the hash partitioning)
        for name, at in (("shardRowsReceived", 0), ("shardBytesReceived", 2)):
            got = [st[at] for st in stats_h]
            m.add(name + "Max", max(got))
            m.add(name + "Min", min(got))
        # and the row slots it was sent: every peer sends a bucket of the
        # shard's whole capacity, so rows over slots is the wire's fill
        m.add("shardSlotsReceived",
              n * sum(g[0].row_mask.shape[0] for g in groups))

    # -- lifecycle -----------------------------------------------------
    def _drop(self):
        if self._out is not None:
            for h in self._out:
                h.close()
            self._out = None
        if self._staged is not None:
            for h, _ in self._staged:
                h.close()
            self._staged = None

    def release(self):
        with self._lock:
            self._drop()
        # release the fused operators (reaches the shared map subtree
        # exactly once through whichever member sits on top)
        self._fallback_node().release()

    def __del__(self):
        # unreachable, so there is no one to lock out; and a finalizer
        # runs inside whatever lock region the collector interrupts, so
        # it takes no lock of its own (members finalize themselves)
        try:
            self._drop()
        except Exception:
            pass
