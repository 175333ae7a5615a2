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
             in-trace merge (`_merge_body`, host sort disabled —
             pure_callback would deadlock inside shard_map) →
             `_finalize_fn`. One compiled program per stage.
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
from .batch import DeviceBatch
from .mesh_exchange import (MeshExchangeExec, _empty_cv, _flatten_cvs,
                            _local_shards, _pad_round_cv, _unflatten_cvs)
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
        self._out: Optional[List[List]] = None      # per shard: handles
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
        return list(self._jit_cache.values())

    # -- staging -------------------------------------------------------
    def _ensure_staged(self, ctx: ExecContext):
        """Drain the map side ONCE into spillable handles (priority 10,
        original drain order preserved) with exact per-batch byte
        accounting — the byte stats the AQE re-shard/demote rules and
        the working-set budget check read."""
        with self._lock:
            if self._staged is not None:
                return
            from ..memory.retry import retry_no_split
            from ..memory.spill import spill_store
            store = spill_store(ctx.conf)
            m = ctx.metrics_for(self._op_id)
            child = self.children[0]
            staged: List[Tuple] = []
            total = 0
            try:
                with m.timer("partitionTime"):
                    for cpid in range(child.num_partitions(ctx)):
                        for b in child.execute_partition(ctx, cpid):
                            ctx.check_cancel()
                            nbytes = int(b.nbytes)
                            total += nbytes
                            staged.append((retry_no_split(
                                lambda b=b: store.add_batch(
                                    b, priority=10)), nbytes))
            except BaseException:
                for h, _ in staged:
                    h.close()
                raise
            self._staged = staged
            self._staged_bytes = total
            m.set("spmdStagedBytes", total)

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
            if (not conf.get(SPMD_RESHARD_ENABLED)
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
                self._out = [[] for _ in range(self.exchange.n)]
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
                    from ..profiler import tracing
                    with tracing.span("spmd.degrade", "degrade", ctx,
                                      reason=type(e).__name__):
                        self._degrade(ctx, type(e).__name__)
                    faults.note_recovery("degradations")
                    return
                raise

    def _degrade(self, ctx: ExecContext, reason: str):
        """Swap the round-based exchange in over the staged handles.
        The exchange re-drains them in original order, so its output is
        byte-identical to a direct round-based run; the map side does
        NOT re-execute."""
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
        for h in self._out[pid]:
            yield h.materialize()

    # -- the fused program ---------------------------------------------
    def _gather_global(self, pieces, sharding, devices):
        """Per-shard pieces -> one global array, each piece device_put
        to its shard (no single-device staging; compression stays on
        the round-based path — one-shot stages move raw)."""
        shape = ((len(pieces) * pieces[0].shape[0],)
                 + tuple(pieces[0].shape[1:]))
        arrs = [jax.device_put(p, d) for p, d in zip(pieces, devices)]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrs)

    def _agg_nchunks(self, batches) -> Tuple[int, ...]:
        """Static string-chunk counts for the consumer's keys, measured
        over the staged wire batches (per-row string LENGTH is exchange-
        invariant, so pre-exchange maxima bound the merge's chunks).
        All measurements batch into ONE device fetch (the same
        live-rows-only rule as HashAggregateExec._nchunks_for)."""
        from ..columnar import dtypes as dt
        from ..ops import sortkeys as sk
        from ..utils.transfer import fetch
        keys = self.consumer.keys
        maxlens = []        # (key index, device max-len scalar)
        for b in batches:
            kcvs = list(b.cvs())[:len(keys)]
            for ki, (kcv, kexpr) in enumerate(zip(kcvs, keys)):
                if not isinstance(kexpr.dtype,
                                  (dt.StringType, dt.BinaryType)):
                    continue
                lens = kcv.offsets[1:] - kcv.offsets[:-1]
                lens = jnp.where(b.row_mask & kcv.validity, lens, 0)
                if lens.shape[0]:
                    maxlens.append((ki, jnp.max(lens)))
        # string keys floor at the 1-byte chunk count even when every
        # staged value is null/empty (matches _nchunks_for)
        ncs = [sk.nchunks_for_len(1)
               if isinstance(k.dtype, (dt.StringType, dt.BinaryType))
               else 0 for k in keys]
        if maxlens:
            # tpulint: allow[sync-under-lock] one batched max-length fetch while building the memoized stage program; readers block on _lock until _out is set regardless
            fetched = fetch([v for _, v in maxlens])
            for (ki, _), v in zip(maxlens, fetched):
                ncs[ki] = max(ncs[ki],
                              sk.nchunks_for_len(max(int(v), 1)))
        return tuple(ncs)

    def _program(self, has_offsets, out_has, nchunks):
        """Build (or fetch) THE one compiled program for this stage:
        partition ids + all_to_all + consumer, inside one shard_map.
        Keyed on the mesh topology first — collective lowering bakes in
        replica groups and ICI routing, so programs must never cross
        topologies (mesh-program-key lint rule)."""
        from jax.sharding import PartitionSpec as P
        from ..parallel.collectives import exchange_cvs
        from ..parallel.mesh import mesh_topology_key
        from ..runtime.program_cache import cached_program, exprs_fp

        ex = self.exchange
        mesh = ex._get_mesh()
        n = ex.n
        axis = ex.axis_name
        n_active = self._n_active
        # close over bound exprs / member protocols, never self: a
        # cached entry pinning the builder must not pin staged output
        ex_keys = ex.keys
        ex_key_dtypes = [k.dtype for k in ex_keys]
        kind = self.kind
        consumer = self.consumer
        chain_fns = [nd.fusable_stage() for nd in reversed(self.chain)]
        n_out_flat = sum(3 if ho else 2 for ho in out_has)

        if kind == "agg":
            ckey = consumer._fp + (nchunks,)
        elif kind == "chain":
            ckey = tuple(nd.stage_fingerprint() for nd in self.chain)
        else:
            ckey = ()

        def shard_fn(flat, mask):
            cvs = _unflatten_cvs(flat, has_offsets)
            cap = mask.shape[0]
            ectx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ectx) for k in ex_keys]
            pids = partition_ids(key_cvs, ex_key_dtypes, n_active)
            out_cvs, out_mask = exchange_cvs(cvs, mask, pids, n, axis)
            if kind == "agg":
                ocap = out_mask.shape[0]
                kctx = EmitCtx(out_cvs, ocap)
                mkeys = [k.emit(kctx) for k in consumer.keys]
                nkeys = len(consumer.keys)
                flat_states = [cv.data for cv in out_cvs[nkeys:]]
                # in-trace merge: host-callback sort force-disabled —
                # pure_callback deadlocks inside shard_map
                mk, mflat, mlive = consumer._merge_body(
                    mkeys, flat_states, out_mask, nchunks,
                    allow_host_sort=False)
                outs = consumer._finalize_fn(mk, mflat, mlive)
                count = jnp.sum(mlive.astype(jnp.int32))
            else:
                for fn in chain_fns:
                    out_cvs, out_mask = fn(out_cvs, out_mask)
                outs, count = compact(out_cvs, out_mask)
            stats = [count.astype(jnp.int64)]
            for cv in outs:
                if cv.offsets is not None:
                    stats.append(cv.offsets[count].astype(jnp.int64))
            return _flatten_cvs(outs), jnp.stack(stats)

        def step(flat, mask):
            return jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(tuple(P(axis) for _ in flat), P(axis)),
                out_specs=(tuple(P(axis) for _ in range(n_out_flat)),
                           P(axis)),
            )(tuple(flat), mask)

        return cached_program(
            step, cls="SpmdStageExec", tag=kind,
            key=(mesh_topology_key(n, axis), n_active, exprs_fp(ex_keys),
                 kind) + ckey + (tuple(has_offsets),))

    def _run_fused(self, ctx: ExecContext, m):
        """Assemble per-shard send batches from the staged handles, run
        THE stage program, slice each shard's live prefix, park the
        results. Exactly one compiled program; zero intermediate
        park/unpark."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..memory.retry import retry_no_split
        from ..memory.spill import spill_store
        from ..utils.transfer import fetch

        ex = self.exchange
        n = ex.n
        store = spill_store(ctx.conf)
        mesh = ex._get_mesh()
        sharding = NamedSharding(mesh, P(ex.axis_name))
        devices = list(mesh.devices.reshape(-1))
        wire = ex.schema
        has_offsets = [f.dtype.is_variable_width for f in wire.fields]
        out_has = [f.dtype.is_variable_width for f in self.schema.fields]

        with m.timer("partitionTime"):
            # deal staged batches round-robin onto shard slots; each
            # slot concatenates to ONE padded send batch (power-of-two
            # bucketed rows/bytes, like the round path's bounce buffer)
            per_shard: List[List[DeviceBatch]] = [[] for _ in range(n)]
            for i, (h, _) in enumerate(self._staged):
                per_shard[i % n].append(h.materialize())
            row_cap = bucket_capacity(max(1, max(
                (sum(b.capacity for b in bs) for bs in per_shard if bs),
                default=1)))
            bcaps = []
            for ci, f in enumerate(wire.fields):
                if has_offsets[ci]:
                    mx = max((sum(b.cvs()[ci].data.shape[0] for b in bs)
                              for bs in per_shard if bs), default=1)
                    bcaps.append(bucket_capacity(max(mx, 1)))
                else:
                    bcaps.append(0)
            shard_cvs, shard_masks = [], []
            for s in range(n):
                bs = per_shard[s]
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
                shard_cvs.append(cvs)
                shard_masks.append(msk)
            flat_global = []
            for ci in range(len(wire.fields)):
                parts = [shard_cvs[s][ci] for s in range(n)]
                flat_global.append(self._gather_global(
                    [p.data for p in parts], sharding, devices))
                flat_global.append(self._gather_global(
                    [p.validity for p in parts], sharding, devices))
                if has_offsets[ci]:
                    flat_global.append(self._gather_global(
                        [p.offsets for p in parts], sharding, devices))
            mask_global = self._gather_global(shard_masks, sharding,
                                              devices)
            m.add("collectiveBytes",
                  sum(int(a.nbytes) for a in flat_global)
                  + int(mask_global.nbytes))

        nchunks = (self._agg_nchunks([b for bs in per_shard for b in bs])
                   if self.kind == "agg" else ())
        key = (tuple(has_offsets), nchunks, self._n_active)
        prog = self._jit_cache.get(key)
        if prog is None:
            prog = self._program(has_offsets, out_has, nchunks)
            self._jit_cache[key] = prog

        with m.timer("exchangeTime"):
            out_flat, stats = prog(flat_global, mask_global)
            n_var = sum(1 for ho in out_has if ho)
            # tpulint: allow[sync-under-lock] ONE stats fetch for the whole fused stage (the round path pays this per round); readers block on _lock until _out is set regardless
            stats_h = fetch(stats).reshape(n, 1 + n_var)

        out: List[List] = [[] for _ in range(n)]
        # slice each shard's live prefix from its device-LOCAL piece:
        # indexing the global sharded array would lower to an
        # all-gather rendezvous, unsafe to interleave with any other
        # in-flight collective (see _local_shards)
        flat_loc = [_local_shards(a, n) for a in out_flat]
        try:
            for s in range(n):
                nlive = int(stats_h[s, 0])
                if nlive == 0:
                    continue
                cvs = []
                fi = 0
                si = 1
                for ci in range(len(self.schema.fields)):
                    vcap = out_flat[fi + 1].shape[0] // n
                    new_cap = min(bucket_capacity(nlive), vcap)
                    if out_has[ci]:
                        dcap = out_flat[fi].shape[0] // n
                        nbytes = int(stats_h[s, si])
                        si += 1
                        bcap_new = min(bucket_capacity(max(nbytes, 1)),
                                       dcap)
                        data = flat_loc[fi][s][:bcap_new]
                        valid = flat_loc[fi + 1][s][:new_cap]
                        offs = flat_loc[fi + 2][s][:new_cap + 1]
                        cvs.append(CV(data, valid, offs))
                        fi += 3
                    else:
                        data = flat_loc[fi][s][:new_cap]
                        valid = flat_loc[fi + 1][s][:new_cap]
                        cvs.append(CV(data, valid))
                        fi += 2
                tbl = make_table(self.schema, cvs, nlive)
                batch = DeviceBatch(tbl, nlive, None, new_cap)
                out[s].append(retry_no_split(
                    lambda b=batch: store.add_batch(b, priority=5)))
                m.add("numOutputRows", nlive)
        except BaseException:
            for pile in out:
                for h in pile:
                    h.close()
            raise
        self._out = out
        m.add("spmdStages", 1)
        m.add("numOutputBatches", sum(len(p) for p in out))

    # -- lifecycle -----------------------------------------------------
    def release(self):
        with self._lock:
            if self._out is not None:
                for pile in self._out:
                    for h in pile:
                        h.close()
                self._out = None
            if self._staged is not None:
                for h, _ in self._staged:
                    h.close()
                self._staged = None
        # release the fused operators (reaches the shared map subtree
        # exactly once through whichever member sits on top)
        self._fallback_node().release()

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass
