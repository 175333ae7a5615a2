"""Mesh (ICI collective) shuffle exchange — streaming, bounded-memory.

The multi-chip execution heart: instead of the in-process file shuffle
(shuffle/local.py — the MULTITHREADED-mode analog), the exchange runs as a
compiled SPMD program over a jax.sharding.Mesh: every shard computes target
partition ids locally, then `jax.lax.all_to_all` moves row payloads (and
string bytes) over ICI. Replaces the reference's UCX peer-to-peer transport
(reference: RapidsShuffleInternalManagerBase.scala:56, shuffle-plugin
UCXShuffleTransport.scala:49) with XLA collectives.

Bounded memory (the bounce-buffer analog): the child is drained into
per-shard input queues whose batches are registered as SPILLABLE handles,
then exchanged in ROUNDS — each round every shard contributes at most one
batch, padded to a fixed power-of-two row/byte capacity (the per-round
"bounce buffer"), and ONE collective program (compiled once, reused every
round) moves the rows. Received rows arrive as a live prefix (the exchange
places the peers' runs end to end), are sliced down to a bucketed capacity,
and parked as spillable handles until the consumer pulls them. Peak device
residency is therefore O(n_devices * round_capacity) for the in-flight round
plus whatever the spill store lets accumulate — skew changes how many rounds
a shard receives, not the padding (round-2's global-max padding multiplied
memory by n_devices under skew).

Downstream operators see `n` output partitions (one per shard/device), each
yielding a stream of batches holding exactly the rows whose keys hash to
that shard — the same ownership contract the hash file-shuffle provides, so
per-partition aggregation/join run unchanged on top.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.column import bucket_capacity
from ..columnar.table import Schema
from ..expr.expressions import EmitCtx, Expression
from ..ops.concat import pad_cv, pad_mask
from ..ops.hash import partition_ids
from ..ops.kernel_utils import CV
from .base import ExecContext, TpuExec
from .batch import DeviceBatch
from .nodes import make_table

__all__ = ["MeshExchangeExec"]

# end-of-partition marker in the parallel drain's per-partition queues
_DRAIN_DONE = object()


class MeshExchangeExec(TpuExec):
    """Hash partition exchange over a device mesh, in chunked collective
    rounds with spillable accumulation on both sides."""

    def __init__(self, child: TpuExec, n_devices: int,
                 bound_keys: Sequence[Expression], schema: Schema,
                 axis_name: str = "data"):
        super().__init__([child], schema)
        self.n = n_devices
        self.keys = list(bound_keys)
        self.axis_name = axis_name
        self._mesh = None
        self._out: Optional[List[List]] = None   # per shard: spill handles
        from ..runtime import lockdep
        self._lock = lockdep.rlock("MeshExchangeExec._lock")
        self._jit_cache = {}
        self._compress = False    # set per-execution from conf

    def describe(self):
        return f"MeshExchangeExec[hash, devices={self.n}]"

    def num_partitions(self, ctx):
        return self.n

    # ------------------------------------------------------------------
    def _get_mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import make_mesh
            self._mesh = make_mesh(self.n, self.axis_name)
        return self._mesh

    def _build_program(self, has_offsets):
        """shard_map program: emit keys -> pids -> exchange.

        Per shard, returns the received rows (a live prefix as they come),
        plus a stats vector [row_count, bytes_col0, bytes_col1, ...] so the
        host can slice buffers down without extra device syncs."""
        from jax.sharding import PartitionSpec as P
        from ..parallel.collectives import exchange_cvs

        mesh = self._get_mesh()
        n = self.n
        axis = self.axis_name
        # close over the bound key exprs, never self: a cached entry
        # pinning the builder must not pin this exchange's parked output
        keys = self.keys
        key_dtypes = [k.dtype for k in keys]

        def shard_fn(flat, mask):
            cvs = _unflatten_cvs(flat, has_offsets)
            cap = mask.shape[0]
            ectx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ectx) for k in keys]
            pids = partition_ids(key_cvs, key_dtypes, n)
            # the received rows are already a live prefix of `count`
            out_cvs, _, count = exchange_cvs(cvs, mask, pids, n, axis)
            stats = [count.astype(jnp.int64)]
            for cv in out_cvs:
                if cv.offsets is not None:
                    stats.append(cv.offsets[count].astype(jnp.int64))
            return _flatten_cvs(out_cvs), jnp.stack(stats)

        def step(flat, mask):
            return jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(tuple(P(axis) for _ in flat), P(axis)),
                out_specs=(tuple(P(axis) for _ in flat), P(axis)),
            )(tuple(flat), mask)

        from ..parallel.mesh import mesh_topology_key
        from ..runtime.program_cache import cached_program, exprs_fp
        # the key leads with the mesh topology (n_devices, axis, device
        # kind): collective lowering bakes in replica groups and ICI
        # routing, so programs must never cross topologies
        return cached_program(
            step, cls="MeshExchangeExec", tag="step",
            key=(mesh_topology_key(n, axis), exprs_fp(keys),
                 tuple(has_offsets)))

    # ------------------------------------------------------------------
    def _assemble_global(self, pieces, sharding, devices, m=None):
        """Build the round's global array from per-shard pieces WITHOUT a
        host/single-device concatenate: each piece is device_put to its
        target shard (D2D/DMA on hardware — the device-resident bounce
        buffer, vs r3's jnp.concatenate + device_put which staged every
        round through one device; reference keeps bounce buffers
        device-resident too, UCXShuffleTransport.scala:49).

        With mesh.shuffle.compress on, each piece plane-pack-compresses
        on its SOURCE device, the bucketed compressed bytes make the
        move, and the TARGET device decompresses — the device-side
        shuffle-compression analog of NvcompLZ4CompressionCodec."""
        shape = ((len(pieces) * pieces[0].shape[0],)
                 + tuple(pieces[0].shape[1:]))
        if self._compress:
            from ..columnar.column import bucket_capacity
            from ..ops.device_codec import (compress_array,
                                            decompress_array)
            from ..utils.transfer import fetch
            # compress everything first, then ONE batched size fetch —
            # a per-piece sync would serialize every column of every
            # shard and undo the round's async pipelining
            packed = [compress_array(p) for p in pieces]
            # tpulint: allow[sync-under-lock] one batched size fetch inside the memoized exchange build; readers block on _lock until _out is set regardless
            totals = [int(v) for v in fetch([t for _, t, _ in packed])]
            arrs = []
            for (comp, _t, nbytes), t, p, d in zip(packed, totals,
                                                   pieces, devices):
                if nbytes and t < nbytes:           # worth moving packed
                    cap = min(bucket_capacity(max(t, 1)),
                              comp.shape[0])
                    moved = jax.device_put(comp[:cap], d)
                    arrs.append(decompress_array(moved, nbytes, p.shape,
                                                 p.dtype))
                    if m is not None:
                        m.add("compressedBytes", t)
                        m.add("rawBytes", nbytes)
                else:
                    arrs.append(jax.device_put(p, d))
                    if m is not None:
                        m.add("compressedBytes", nbytes)
                        m.add("rawBytes", nbytes)
        else:
            arrs = [jax.device_put(p, d) for p, d in zip(pieces, devices)]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrs)

    def _dispatch_round(self, m, slot_handles, sharding, devices,
                        has_offsets):
        """Assemble one round's send buffers (≤ n batches, one per shard
        slot) and dispatch the collective program asynchronously.
        Returns (out_flat, stats, row_cap, bcaps) with stats NOT yet
        fetched — the caller overlaps the next round's assembly with
        this round's device execution (double buffering)."""
        n = self.n
        with m.timer("partitionTime"):
            batches = [h.materialize() for h in slot_handles]
            # per-round capacities: power-of-two bucketed so padding
            # amplification is a constant (<2x) and the jit cache stays
            # small under varying batch sizes
            row_cap = bucket_capacity(max(b.capacity for b in batches))
            bcaps = []
            for ci, f in enumerate(self.schema.fields):
                if has_offsets[ci]:
                    bcaps.append(bucket_capacity(max(
                        b.cvs()[ci].data.shape[0] for b in batches)))
                else:
                    bcaps.append(0)
            shard_cvs, shard_masks = [], []
            for s in range(n):
                if s < len(batches):
                    b = batches[s]
                    cvs = [_pad_round_cv(cv, row_cap, bcaps[ci])
                           for ci, cv in enumerate(b.cvs())]
                    msk = pad_mask(b.row_mask, row_cap)
                else:
                    cvs = [_empty_cv(f.dtype, row_cap, bcaps[ci])
                           for ci, f in enumerate(self.schema.fields)]
                    msk = jnp.zeros(row_cap, jnp.bool_)
                shard_cvs.append(cvs)
                shard_masks.append(msk)
            for h in slot_handles:
                h.close()

            flat_global = []
            for ci in range(len(self.schema.fields)):
                parts = [shard_cvs[s][ci] for s in range(n)]
                flat_global.append(self._assemble_global(
                    [p.data for p in parts], sharding, devices, m))
                flat_global.append(self._assemble_global(
                    [p.validity for p in parts], sharding, devices, m))
                if has_offsets[ci]:
                    flat_global.append(self._assemble_global(
                        [p.offsets for p in parts], sharding, devices,
                        m))
            mask_global = self._assemble_global(shard_masks, sharding,
                                                devices, m)
            m.add("meshRounds", 1)
            m.add("collectiveBytes",
                  sum(int(a.nbytes) for a in flat_global)
                  + int(mask_global.nbytes))

        with m.timer("exchangeTime"):
            key = tuple(has_offsets)
            prog = self._jit_cache.get(key)
            if prog is None:
                prog = self._build_program(has_offsets)
                self._jit_cache[key] = prog
            out_flat, stats = prog(flat_global, mask_global)
        return out_flat, stats, row_cap, bcaps

    def _collect_round(self, ctx, m, store, out, rnd_state, has_offsets,
                       n_str):
        """Fetch a dispatched round's stats (blocks until the device
        finishes it), slice each shard's live prefix to a bucketed
        capacity, and park the output as spillable handles. Runs on the
        collector thread; polls the cancel token between shards so a
        killed query stops parking mid-round."""
        out_flat, stats, row_cap, bcaps = rnd_state
        n = self.n
        ctx.check_cancel()
        with m.timer("exchangeTime"):
            from ..utils.transfer import fetch
            # tpulint: allow[sync-under-lock] round collection is double-buffered INSIDE the memoized build; the fetch overlaps the next round's collective and readers need _out anyway
            stats_h = fetch(stats).reshape(n, 1 + n_str)
        out_cap = n * row_cap
        # collect each shard from its device-LOCAL piece: basic
        # indexing on the GLOBAL sharded array lowers to an all-gather,
        # and with the next round's all_to_all already in flight on the
        # dispatch thread the two rendezvous interleave on the same
        # device threads and deadlock each other (XLA collectives
        # rendezvous by arrival, not by launch). Local-shard slices are
        # single-device programs: no rendezvous, overlap stays safe.
        flat_loc = [_local_shards(a, n) for a in out_flat]
        for s in range(n):
            ctx.check_cancel()
            nlive = int(stats_h[s, 0])
            if nlive == 0:
                continue
            # clamp to the shard's receive region: out_cap is not a
            # power of two when n_devices isn't
            new_cap = min(bucket_capacity(nlive), out_cap)
            cvs = []
            fi = 0
            si = 1
            for ci, f in enumerate(self.schema.fields):
                if has_offsets[ci]:
                    bc = n * bcaps[ci]
                    nbytes = int(stats_h[s, si])
                    si += 1
                    bcap_new = min(bucket_capacity(nbytes), bc)
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
            out[s].append(store.add_batch(batch, priority=5))
            m.add("numOutputRows", nlive)

    def _ensure_exchanged(self, ctx: ExecContext):
        with self._lock:
            if self._out is not None:
                return
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..config import MESH_COMPRESS
            from ..memory.spill import spill_store
            store = spill_store(ctx.conf)
            self._compress = bool(ctx.conf.get(MESH_COMPRESS))
            m = ctx.metrics_for(self._op_id)
            mesh = self._get_mesh()
            child = self.children[0]
            n = self.n
            sharding = NamedSharding(mesh, P(self.axis_name))
            devices = list(mesh.devices.reshape(-1))
            # var-width-ness is a schema property (not observed bytes):
            # every round runs the same program shape
            has_offsets = [f.dtype.is_variable_width
                           for f in self.schema.fields]
            n_str = sum(1 for h in has_offsets if h)

            # STREAMING: no full pre-drain (r3 buffered the entire child
            # before round 1). Child batches fill an n-slot round; as
            # soon as it's full the round dispatches, and its collection
            # — the blocking per-round stats fetch — moves to a
            # single-thread collector so the orchestration thread goes
            # straight back to draining the child and assembling the
            # NEXT round (r5 collected round k-1 inline on the
            # orchestration thread, which stalled round k+1's dispatch
            # behind a device sync). One collector thread keeps round
            # collection in dispatch order, so the per-shard output
            # piles — and therefore exchange output — stay
            # byte-identical to the serial collect.
            import concurrent.futures as cf
            out: List[List] = [[] for _ in range(n)]
            slot: List = []
            collector = cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="mesh-collect")
            futs: List = []

            def flush(slot_handles):
                """Dispatch a round; hand its collection to the
                collector thread so the stats fetch overlaps the next
                round's assembly and dispatch."""
                # surface a collector failure before dispatching more
                for f in futs:
                    if f.done():
                        # tpulint: allow[wait-under-lock] guarded by f.done() — result() never blocks here, it only rethrows a finished collect's failure
                        f.result()
                cur = self._dispatch_round(m, slot_handles, sharding,
                                           devices, has_offsets)
                futs.append(collector.submit(
                    self._collect_round, ctx, m, store, out, cur,
                    has_offsets, n_str))

            nparts = child.num_partitions(ctx)
            from .exchange_pool import PermitRider, resolve_map_threads
            threads = resolve_map_threads(ctx, nparts)
            queues: List = []
            try:
                if threads <= 1 or nparts <= 1:
                    for cpid in range(nparts):
                        for b in child.execute_partition(ctx, cpid):
                            ctx.check_cancel()
                            # waiting slot batches are spillable: a slow
                            # child partition must not pin up to n-1
                            # batches in HBM
                            slot.append(store.add_batch(b, priority=10))
                            if len(slot) == n:
                                flush(slot)
                                slot = []
                else:
                    slot = self._parallel_drain(
                        ctx, store, child, nparts, threads, queues,
                        slot, flush, m, PermitRider)
                if slot:
                    flush(slot)
                    slot = []
                for f in futs:
                    # tpulint: allow[wait-under-lock] the end-of-exchange barrier: the collector thread never takes this lock, its rounds are bounded device work, and _collect_round polls the cancel token
                    f.result()
                collector.shutdown(wait=True)
            except BaseException:
                # failing mid-stream (upstream OOM, bad data, cancel)
                # must not leak: let in-flight collects finish parking
                # (so their handles are visible below), then close
                # waiting queue/slot handles and everything parked so
                # far; self._out stays None so a retried action re-runs
                # the exchange from a clean slate
                collector.shutdown(wait=True)
                for q in queues:
                    while True:
                        try:
                            item = q.get_nowait()
                        except Exception:
                            break
                        if item is not _DRAIN_DONE:
                            item.close()
                for h in slot:
                    h.close()
                for pile in out:
                    for h in pile:
                        h.close()
                raise
            self._out = out

    def _parallel_drain(self, ctx, store, child, nparts, threads,
                        queues, slot, flush, m, PermitRider):
        """Drain child partitions on a bounded worker pool. Workers park
        batches as spillable handles into per-partition queues; the
        calling thread consumes the queues in STRICT cpid order, feeding
        the same n-slot rounds as the serial drain — round composition
        (and therefore exchange output) stays byte-identical. Device
        admission per child step goes through the PermitRider so chip
        concurrency stays bounded by sql.concurrentTpuTasks."""
        import concurrent.futures as cf
        import queue as _queue
        from .nodes import _session_semaphore
        sem = _session_semaphore(ctx)
        rider = PermitRider(sem,
                            priority=getattr(ctx, "sem_priority", 0),
                            token=ctx.cancel)
        stop = threading.Event()
        n = self.n
        queues.extend(_queue.Queue(maxsize=4) for _ in range(nparts))

        def put_item(q, item):
            """Bounded put that stays cancellable; returns False when
            the drain was aborted before hand-off."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except _queue.Full:
                    ctx.check_cancel()
            return False

        def produce(cpid):
            q = queues[cpid]
            it = child.execute_partition(ctx, cpid)
            while True:
                ctx.check_cancel()
                if stop.is_set():
                    return
                with rider.step():
                    b = next(it, None)
                    h = (None if b is None
                         else store.add_batch(b, priority=10))
                if h is None:
                    break
                if not put_item(q, h):
                    h.close()
                    return
            put_item(q, _DRAIN_DONE)

        with cf.ThreadPoolExecutor(
                threads, thread_name_prefix="tpu-mesh-map") as pool:
            futs = [pool.submit(produce, cpid)
                    for cpid in range(nparts)]
            try:
                for cpid in range(nparts):
                    q = queues[cpid]
                    while True:
                        try:
                            item = q.get(timeout=0.05)
                        except _queue.Empty:
                            ctx.check_cancel()
                            f = futs[cpid]
                            if f.done() and f.exception() is not None:
                                raise f.exception()
                            continue
                        if item is _DRAIN_DONE:
                            break
                        slot.append(item)
                        if len(slot) == n:
                            flush(slot)
                            slot = []
                for f in futs:
                    # tpulint: allow[wait-under-lock] producer join under the memoizing _lock: queues already drained _DRAIN_DONE so workers are exiting; PermitRider kept them off blocking sem.acquire
                    f.result()
            except BaseException:
                stop.set()  # unblock producers parked on full queues
                for f in futs:
                    f.cancel()
                raise
        if rider.waited_secs > 0:
            m.add("mapPoolWaitMs", round(rider.waited_secs * 1e3, 3))
        return slot

    def execute_partition(self, ctx: ExecContext, pid: int):
        self._ensure_exchanged(ctx)
        # handles stay open: the session caches exec trees, so a second
        # action re-pulls the same partitions. Unused handles demote to
        # host/disk under pressure instead of pinning HBM; release()
        # closes them when the owning plan is dropped.
        for h in self._out[pid]:
            yield h.materialize()

    def release(self):
        """Close parked exchange outputs (ADVICE r3 medium: without
        this, every mesh query leaks device-budget accounting, host
        memory, and spill files for the process lifetime)."""
        with self._lock:
            self._drop()
        super().release()

    def _drop(self):
        if self._out is not None:
            for pile in self._out:
                for h in pile:
                    h.close()
            self._out = None

    def __del__(self):
        # unreachable, so there is no one to lock out; and a finalizer
        # runs inside whatever lock region the collector interrupts, so
        # it takes no lock of its own (children finalize themselves)
        try:
            self._drop()
        except Exception:
            pass


def _local_shards(arr, n: int):
    """Per-device local pieces of a 1-D array sharded n ways, ordered
    by shard position. Slicing these is a single-device program; the
    equivalent slice of the GLOBAL array lowers to an all-gather whose
    rendezvous can deadlock against another in-flight collective."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards or len(shards) != n:
        # unsharded (single-device / committed) array: fall back to
        # host-side views of the global buffer
        shard_len = arr.shape[0] // n
        return [arr[s * shard_len:(s + 1) * shard_len] for s in range(n)]
    from ..parallel.mesh_program import local_pieces
    return local_pieces(arr, n)


def _flatten_cvs(cvs: Sequence[CV]):
    flat = []
    for cv in cvs:
        flat.append(cv.data)
        flat.append(cv.validity)
        if cv.offsets is not None:
            flat.append(cv.offsets)
    return tuple(flat)


def _unflatten_cvs(flat, has_offsets):
    cvs, i = [], 0
    for ho in has_offsets:
        if ho:
            cvs.append(CV(flat[i], flat[i + 1], flat[i + 2]))
            i += 3
        else:
            cvs.append(CV(flat[i], flat[i + 1]))
            i += 2
    return cvs


def _empty_cv(dtype: dt.DataType, cap: int, bcap: int) -> CV:
    if dtype.is_variable_width:
        return CV(jnp.zeros(bcap, jnp.uint8), jnp.zeros(cap, jnp.bool_),
                  jnp.zeros(cap + 1, jnp.int32))
    from ..columnar.column import alloc_shape
    return CV(jnp.zeros(alloc_shape(dtype, cap), dtype.np_dtype or jnp.int8),
              jnp.zeros(cap, jnp.bool_))


def _pad_round_cv(cv: CV, cap: int, byte_cap: int) -> CV:
    cv = pad_cv(cv, cap)
    if cv.offsets is not None and cv.data.shape[0] != byte_cap:
        if cv.data.shape[0] < byte_cap:
            extra = byte_cap - cv.data.shape[0]
            cv = CV(jnp.concatenate([cv.data,
                                     jnp.zeros(extra, jnp.uint8)]),
                    cv.validity, cv.offsets)
        else:
            cv = CV(cv.data[:byte_cap], cv.validity, cv.offsets)
    return cv
