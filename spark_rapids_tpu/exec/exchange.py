"""Shuffle exchange operator over the local multithreaded transport.

(reference: GpuShuffleExchangeExecBase.scala:174 — partition ids computed
on device, contiguous-split into per-partition sub-batches, serializer on
host.) Map side runs one fused XLA program per batch: murmur3 partition
ids (or round-robin, or range bounds), then `_finish_map` brings the rows
into stable target order WITHOUT indexing them: every fixed-width array
(data, validity, decimal128 limbs, a struct's leaves) rides one
two-operand stable sort keyed by the target, a 32-bit word a turn
(`ops/partition.py:sorted_by_target`, the routine the mesh exchange
uses), and the per-partition counts are counted (n reductions), not
scattered. Only a variable-width column (string, list) is still gathered,
by the row index that rode the same sort. `mapSortWords` and
`mapGatheredColumns` say which of the two a pass did. Then a single bulk
D2H and host slicing into serializer sub-batches. Reduce side is
LocalShuffle.reduce_batch (host concat + one H2D).
"""
from __future__ import annotations

import threading
import uuid
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.table import Schema
from ..expr.expressions import EmitCtx, Expression
from ..ops.gather import take
from ..ops.hash import partition_ids
from ..ops.kernel_utils import CV
from ..ops.partition import runs_by_target, sorted_by_target, word_count
from ..profiler import tracing
from ..shuffle.local import LocalShuffle
from ..shuffle.serializer import HostSubBatch
from ..utils.transfer import fetch
from .base import ExecContext, TpuExec
from .batch import DeviceBatch

__all__ = ["ShuffleExchangeExec", "RangeShuffleExchangeExec",
           "map_partitions_executed"]

# process-global count of map partitions actually EXECUTED (not served
# from a materialized shuffle): the exchange-reuse acceptance counter —
# a deduped plan must show the same delta as its single-occurrence run
_map_exec_lock = threading.Lock()
_map_exec_stats = {"partitions": 0}


def map_partitions_executed() -> int:
    with _map_exec_lock:
        return _map_exec_stats["partitions"]


def _count_map_exec(n: int = 1):
    with _map_exec_lock:
        _map_exec_stats["partitions"] += n


def _has_var(cv) -> bool:
    return cv.offsets is not None or any(_has_var(ch) for ch in cv.children)


def _riders(cv, out):
    """Append the fixed-width arrays of `cv` that ride the sort, in the
    order `_in_target_order` takes them back: validity, then data or a
    struct's fields. A string or list goes by `take` and adds none."""
    if cv.offsets is not None:
        return
    out.append(cv.validity)
    if not cv.children:
        out.append(cv.data)
    for ch in cv.children:
        _riders(ch, out)


def _in_target_order(cv, riders, order, live):
    """`cv` in target order: from the sorted `riders` where it rode,
    else by `take` with the row index that rode beside them."""
    if cv.offsets is not None:
        return take(cv, order, in_bounds=live)
    valid = next(riders) & live
    if not cv.children:
        return CV(next(riders), valid)
    return CV(jnp.zeros(0, jnp.int8), valid, None,
              tuple(_in_target_order(ch, riders, order, live)
                    for ch in cv.children))


def _payload(cvs):
    """(riders, gathered): the arrays of `cvs` that ride the sort and
    the number of columns that hold a variable-width buffer."""
    riders = []
    for cv in cvs:
        _riders(cv, riders)
    return riders, sum(_has_var(cv) for cv in cvs)


def map_sort_shape(cvs):
    """(words, gathered) of one map pass over `cvs`: the 32-bit words a
    row takes through the sort (`mapSortWords`; the row index is one
    where a column is gathered) and the columns that go by `take`
    (`mapGatheredColumns`). Reads shapes and dtypes only."""
    riders, gathered = _payload(cvs)
    return word_count(riders) + bool(gathered), gathered


def _finish_map(cvs, mask, pids, n):
    """Shared map-side tail: the columns in stable target order (dead
    rows after every live row, validity false), per-partition counts.
    Every fixed-width array rides ONE sort by target
    (`ops/partition.py`); a string or list column is gathered by the row
    index that rode the same sort. Counts are counted, not scattered."""
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    eff, starts = runs_by_target(mask, pids, n)
    riders, gathered = _payload(cvs)
    if gathered:
        riders.append(rows)
    if riders:
        riders = sorted_by_target(eff, riders)
    order = riders[-1] if gathered else None
    live = rows < starts[n]
    riders = iter(riders)
    out = [_in_target_order(cv, riders, order, live) for cv in cvs]
    return out, starts[1:] - starts[:-1]


class ShuffleExchangeExec(TpuExec):
    def __init__(self, child: TpuExec, num_partitions: int,
                 bound_keys: Optional[Sequence[Expression]],
                 schema: Schema):
        super().__init__([child], schema)
        self.n = num_partitions
        self.keys = list(bound_keys) if bound_keys else None
        self._shuffle: Optional[LocalShuffle] = None
        self._pstats: Optional[List[int]] = None
        from ..runtime import lockdep
        self._lock = lockdep.rlock("ShuffleExchangeExec._lock")
        # the program closes over plan-time config only (n + bound key
        # exprs), never self: a cached entry pinning the builder must
        # not pin this instance's shuffle files / partition stats
        from ..runtime.program_cache import cached_program, exprs_fp
        self._jit = cached_program(
            self._build_map_fn(self.n, self.keys),
            cls=type(self).__name__, tag="map",
            key=(self.n,
                 exprs_fp(self.keys) if self.keys else None))

    def describe(self):
        mode = "hash" if self.keys else "roundrobin"
        return f"ShuffleExchangeExec[{mode}, n={self.n}]"

    def num_partitions(self, ctx):
        return self.n

    # ---- map-side device program --------------------------------------
    def _run_map(self, cvs, mask):
        """Dispatch the cached map-side program for one batch (the
        OOM-retry injection seam for tests)."""
        return self._jit(cvs, mask, *self._map_args())

    def _map_args(self):
        """Extra traced arguments appended to the map program call
        (range bounds — device data must be traced, never baked)."""
        return ()

    @staticmethod
    def _build_map_fn(n, keys):
        def _compute_pids(cvs, mask):
            """int32[cap] target partition per row."""
            cap = mask.shape[0]
            if not keys:
                return ((jnp.cumsum(mask.astype(jnp.int32)) - 1)
                        % n).astype(jnp.int32)
            ctx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ctx) for k in keys]
            return partition_ids(key_cvs, [k.dtype for k in keys], n)

        def _map_fn(cvs, mask):
            return _finish_map(cvs, mask, _compute_pids(cvs, mask), n)
        return _map_fn

    def release(self):
        sh, self._shuffle = self._shuffle, None
        self._pstats = None
        if sh is not None:
            try:
                sh.cleanup()   # frees map files + the arena's host-
            except Exception:  # budget reservation
                pass
        super().release()

    # ---- map phase ------------------------------------------------------
    def _ensure_shuffled(self, ctx: ExecContext):
        with self._lock:
            if self._shuffle is not None:
                return
            from ..config import (SHUFFLE_COMPRESS, SHUFFLE_DIR,
                                  SHUFFLE_READER_THREADS,
                                  SHUFFLE_WRITER_THREADS)
            sh = LocalShuffle(
                uuid.uuid4().hex[:12], self.n, self.schema,
                shuffle_dir=ctx.conf.get(SHUFFLE_DIR),
                writer_threads=ctx.conf.get(SHUFFLE_WRITER_THREADS),
                reader_threads=ctx.conf.get(SHUFFLE_READER_THREADS),
                codec=ctx.conf.get(SHUFFLE_COMPRESS))
            m = ctx.metrics_for(self._op_id)
            child = self.children[0]
            from ..memory.retry import with_retry

            def map_one(batch):
                """Idempotent map-side partition pass for one (sub)batch:
                device partition + ONE bulk D2H (split-and-retry safe —
                halves simply produce more sub-batches per partition)."""
                from ..runtime import faults
                if faults.ACTIVE:
                    # inside the with_retry wrapper: an injected
                    # RESOURCE_EXHAUSTED exercises the split-retry path
                    faults.hit("exchange.map", query_id=ctx.query_id,
                               op=type(self).__name__)
                with m.timer("partitionTime"):
                    from ..shuffle.serializer import cv_shuffle_bufs
                    cvs = batch.cvs()
                    out, counts = self._run_map(cvs, batch.row_mask)
                    words, gathered = map_sort_shape(cvs)
                    m.add("mapSortWords", words)
                    m.add("mapGatheredColumns", gathered)
                    # tpulint: allow[sync-under-lock] the map phase IS the critical section: _lock memoizes the whole shuffle build and readers only need it after _shuffle is set
                    host = fetch({
                        "cols": [cv_shuffle_bufs(cv) for cv in out],
                        "counts": counts,
                    })
                    m.add("shuffleD2HBytes", sum(
                        a.nbytes for a in jax.tree_util.tree_leaves(host)))
                    return host

            def slice_into(host, pieces):
                """Host-side: cut one map pass output into per-reduce
                sub-batches (numpy views, no device work)."""
                # tpulint: allow[host-sync] `host` is map_one's fetch output (numpy views)
                counts_h = np.asarray(host["counts"])
                starts = np.concatenate(
                    [[0], np.cumsum(counts_h)]).astype(np.int64)
                for rp in range(self.n):
                    cnt = int(counts_h[rp])
                    if cnt == 0:
                        continue
                    lo, hi = int(starts[rp]), int(starts[rp] + cnt)
                    from ..shuffle.serializer import slice_host_col
                    cols = [slice_host_col(cb, lo, hi)
                            for cb in host["cols"]]
                    pieces[rp].append(HostSubBatch(cols, cnt))

            def map_partition(mpid, rider=None, stop=None):
                """One full map task: child execute + device partition
                pass (permit-bounded when pooled), host slicing, shuffle
                write. Workers write to their own mpid-keyed file, so
                pool completion order never changes reduce-side bytes."""
                pieces = [[] for _ in range(self.n)]
                it = child.execute_partition(ctx, mpid)
                while True:
                    ctx.check_cancel()
                    if stop is not None and stop.is_set():
                        return  # a sibling worker failed; unwind quietly
                    if rider is None:
                        batch = next(it, None)
                        hosts = (None if batch is None
                                 else list(with_retry(batch, map_one)))
                    else:
                        # device admission: ride the caller's permit or
                        # take a real one (exchange_pool.PermitRider)
                        with rider.step():
                            batch = next(it, None)
                            hosts = (None if batch is None
                                     else list(with_retry(batch,
                                                          map_one)))
                    if batch is None:
                        break
                    for host in hosts:
                        with tracing.span("shuffle.slice", "op"):
                            slice_into(host, pieces)
                with m.timer("writeTime"):
                    sh.write_map_partition(mpid, pieces)
                _count_map_exec()

            nparts = child.num_partitions(ctx)
            from .exchange_pool import PermitRider, resolve_map_threads
            threads = resolve_map_threads(ctx, nparts)
            try:
                if threads <= 1 or nparts <= 1:
                    for mpid in range(nparts):
                        map_partition(mpid)
                else:
                    import concurrent.futures as cf
                    from .nodes import _session_semaphore
                    sem = _session_semaphore(ctx)
                    rider = PermitRider(
                        sem, priority=getattr(ctx, "sem_priority", 0),
                        token=ctx.cancel)
                    stop = threading.Event()
                    _tc = tracing.current()

                    def _map_task(mpid, rider, stop):
                        # seed the worker with the submitting query's
                        # trace context: pool_wait/compile spans opened
                        # inside parent under this map-task span
                        ctx.check_cancel()
                        with tracing.use(_tc), \
                                tracing.span("exchange.map",
                                             "pool_task", mpid=mpid):
                            map_partition(mpid, rider, stop)

                    with cf.ThreadPoolExecutor(
                            threads,
                            thread_name_prefix="tpu-exch-map") as pool:
                        futs = [pool.submit(_map_task, mpid, rider,
                                            stop)
                                for mpid in range(nparts)]
                        try:
                            # tpulint: allow[wait-under-lock] map-pool join under the memoizing _lock is the design: PermitRider guarantees worker progress (rides the caller's permit), and other readers must wait for materialization anyway
                            for f in cf.as_completed(futs):
                                # tpulint: allow[wait-under-lock] same join as the line above; sibling failure breaks the loop via stop+cancel
                                f.result()
                        except BaseException:
                            stop.set()  # drain in-flight workers fast
                            for f in futs:
                                f.cancel()
                            raise
                    if rider.waited_secs > 0:
                        # Ms suffix on purpose: op_time_seconds sums
                        # *Time keys and pool wait is not operator time
                        m.add("mapPoolWaitMs",
                              round(rider.waited_secs * 1e3, 3))
            except BaseException:
                sh.cleanup()  # cancelled/failed map phase leaks nothing
                raise
            m.set("mapPartitionsExecuted", nparts)
            # data-movement visibility (the Theseus point PAPERS.md
            # makes): serialized bytes through this exchange, for the
            # event log / EXPLAIN ANALYZE
            m.set("shuffleBytesWritten", sh.metrics["bytesWritten"])
            # the same blocks before the codec, and how many there were
            m.set("shuffleRawBytes", sh.metrics["rawBytesWritten"])
            m.set("shuffleBlocksWritten", sh.metrics["blocksWritten"])
            self._pstats = sh.partition_stats()
            # exact per-reduce-partition byte distribution (write-time
            # accumulated, shuffle/local.py) — the skew detector's
            # input, surfaced in EXPLAIN ANALYZE and the event log
            ordered = sorted(self._pstats)
            m.set("shufflePartitionBytesMin", int(ordered[0]))
            m.set("shufflePartitionBytesMedian",
                  int(ordered[len(ordered) // 2]))
            m.set("shufflePartitionBytesMax", int(ordered[-1]))
            self._shuffle = sh

    # ---- adaptive stage API (GpuCustomShuffleReaderExec inputs) --------
    def stage_stats(self, ctx: ExecContext):
        """Materialize the map stage and return serialized bytes per
        reduce partition (MapOutputStatistics analog)."""
        self._ensure_shuffled(ctx)
        return self._pstats

    def read_slice(self, ctx: ExecContext, rpid: int, chunk: int = 0,
                   nchunks: int = 1):
        self._ensure_shuffled(ctx)
        m = ctx.metrics_for(self._op_id)
        from ..memory.retry import retry_no_split
        pstats = getattr(self, "_pstats", None)
        if pstats is not None and rpid < len(pstats):
            m.add("shuffleBytesRead", pstats[rpid] // max(nchunks, 1))
        with m.timer("fetchAndMergeTime"):
            if nchunks == 1:
                batch = retry_no_split(
                    lambda: self._shuffle.reduce_batch(rpid))
            else:
                batch = retry_no_split(
                    lambda: self._shuffle.reduce_batch_slice(rpid, chunk,
                                                             nchunks))
        if batch is not None:
            # the padded buffers the upload moved, bucket padding and all
            m.add("shuffleH2DBytes", batch.table.nbytes)
        return batch

    def execute_partition(self, ctx: ExecContext, pid: int):
        batch = self.read_slice(ctx, pid)
        if batch is not None:
            ctx.metrics_for(self._op_id).add("numOutputBatches", 1)
            yield batch


class RangeShuffleExchangeExec(ShuffleExchangeExec):
    """Range partitioning (reference: GpuRangePartitioner.scala —
    sample-based bounds). Round-1 supports a single numeric/date key:
    bounds come from sampling the first child batch; partition ids via
    searchsorted over the bounds."""

    def __init__(self, child, num_partitions, bound_keys, schema):
        from ..expr.expressions import UnsupportedExpr
        super().__init__(child, num_partitions, bound_keys, schema)
        if not bound_keys or len(bound_keys) != 1:
            raise UnsupportedExpr(
                "range partitioning supports one key round-1")
        self._bounds = None

    def describe(self):
        return f"RangeShuffleExchangeExec[n={self.n}]"

    def _map_args(self):
        # sampled bounds are device data: traced argument, NOT a baked
        # closure constant — a shared cached program must see each
        # instance's own bounds
        return (self._bounds,)

    @staticmethod
    def _build_map_fn(n, keys):
        def _map_fn(cvs, mask, bounds):
            cap = mask.shape[0]
            ctx = EmitCtx(cvs, cap)
            kcv = keys[0].emit(ctx)
            pids = jnp.searchsorted(bounds, kcv.data,
                                    side="right").astype(jnp.int32)
            # nulls partition first (Spark null ordering for range)
            pids = jnp.where(kcv.validity, pids, 0)
            return _finish_map(cvs, mask, pids, n)
        return _map_fn

    def _ensure_shuffled(self, ctx):
        with self._lock:  # RLock: safe to re-enter in super()
            self._ensure_bounds(ctx)
            super()._ensure_shuffled(ctx)

    def _ensure_bounds(self, ctx):
        if self._bounds is None:
            # sample bounds from the first child batch
            child = self.children[0]
            first = next(iter(child.execute_partition(ctx, 0)), None)
            if first is None:
                self._bounds = jnp.zeros(self.n - 1)
            else:
                ectx = EmitCtx(first.cvs(), first.capacity)
                kcv = self.keys[0].emit(ectx)
                live = first.row_mask & kcv.validity
                order = jnp.argsort(jnp.where(live, kcv.data,
                                              kcv.data.max()))
                nlive = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
                qs = (jnp.arange(1, self.n) * nlive) // self.n
                self._bounds = kcv.data[order[jnp.clip(qs, 0,
                                                       first.capacity - 1)]]
