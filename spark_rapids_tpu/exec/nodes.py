"""Core physical operators: scan, project, filter, limit, union, collect.

Analogs (reference): GpuFileSourceScanExec / basicPhysicalOperators.scala
(GpuProjectExec :~, GpuFilterExec), limit.scala, GpuUnionExec. The fused
project/filter path compiles each operator's bound expression list into one
jitted function over the batch's CV pytree.
"""
from __future__ import annotations

import concurrent.futures as cf
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Column, bucket_capacity
from ..columnar.table import Field, Schema, Table
from ..expr.expressions import EmitCtx, Expression, d128_nodes
from ..ops.kernel_utils import CV
from ..profiler import tracing, xla_stats
from ..runtime import faults
from ..utils.transfer import fetch_int
from .base import D128_MARK, ExecContext, TpuExec, report_d128
from .batch import DeviceBatch, MeshBatch

__all__ = ["InMemoryScanExec", "CachedScanExec", "ParquetScanExec",
           "ProjectExec", "FilterExec",
           "LimitExec", "UnionExec", "collect_to_arrow", "cv_to_column",
           "make_table"]


def cv_to_column(cv: CV, dtype: dt.DataType, length: int) -> Column:
    children = []
    if isinstance(dtype, (dt.ArrayType, dt.MapType)):
        # child logical length = its full capacity: parent offsets only
        # reference the true element prefix, so trailing garbage is inert
        # (avoids a device sync to learn the exact element count in-trace)
        ch = cv.children[0]
        children = [cv_to_column(ch, Column.element_dtype(dtype),
                                 int(ch.validity.shape[0]))]
    elif isinstance(dtype, dt.StructType):
        children = [cv_to_column(ch, f.dtype, length)
                    for ch, f in zip(cv.children, dtype.fields)]
    return Column(dtype, length, cv.data, cv.validity, cv.offsets, children)


def make_table(schema: Schema, cvs: Sequence[CV], num_rows: int) -> Table:
    cols = [cv_to_column(cv, f.dtype, num_rows)
            for f, cv in zip(schema.fields, cvs)]
    return Table(schema.names, cols)


# ----------------------------------------------------------------------
class InMemoryScanExec(TpuExec):
    """Streams host (arrow) slices into HBM batches."""

    def __init__(self, arrow_table, schema: Schema):
        super().__init__([], schema)
        self.arrow = arrow_table

    def num_partitions(self, ctx):
        rows = self.arrow.num_rows
        per = max(1, ctx.conf.batch_size_rows)
        return max(1, -(-rows // per))

    def execute_partition(self, ctx, pid) -> Iterator[DeviceBatch]:
        per = max(1, ctx.conf.batch_size_rows)
        start = pid * per
        n = min(per, self.arrow.num_rows - start)
        if n <= 0 and pid > 0:
            return
        sl = self.arrow.slice(start, max(n, 0))
        m = ctx.metrics_for(self._op_id)
        with m.timer("scanTime"):
            tbl = Table.from_arrow(sl)
        m.add("numOutputRows", max(n, 0))
        m.add("numOutputBatches", 1)
        yield DeviceBatch(tbl, num_rows=max(n, 0))


def _rg_survives(stats, op: str, value) -> bool:
    """Can a row group with these column stats contain a matching row?"""
    try:
        if stats is None or not stats.has_min_max:
            return True
        # pyarrow raises ArrowNotImplementedError extracting stats for
        # some logical types (e.g. decimals stored as integers): keep
        # the group rather than die
        lo, hi = stats.min, stats.max
    except Exception:
        return True
    try:
        if op == ">=":
            return hi >= value
        if op == ">":
            return hi > value
        if op == "<=":
            return lo <= value
        if op == "<":
            return lo < value
        if op == "=":
            return lo <= value <= hi
    except TypeError:
        return True  # incomparable stat/literal types: keep the group
    return True


def prune_row_groups(pf, filters) -> List[int]:
    """Row groups whose footer stats might satisfy every conjunct
    (the filterBlocks analog: reference GpuParquetScan.scala:679)."""
    md = pf.metadata
    name_to_idx = {md.schema.column(i).name: i
                   for i in range(md.num_columns)}
    kept = []
    for rg in range(md.num_row_groups):
        g = md.row_group(rg)
        ok = True
        for (name, op, value) in filters:
            ci = name_to_idx.get(name)
            if ci is None:
                continue
            if not _rg_survives(g.column(ci).statistics, op, value):
                ok = False
                break
        if ok:
            kept.append(rg)
    return kept


class ParquetScanExec(TpuExec):
    """Parquet reader (reference: GpuParquetScan.scala reader types):
    - footer-stats row-group pruning from pushed-down conjuncts
      (filterBlocks :679)
    - MULTITHREADED mode: a thread pool decodes batches ahead of the
      device consumer through a bounded queue (the cloud reader :3134
      fetch/decode overlap, host-side)
    Host decode via Arrow C++, one H2D per batch; device decode is
    follow-on work (docs/compatibility.md)."""

    def __init__(self, paths: Sequence[str], schema: Schema,
                 columns: Optional[Sequence[str]] = None,
                 filters=None, dv=None, snapshot=None, delta_version=None):
        super().__init__([], schema)
        self.paths = list(paths)
        self.columns = list(columns) if columns else None
        self.filters = list(filters) if filters else None
        # {path: (table_root, deletionVector descriptor)} — dead-row
        # masks applied lazily per batch (Delta DVs); loaded once per
        # file at exec time, never at plan construction
        self.dv = dict(dv) if dv else None
        # bind-time (path, mtime_ns, size) pinning + Delta version,
        # copied from the logical scan (plan/logical.py). Public: both
        # flow into the exchange-subtree fingerprint the fragment cache
        # keys on. Verified per execute_partition — a file overwritten
        # MID-query raises instead of mixing old and new bytes
        # (between-action changes replan via DataFrame._execute).
        self.snapshot = tuple(snapshot) if snapshot else None
        self.delta_version = delta_version
        self._dv_cache = {}
        self._groups_cache = None

    def _verify_snapshot(self, ctx):
        if self.snapshot is None:
            return
        from ..io.snapshot import SnapshotMismatch, snapshot_current
        if not snapshot_current(self.snapshot):
            ctx.metrics_for(self._op_id).add("scanSnapshotViolations", 1)
            raise SnapshotMismatch(
                f"parquet files changed under a running scan: "
                f"{self.paths[:3]}{'...' if len(self.paths) > 3 else ''} "
                f"(bind-time snapshot no longer matches; re-run the "
                f"action to rebind)")

    def _reader_type(self, ctx) -> str:
        # cached: AUTO must not re-stat files per call — a flipped
        # decision mid-query would reinterpret partition indices (group
        # vs file) and silently drop rows
        rt = getattr(self, "_rt_cache", None)
        if rt is not None:
            return rt
        from ..config import (CLUSTER_EXECUTORS,
                              PARQUET_COALESCING_TARGET,
                              PARQUET_READER_TYPE)
        if ctx.conf.get(CLUSTER_EXECUTORS) > 0:
            # executor offload decodes per file; grouping is the
            # cluster scheduler's job there
            rt = "MULTITHREADED"
        else:
            rt = str(ctx.conf.get(PARQUET_READER_TYPE)).upper()
        if rt == "AUTO":
            # AUTO: many files each below the coalescing target ->
            # fewer uploads wins; else decode-prefetch overlap wins
            rt = "MULTITHREADED"
            if len(self.paths) >= 4:
                import os as _os
                target = ctx.conf.get(PARQUET_COALESCING_TARGET)
                try:
                    if all(_os.path.getsize(p) < target // 4
                           for p in self.paths):
                        rt = "COALESCING"
                except OSError:
                    pass
        self._rt_cache = rt
        return rt

    def _groups(self, ctx):
        """COALESCING reader: bin-pack files (in order) into groups of
        ~targetBytes on-disk size; one output partition per group."""
        if self._groups_cache is None:
            import os as _os
            from ..config import PARQUET_COALESCING_TARGET
            target = max(1, ctx.conf.get(PARQUET_COALESCING_TARGET))
            groups, cur, size = [], [], 0
            for p in self.paths:
                try:
                    fsz = _os.path.getsize(p)
                except OSError:
                    fsz = target
                if cur and size + fsz > target:
                    groups.append(cur)
                    cur, size = [], 0
                cur.append(p)
                size += fsz
            if cur:
                groups.append(cur)
            self._groups_cache = groups
        return self._groups_cache

    def num_partitions(self, ctx):
        if self._reader_type(ctx) == "COALESCING":
            return len(self._groups(ctx))    # 0 files -> 0 partitions
        return len(self.paths)

    def describe(self):
        f = f", filters={self.filters}" if self.filters else ""
        return f"ParquetScanExec[{len(self.paths)} files{f}]"

    def _dead_positions(self, path):
        """Dead row set for a DV-carrying file (cached per exec)."""
        if self.dv is None or path not in self.dv:
            return None
        got = self._dv_cache.get(path)
        if got is None:
            from ..io.dv import load_dv_positions
            root, desc = self.dv[path]
            # concurrent scan workers may both miss; setdefault keeps
            # one winner so every caller shares a single row set
            got = self._dv_cache.setdefault(
                path, set(load_dv_positions(root, desc)))
        return got

    def _device_decode_on(self, ctx) -> bool:
        """Device parquet decode applies when enabled AND the backend
        is a real accelerator; on the CPU backend pyarrow's native
        decoder shares the silicon with the 'device' kernels and wins,
        so there it only fires when the conf is set explicitly (tests,
        parity fuzzing, scan profiling)."""
        from ..config import PARQUET_DEVICE_DECODE
        if not ctx.conf.get(PARQUET_DEVICE_DECODE):
            return False
        if jax.default_backend() == "cpu":
            return ctx.conf.is_set(PARQUET_DEVICE_DECODE)
        return True

    def _device_decoded_batches(self, ctx, path, m):
        """Device-decode path (GpuParquetScan.scala:3364 analog): per
        row group, eligible column chunks decode ON DEVICE from one raw
        byte upload (staged through the pinned pool; snappy pages
        decompress in parallel on the prefetch thread pool); remaining
        columns ride the host pyarrow path and merge into the same
        DeviceBatch. Returns None when nothing in the file is
        device-decodable (caller uses the host path)."""
        import pyarrow.parquet as pq

        from ..columnar import dtypes as dt
        from ..columnar.column import Column, bucket_capacity
        from ..config import PARQUET_DEVICE_SNAPPY
        from ..io.file_cache import cached_local_path
        from ..io.parquet_device import (chunk_device_plan,
                                         decode_chunk_device,
                                         eligible_chunks,
                                         fallback_reasons)
        from ..memory.host import staging_pool
        try:
            lp = cached_local_path(path, ctx.conf)
            pf = pq.ParquetFile(lp)
        except FileNotFoundError:
            lp = path
            pf = pq.ParquetFile(path)
        cols = (self.columns if self.columns is not None
                else [f.name for f in self.schema.fields])
        if pf.metadata.num_row_groups == 0:
            return None
        if not eligible_chunks(pf, 0, cols):
            for name, (cat, _detail) in fallback_reasons(
                    pf, 0, cols).items():
                m.add(f"deviceDecodeFallback.{cat}", 1)
            return None
        kept = (prune_row_groups(pf, self.filters) if self.filters
                else list(range(pf.metadata.num_row_groups)))

        # the decode unit is a whole row group; cap the batch-size blowup
        # vs the host path (which slices to batch_size_rows) to bound the
        # device-memory spike on huge row groups. Checked BEFORE any
        # metric: the host fallback records skippedRowGroups itself.
        per = max(1, ctx.conf.batch_size_rows)
        if any(pf.metadata.row_group(rg).num_rows > 4 * per
               for rg in kept):
            return None
        m.add("skippedRowGroups", pf.metadata.num_row_groups - len(kept))
        field_by_name = {f.name: f for f in self.schema.fields}
        pool = staging_pool(ctx.conf)
        decomp = _decompress_pool(ctx)
        dev_snappy = ctx.conf.get(PARQUET_DEVICE_SNAPPY)

        import numpy as _np
        import pyarrow as _pa

        def gen():
            pool0 = dict(pool.metrics)
            for rg in kept:
                nrows = pf.metadata.row_group(rg).num_rows
                if nrows == 0:
                    continue
                cap = bucket_capacity(nrows)
                elig = eligible_chunks(pf, rg, cols)
                for name, (cat, _detail) in fallback_reasons(
                        pf, rg, cols).items():
                    m.add(f"deviceDecodeFallback.{cat}", 1)
                dev_cols = {}
                chunks = []
                rgmd = pf.metadata.row_group(rg)
                with m.timer("scanTime"):
                    for name, ci in list(elig.items()):
                        fld = field_by_name[name]
                        np_dt = fld.dtype.np_dtype
                        if np_dt is None or (
                                isinstance(fld.dtype, dt.DecimalType)
                                and fld.dtype.is_decimal128):
                            # decimal128 needs the two-limb buffer the
                            # fixed-width decode does not produce
                            m.add("deviceDecodeFallback.type", 1)
                            continue
                        af = pf.schema_arrow.field(name)
                        if (_pa.types.is_timestamp(af.type)
                                and af.type.unit != "us"):
                            # non-micros: host path converts
                            m.add("deviceDecodeFallback.type", 1)
                            continue
                        c = chunk_device_plan(
                            pf, lp, rg, ci, name, af.nullable,
                            pool=pool, decomp_pool=decomp,
                            device_snappy=dev_snappy, metrics=m)
                        try:
                            got = (decode_chunk_device(c, cap,
                                                       metrics=m)
                                   if c else None)
                        except Exception:
                            got = None      # leases must not leak
                        if got is None:
                            if c is not None:
                                c.close()
                            m.add("deviceDecodeFallback.pages", 1)
                            continue
                        chunks.append(c)
                        if isinstance(fld.dtype,
                                      (dt.StringType, dt.BinaryType)):
                            data, valid, offsets = got
                            dev_cols[name] = Column(fld.dtype, nrows,
                                                    data, valid,
                                                    offsets)
                        else:
                            vals, valid = got
                            if str(vals.dtype) != _np.dtype(np_dt).name:
                                vals = vals.astype(np_dt)
                            dev_cols[name] = Column(fld.dtype, nrows,
                                                    vals, valid)
                        m.add("deviceDecodeBytes", rgmd.column(ci)
                              .total_compressed_size)
                    rest = [n for n in cols if n not in dev_cols]
                    if rest:
                        at = pf.read_row_group(rg, columns=rest)
                        host_tbl = Table.from_arrow(at)
                        host_by_name = dict(zip(at.schema.names,
                                                host_tbl.columns))
                    else:
                        host_by_name = {}
                    out_cols = []
                    for n in cols:
                        if n in dev_cols:
                            out_cols.append(dev_cols[n])
                        else:
                            out_cols.append(host_by_name[n])
                    tbl = Table(list(cols), out_cols)
                # staging buffers go back to the pool only after the
                # decode OUTPUTS are materialized: jnp.asarray can alias
                # the host buffer zero-copy (CPU backend) and dispatch
                # is async, so a reused lease would be overwritten while
                # queued kernels still read it. Worker-side wait, off
                # the compute thread.
                if chunks:
                    outs = [(col.data, col.validity, col.offsets)
                            for col in dev_cols.values()
                            if col.offsets is not None] + \
                           [(col.data, col.validity)
                            for col in dev_cols.values()
                            if col.offsets is None]
                    # tpulint: allow[block-sync] prefetch-thread join:
                    jax.block_until_ready(outs)  # staging reuse must
                    # not race async kernels aliasing the host buffer
                for c in chunks:
                    c.close()
                m.add("numOutputRows", nrows)
                m.add("numOutputBatches", 1)
                m.add("deviceDecodedChunks", len(dev_cols))
                yield DeviceBatch(tbl, num_rows=nrows)
            for k, v in pool.metrics.items():
                delta = v - pool0.get(k, 0)
                if k.endswith("HeldBytes"):
                    m.set(k, v)
                elif delta:
                    m.add(k, delta)
        return gen()

    def _decoded_batches(self, ctx, path, m):
        import pyarrow as pa
        import pyarrow.parquet as pq
        from ..io.file_cache import cached_local_path
        per = max(1, ctx.conf.batch_size_rows)
        try:
            pf = pq.ParquetFile(cached_local_path(path, ctx.conf))
        except FileNotFoundError:
            # LRU eviction can unlink the cached copy between
            # local_path() and open; the source path is always valid
            pf = pq.ParquetFile(path)
        cols = (self.columns if self.columns is not None
                else [f.name for f in self.schema.fields])
        dead = self._dead_positions(path)
        # row-group pruning would shift file-row positions under a DV
        if self.filters and dead is None:
            kept = prune_row_groups(pf, self.filters)
            m.add("skippedRowGroups",
                  pf.metadata.num_row_groups - len(kept))
            if not kept:
                return
            it = pf.iter_batches(batch_size=per, columns=cols,
                                 row_groups=kept)
        else:
            it = pf.iter_batches(batch_size=per, columns=cols)
        off = 0
        for rb in it:
            at = pa.table(rb)
            if dead is not None:
                from ..io.dv import apply_dv_to_table
                n0 = at.num_rows
                batch_dead = {d - off for d in dead
                              if off <= d < off + n0}
                at = apply_dv_to_table(at, batch_dead)
                off += n0
                if at.num_rows == 0:
                    continue
            yield at

    def execute_partition(self, ctx, pid) -> Iterator[DeviceBatch]:
        from ..config import (CLUSTER_EXECUTORS,
                              MULTITHREADED_READ_THREADS,
                              PARQUET_READER_TYPE)
        m = ctx.metrics_for(self._op_id)
        self._verify_snapshot(ctx)
        reader_type = self._reader_type(ctx)
        if reader_type == "COALESCING":
            # pid indexes file GROUPS here, not files
            yield from self._execute_coalescing(ctx, pid, m)
            return
        path = self.paths[pid]
        if (ctx.conf.get(CLUSTER_EXECUTORS) > 0
                and ctx.session is not None
                and not (self.dv and path in self.dv)):
            # driver/executor split: host decode runs in an executor
            # process, Arrow IPC ships back (cluster/driver.py)
            cm = ctx.session.cluster_manager()
            fut = cm.submit(_remote_decode_parquet, path, self.columns
                            or [f.name for f in self.schema.fields],
                            self.filters, max(1, ctx.conf.batch_size_rows))
            import pyarrow as pa
            blobs, skipped = fut.result()
            m.add("skippedRowGroups", skipped)
            for blob in blobs:
                with pa.ipc.open_stream(blob) as rd:
                    at = rd.read_all()
                with m.timer("scanTime"):
                    tbl = Table.from_arrow(at)
                m.add("numOutputRows", at.num_rows)
                m.add("numOutputBatches", 1)
                yield DeviceBatch(tbl, num_rows=at.num_rows)
            return
        from ..config import PARQUET_DEVICE_DECODE
        if (self._device_decode_on(ctx)
                and not (self.dv and path in self.dv)):
            dev_iter = self._device_decoded_batches(ctx, path, m)
            if dev_iter is not None:
                # decompress + plan + upload staging runs on a worker
                # thread: device compute only ever waits on the queue
                # (prefetchWaitSecs), not on snappy or page parsing
                nthreads = max(1,
                               ctx.conf.get(MULTITHREADED_READ_THREADS))
                yield from _prefetched(dev_iter,
                                       depth=min(nthreads, 4),
                                       wait_metrics=(m,
                                                     "prefetchWaitSecs"))
                return
        host_iter = self._decoded_batches(ctx, path, m)
        if reader_type == "MULTITHREADED":
            nthreads = max(1, ctx.conf.get(MULTITHREADED_READ_THREADS))
            host_iter = _prefetched(host_iter, depth=min(nthreads, 4))
        for at in host_iter:
            with m.timer("scanTime"):
                tbl = Table.from_arrow(at)
            m.add("numOutputRows", at.num_rows)
            m.add("numOutputBatches", 1)
            yield DeviceBatch(tbl, num_rows=at.num_rows)

    def _execute_coalescing(self, ctx, pid, m):
        """COALESCING reader: the group's files decode IN PARALLEL on a
        thread pool, concatenate host-side, and upload as full-target
        batches — many small files cost one H2D per coalesced batch
        instead of one per file (reference: GpuParquetScan COALESCING
        reader, GpuMultiFileReader.scala)."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from concurrent.futures import ThreadPoolExecutor
        from ..config import MULTITHREADED_READ_THREADS
        group = self._groups(ctx)[pid]
        cols = (self.columns if self.columns is not None
                else [f.name for f in self.schema.fields])
        if not cols:
            # count-style scan: pf.read(columns=[]) drops the row count
            # (0-column Table), so stream per-file batches which keep it
            for p in group:
                for at in self._decoded_batches(ctx, p, m):
                    with m.timer("scanTime"):
                        tbl = Table.from_arrow(at)
                    m.add("numOutputRows", at.num_rows)
                    m.add("numOutputBatches", 1)
                    yield DeviceBatch(tbl, num_rows=at.num_rows)
            return

        from ..io.file_cache import cached_local_path

        def read_one(p):
            try:
                pf = pq.ParquetFile(cached_local_path(p, ctx.conf))
            except FileNotFoundError:
                # cache-eviction race: fall back to the source path
                pf = pq.ParquetFile(p)
            dead = self._dead_positions(p)
            if self.filters and dead is None:
                kept = prune_row_groups(pf, self.filters)
                skipped = pf.metadata.num_row_groups - len(kept)
                if not kept:
                    return None, skipped
                return pf.read_row_groups(kept, columns=cols), skipped
            at = pf.read(columns=cols)
            if dead is not None:
                from ..io.dv import apply_dv_to_table
                at = apply_dv_to_table(at, dead)
            return at, 0

        nthreads = max(1, ctx.conf.get(MULTITHREADED_READ_THREADS))
        with ThreadPoolExecutor(max_workers=nthreads,
                                thread_name_prefix="tpu-coalesce") as pool:
            parts = list(pool.map(read_one, group))
        tables = []
        for at, skipped in parts:
            m.add("skippedRowGroups", skipped)
            if at is not None and at.num_rows:
                tables.append(at)
        if not tables:
            return
        combined = (pa.concat_tables(tables) if len(tables) > 1
                    else tables[0])
        m.add("coalescedFiles", len(group))
        per = max(1, ctx.conf.batch_size_rows)
        for start in range(0, combined.num_rows, per):
            sl = combined.slice(start, min(per, combined.num_rows - start))
            with m.timer("scanTime"):
                tbl = Table.from_arrow(sl)
            m.add("numOutputRows", sl.num_rows)
            m.add("numOutputBatches", 1)
            yield DeviceBatch(tbl, num_rows=sl.num_rows)


# tpulint: allow[pool-cancel] remote-executor task, no ExecContext — cancel is task abort
def _remote_decode_parquet(path, columns, filters, batch_rows):
    """Executor-side parquet decode task: returns (list of Arrow IPC
    stream blobs — one per batch — , skipped row-group count). Pure
    host-side, idempotent (safe to re-execute after executor loss)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(path)
    skipped = 0
    if filters:
        kept = prune_row_groups(pf, filters)
        skipped = pf.metadata.num_row_groups - len(kept)
        if not kept:
            return [], skipped
        it = pf.iter_batches(batch_size=batch_rows, columns=columns,
                             row_groups=kept)
    else:
        it = pf.iter_batches(batch_size=batch_rows, columns=columns)
    blobs = []
    for rb in it:
        at = pa.table(rb)
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, at.schema) as w:
            w.write_table(at)
        blobs.append(sink.getvalue().to_pybytes())
    return blobs, skipped


_DECOMP_POOL = None
_DECOMP_LOCK = __import__("threading").Lock()


def _decompress_pool(ctx):
    """Shared thread pool for per-page snappy decompression in the
    device scan (the MULTITHREADED prefetch pool): pages of one chunk
    decompress in parallel, and the whole plan step already runs on
    the prefetch worker — never the compute thread."""
    global _DECOMP_POOL
    from ..config import MULTITHREADED_READ_THREADS
    n = max(1, ctx.conf.get(MULTITHREADED_READ_THREADS))
    with _DECOMP_LOCK:
        if _DECOMP_POOL is None:
            from concurrent.futures import ThreadPoolExecutor
            _DECOMP_POOL = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="tpu-decomp")
        return _DECOMP_POOL


def _prefetched(it: Iterator, depth: int, wait_metrics=None):
    """Run `it` on a worker thread with a bounded queue so host parquet
    decode overlaps device compute (async-IO analog, reference io/async
    ThrottlingExecutor). An abandoned consumer (e.g. under a LIMIT)
    signals the worker via a stop event and drains the queue so the
    blocked put unblocks — no leaked threads or pinned batches.
    `wait_metrics=(MetricSet, name)` records consumer block time on the
    queue — the observable proof that decode ran ahead of compute."""
    import queue
    import threading
    import time as _time
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    DONE = object()
    stop = threading.Event()
    err: List[BaseException] = []

    def work():
        try:
            for item in it:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # propagate to consumer
            err.append(e)
        finally:
            # the sentinel must arrive even when the queue is full; keep
            # trying unless the consumer already walked away
            while not stop.is_set():
                try:
                    q.put(DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=work, daemon=True,
                         name="tpu-prefetch")
    t.start()
    try:
        while True:
            if wait_metrics is not None:
                t0 = _time.perf_counter()
                item = q.get()
                wait_metrics[0].add(wait_metrics[1],
                                    _time.perf_counter() - t0)
            else:
                item = q.get()
            if item is DONE:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        stop.set()
        while not q.empty():
            try:
                q.get_nowait()
            except queue.Empty:
                break


class CachedScanExec(TpuExec):
    """Serves HBM-resident batches directly (GpuInMemoryTableScan analog)."""

    def __init__(self, batches, schema: Schema, columns_cached=None,
                 n_shards=0):
        super().__init__([], schema)
        self.batches = list(batches)
        # width of the cached table; `schema` is what this plan reads of
        # it (plan/optimizer.py prunes the scan to zero-copy views)
        self.columns_cached = (len(schema.fields) if columns_cached is None
                               else columns_cached)
        # rows divided over this many devices (a mesh session's cache()):
        # one partition a device, `batches` shard after shard
        self.n_shards = n_shards

    def describe(self):
        sharded = (f" on {self.n_shards} devices" if self.n_shards else "")
        return (f"CachedScanExec[{len(self.schema.fields)} of "
                f"{self.columns_cached} columns, {len(self.batches)} "
                f"batches{sharded}]")

    def num_partitions(self, ctx):
        return self.n_shards or max(1, len(self.batches))

    def execute_mesh(self, ctx, n):
        if n != self.n_shards:
            return None
        self._report_width(ctx)
        per = len(self.batches) // n
        return (MeshBatch([self.batches[s * per + j] for s in range(n)])
                for j in range(per))

    def _report_width(self, ctx):
        m = ctx.metrics_for(self._op_id)
        m.set("columnsRead", len(self.schema.fields))
        m.set("columnsCached", self.columns_cached)

    def whole_input(self, ctx):
        """Every batch at once, for a parent that hands them all to ONE
        program as arguments (the aggregates' whole-input paths)."""
        self._report_width(ctx)
        # a sharded table's batches lie on several devices: no one
        # program takes them all
        return None if self.n_shards else self.batches

    def execute_partition(self, ctx, pid):
        if pid == 0:
            self._report_width(ctx)
        if self.n_shards:
            per = len(self.batches) // self.n_shards
            yield from self.batches[pid * per:(pid + 1) * per]
        elif pid < len(self.batches):
            yield self.batches[pid]


# ----------------------------------------------------------------------
class ProjectExec(TpuExec):
    def __init__(self, child: TpuExec, bound_exprs: List[Expression],
                 schema: Schema):
        super().__init__([child], schema)
        self.bound = bound_exprs
        self._d128 = d128_nodes(bound_exprs)

        def _run(cvs, mask, *rows):
            # `rows`: the running count of live rows a program of
            # 128-bit decimal arithmetic keeps beside its columns
            ctx = EmitCtx(cvs, mask.shape[0])
            out = [e.emit(ctx) for e in self.bound]
            return (out, rows[0] + jnp.sum(mask, dtype=jnp.int64)) \
                if rows else out

        from ..runtime.program_cache import cached_program, exprs_fp
        self._jit = cached_program(
            _run, cls="ProjectExec",
            tag="run" + D128_MARK * bool(self._d128),
            key=exprs_fp(self.bound))

    def describe(self):
        return f"ProjectExec[{', '.join(map(repr, self.bound))}]"

    def fusable_stage(self):
        def fn(cvs, mask):
            ctx = EmitCtx(cvs, mask.shape[0])
            return [e.emit(ctx) for e in self.bound], mask
        return fn

    def stage_fingerprint(self):
        from ..runtime.program_cache import exprs_fp
        return ("Project", exprs_fp(self.bound))

    def preserves_ordinals(self):
        return False

    def d128_exprs(self):
        return self._d128

    def execute_partition(self, ctx, pid):
        from . import degrade
        m = ctx.metrics_for(self._op_id)
        rows = (jnp.zeros((), jnp.int64),) if self._d128 else ()
        for batch in self.children[0].execute_partition(ctx, pid):
            ctx.check_cancel()
            if self._op_id not in ctx.degraded:
                try:
                    if faults.ACTIVE:
                        faults.hit("device.dispatch",
                                   query_id=ctx.query_id,
                                   op="ProjectExec")
                    with m.timer("opTime"):
                        out = self._jit(batch.cvs(), batch.row_mask, *rows)
                except Exception as e:  # noqa: BLE001 - classified below
                    if not degrade.should_degrade(ctx, self, e):
                        raise
                else:
                    if rows:
                        out, rows = out[0], out[1:]
                    xla_stats.count_dispatch()
                    m.add("numOutputBatches", 1)
                    yield DeviceBatch(
                        make_table(self.schema, out, batch.num_rows),
                        batch.num_rows, batch.row_mask, batch.capacity)
                    continue
            # degraded (or this batch's dispatch just failed): the host
            # interpreter evaluates the same bound expressions
            with m.timer("hostEvalTime"):
                hb = degrade.host_project_batch(self, batch)
            m.add("degradedToHost", 1)
            m.add("numOutputBatches", 1)
            yield hb
        if rows:
            report_d128(m, self._d128, fetch_int(rows[0]))


class FilterExec(TpuExec):
    def __init__(self, child: TpuExec, bound_cond: Expression):
        super().__init__([child], child.schema)
        self.bound = bound_cond
        self._d128 = d128_nodes([bound_cond])

        def _run(cvs, mask, *rows):   # `rows`: as ProjectExec's
            ctx = EmitCtx(cvs, mask.shape[0])
            cv = self.bound.emit(ctx)
            kept = mask & cv.validity & cv.data.astype(jnp.bool_)
            return (kept, rows[0] + jnp.sum(mask, dtype=jnp.int64)) \
                if rows else kept

        from ..runtime.program_cache import cached_program, expr_fp
        self._jit = cached_program(
            _run, cls="FilterExec",
            tag="run" + D128_MARK * bool(self._d128),
            key=(expr_fp(self.bound),))

    def describe(self):
        return f"FilterExec[{self.bound!r}]"

    def fusable_stage(self):
        def fn(cvs, mask):
            ctx = EmitCtx(cvs, mask.shape[0])
            cv = self.bound.emit(ctx)
            return cvs, mask & cv.validity & cv.data.astype(jnp.bool_)
        return fn

    def stage_fingerprint(self):
        from ..runtime.program_cache import expr_fp
        return ("Filter", expr_fp(self.bound))

    def d128_exprs(self):
        return self._d128

    def execute_partition(self, ctx, pid):
        from . import degrade
        m = ctx.metrics_for(self._op_id)
        rows = (jnp.zeros((), jnp.int64),) if self._d128 else ()
        for batch in self.children[0].execute_partition(ctx, pid):
            ctx.check_cancel()
            if self._op_id not in ctx.degraded:
                try:
                    if faults.ACTIVE:
                        faults.hit("device.dispatch",
                                   query_id=ctx.query_id,
                                   op="FilterExec")
                    with m.timer("opTime"):
                        new_mask = self._jit(batch.cvs(), batch.row_mask,
                                             *rows)
                except Exception as e:  # noqa: BLE001 - classified below
                    if not degrade.should_degrade(ctx, self, e):
                        raise
                else:
                    if rows:
                        new_mask, rows = new_mask[0], new_mask[1:]
                    xla_stats.count_dispatch()
                    m.add("numOutputBatches", 1)
                    yield DeviceBatch(batch.table, batch.num_rows,
                                      new_mask, batch.capacity)
                    continue
            # degraded (or this batch's dispatch just failed): host
            # predicate evaluation over the same batch
            with m.timer("hostEvalTime"):
                hb = degrade.host_filter_batch(self, batch)
            m.add("degradedToHost", 1)
            if hb is None:
                continue
            m.add("numOutputBatches", 1)
            yield hb
        if rows:
            report_d128(m, self._d128, fetch_int(rows[0]))


class LimitExec(TpuExec):
    """Global limit; collapses to a single output partition.

    The limit itself is stateful across batches (`remaining` lives on
    the host), so it can never be a FusedStage member — instead it
    collapses its own fusable child chain into the clip program
    (collapse_fusable): stages + rank-clip run as one dispatch per
    batch."""

    fuses_child_chain = True

    def __init__(self, child: TpuExec, n: int):
        super().__init__([child], child.schema)
        self.n = n
        self._ncap = bucket_capacity(max(n, 1))
        # resolved lazily at first execute (children may be wrapped by
        # LORE dump pass-throughs after planning)
        self._base = None
        self._stages = None
        self._n_fused = 0

        from ..runtime.program_cache import cached_program

        def _clip(mask, remaining):
            ranks = jnp.cumsum(mask.astype(jnp.int64))
            new_mask = mask & (ranks <= remaining)
            return new_mask, jnp.sum(new_mask.astype(jnp.int64))

        self._clip = _clip
        self._jit = cached_program(_clip, cls="LimitExec", tag="clip")
        # _fused_jit is keyed on the fused chain's structure, which is
        # only known after _resolve_fusion — built there
        self._fused_jit = None
        ncap = self._ncap

        def _perm(mask):
            from ..ops.gather import compaction_perm
            perm, count = compaction_perm(mask)
            return perm[:ncap], jnp.arange(ncap) < count

        self._perm = cached_program(_perm, cls="LimitExec", tag="perm",
                                    key=(ncap,))

    def _resolve_fusion(self, ctx):
        if self._base is None:
            from ..config import STAGE_FUSION_ENABLED
            from .base import collapse_fusable
            if ctx.conf.get(STAGE_FUSION_ENABLED):
                self._base, self._stages, self._n_fused = collapse_fusable(
                    self.children[0])
            else:
                self._base, self._n_fused = self.children[0], 0
                self._stages = lambda cvs, mask: (cvs, mask)
        if self._fused_jit is None:
            from ..runtime.program_cache import cached_program
            clip = self._clip

            def _clip_fused(cvs, mask, remaining):
                cvs, mask = self._stages(cvs, mask)
                new_mask, took = clip(mask, remaining)
                return cvs, new_mask, took

            # tpulint: allow[fp-unstable-attr,unstable-program-key] id(self) is the documented per-instance fallback key: unshared, never falsely shared, excluded from warm packs
            self._fused_jit = cached_program(
                _clip_fused, cls="LimitExec", tag="clip_fused",
                key=getattr(self._stages, "_stage_fp",
                            ("inst", id(self))))

    def describe(self):
        fused = f", fused_stages={self._n_fused}" if self._n_fused else ""
        return f"LimitExec[{self.n}{fused}]"

    def num_partitions(self, ctx):
        return 1

    def execute_partition(self, ctx, pid):
        self._resolve_fusion(ctx)
        remaining = self.n
        child = self._base
        for cpid in range(child.num_partitions(ctx)):
            if remaining <= 0:
                return
            for batch in child.execute_partition(ctx, cpid):
                ctx.check_cancel()
                if remaining <= 0:
                    return
                if self._n_fused:
                    cvs, mask, took = self._fused_jit(
                        batch.cvs(), batch.row_mask, remaining)
                    tbl = None
                else:
                    cvs, tbl = batch.cvs(), batch.table
                    mask, took = self._jit(batch.row_mask, remaining)
                xla_stats.count_dispatch()
                took = int(took)
                if took == 0:
                    continue
                remaining -= took
                if batch.capacity > 2 * self._ncap:
                    # the surviving rows are a sliver of the batch: compact
                    # to a limit-sized capacity on device so collect fetches
                    # O(n) bytes, not the full sorted input
                    from ..ops.gather import gather_cols
                    idx, inb = self._perm(mask)
                    out = gather_cols(cvs, idx, inb)
                    yield DeviceBatch(make_table(self.schema, out, took),
                                      took, inb, self._ncap)
                else:
                    if tbl is None:
                        tbl = make_table(self.schema, cvs, batch.num_rows)
                    yield DeviceBatch(tbl, batch.num_rows, mask,
                                      batch.capacity)


class UnionExec(TpuExec):
    def __init__(self, children: List[TpuExec], schema: Schema):
        super().__init__(children, schema)
        self._offsets = []

    def num_partitions(self, ctx):
        return sum(c.num_partitions(ctx) for c in self.children)

    def execute_partition(self, ctx, pid):
        for c in self.children:
            n = c.num_partitions(ctx)
            if pid < n:
                for b in c.execute_partition(ctx, pid):
                    ctx.check_cancel()
                    # positional union: rename child columns to ours
                    yield DeviceBatch(b.table.rename(self.schema.names),
                                      b.num_rows, b.row_mask, b.capacity)
                return
            pid -= n


# ----------------------------------------------------------------------
def _batch_to_arrow(batch: DeviceBatch):
    with tracing.span("export", "op"):
        return _export_batch(batch)


def _export_batch(batch: DeviceBatch):
    import pyarrow as pa
    from ..columnar.column import Column
    from ..utils.transfer import fetch
    # fetch the mask together with all column buffers: ONE device_get
    host = fetch([c.device_buffers() for c in batch.table.columns]
                 + [batch.row_mask])
    # tpulint: allow[host-sync] `host` is fetched above — numpy view
    mask = np.asarray(host[-1])[:batch.num_rows]
    arrs = [Column.arrow_from_host(c.dtype, c.length, b)
            for c, b in zip(batch.table.columns, host[:-1])]
    at = (pa.Table.from_arrays(arrs, names=list(batch.table.names))
          if arrs else pa.table({}))
    if at.num_rows == 0 and batch.num_rows > 0:
        return pa.table({})  # zero-column batch (count(*) pipelines)
    if not mask.all():
        at = at.filter(pa.array(mask))
    return at


def collect_to_arrow(root: TpuExec, ctx: ExecContext):
    """Run the plan and materialize a host pyarrow Table (the analog of
    GpuColumnarToRowExec + collect). Partitions run as concurrent tasks
    bounded by the TpuSemaphore (the GpuSemaphore admission model:
    reference GpuSemaphore.scala:183)."""
    with tracing.span("collect", "op"):
        return _collect(root, ctx)


def _collect(root: TpuExec, ctx: ExecContext):
    import pyarrow as pa
    nparts = root.num_partitions(ctx)
    if nparts <= 1:
        pieces = [_batch_to_arrow(b) for b in root.execute_all(ctx)]
    else:
        sem = _session_semaphore(ctx)
        import concurrent.futures as cf
        import threading as _threading
        sem_wait = [0.0]
        wait_lock = _threading.Lock()
        # pool-weight-derived base priority (service scheduler): heavier
        # pools get more-negative values and win permit ties; pid breaks
        # ties within a query via the heap's seq ordering
        base_prio = getattr(ctx, "sem_priority", 0)

        def run_part(pid):
            # GpuSemaphore model: hold the permit while DEVICE work runs
            # (advancing the iterator executes the jitted kernels), release
            # around the host-side fetch/convert
            out = []
            waited = 0.0
            it = root.execute_partition(ctx, pid)
            try:
                while True:
                    waited += sem.acquire(priority=base_prio,
                                          token=ctx.cancel)
                    try:
                        b = next(it, None)
                    finally:
                        sem.release()
                    if b is None:
                        break
                    ctx.check_cancel()
                    out.append(_batch_to_arrow(b))
            finally:
                with wait_lock:
                    sem_wait[0] += waited
            return out

        workers = min(nparts, max(2, ctx.conf.concurrent_tasks * 2))
        with cf.ThreadPoolExecutor(
                workers, thread_name_prefix="tpu-collect") as pool:
            results = list(pool.map(run_part, range(nparts)))
        pieces = [at for r in results for at in r]
        if sem_wait[0] > 0:
            # per-query chip-admission wait, surfaced on the root node
            # (Ms suffix on purpose: op_time_seconds sums *Time keys and
            # wait is not attributed operator time)
            ctx.metrics_for(root._op_id).add(
                "semaphoreWaitMs", round(sem_wait[0] * 1e3, 3))
    if not pieces:
        return root.schema.to_arrow().empty_table()
    return pa.concat_tables(pieces)


_SEM_LOCK = __import__("threading").Lock()


def _session_semaphore(ctx: ExecContext):
    from ..memory.semaphore import TpuSemaphore
    if ctx.session is None:
        return TpuSemaphore(ctx.conf.concurrent_tasks)
    with _SEM_LOCK:
        sem = getattr(ctx.session, "_semaphore", None)
        if sem is None:
            sem = TpuSemaphore(ctx.conf.concurrent_tasks)
            ctx.session._semaphore = sem
        return sem
