"""Multithreaded host-file shuffle — the portable baseline transport.

(reference: RapidsShuffleThreadedWriter/Reader + MULTITHREADED mode,
RapidsShuffleInternalManagerBase.scala:120; SURVEY.md §2.7.) Map tasks
bucket rows by target partition ON DEVICE (one sort + one bulk D2H per
batch), slice per-partition sub-batches host-side, and a thread pool
appends them to per-map shuffle files with a trailing segment index.
Reduce tasks read their segment from every map file (thread pool),
concatenate on host, and do ONE H2D.
"""
from __future__ import annotations

import concurrent.futures as cf
import io
import os
import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Column, bucket_capacity
from ..columnar.table import Schema, Table
from ..exec.batch import DeviceBatch
from ..profiler import tracing
from ..runtime import racedep
from ..utils.transfer import fetch
from .serializer import HostSubBatch, read_subbatch, write_subbatch

__all__ = ["LocalShuffle", "get_codec"]


def get_codec(name: str):
    if name in (None, "none", ""):
        return None
    if name == "lz4":
        try:
            import lz4.frame as lz4f  # optional
            return lz4f
        except ImportError:
            import zlib
            return zlib  # gated fallback: zlib is always available
    if name == "zstd":
        try:
            import zstandard  # optional

            class _Z:
                compress = staticmethod(
                    lambda b: zstandard.ZstdCompressor().compress(b))
                decompress = staticmethod(
                    lambda b: zstandard.ZstdDecompressor().decompress(b))
            return _Z
        except ImportError:
            import zlib
            return zlib
    raise ValueError(f"unknown codec {name}")


def _np_dtype_for(f_dtype: dt.DataType) -> np.dtype:
    return np.dtype(f_dtype.np_dtype or np.int8)


class LocalShuffle:
    """One shuffle exchange: N map inputs -> M reduce partitions."""

    def __init__(self, shuffle_id: str, num_reduce: int, schema: Schema,
                 shuffle_dir: str = "/tmp/srtpu-shuffle",
                 writer_threads: int = 4, reader_threads: int = 4,
                 codec: Optional[str] = None):
        self.id = shuffle_id
        self.n = num_reduce
        self.schema = schema
        self.dir = os.path.join(shuffle_dir, f"shuffle-{shuffle_id}")
        os.makedirs(self.dir, exist_ok=True)
        import atexit
        atexit.register(self.cleanup)  # ShuffleCleanupManager analog
        self.writer_threads = writer_threads
        self.reader_threads = reader_threads
        self.codec = get_codec(codec)
        from ..runtime import lockdep
        self._lock = lockdep.lock("LocalShuffle._lock")
        # keyed by map partition id and iterated in sorted order: with a
        # parallel map side, COMPLETION order is nondeterministic but
        # reduce-side concatenation must stay byte-identical to serial
        self._map_files: Dict[int, str] = {}
        self._arena = None  # lazy HostArena for reduce-side assembly
        # bytes after the codec and before it (the length prefixes
        # apart), and blocks, over every map partition written
        self.metrics = {"bytesWritten": 0, "rawBytesWritten": 0,
                        "blocksWritten": 0}
        # exact per-reduce-partition serialized bytes + rows, summed at
        # WRITE time (the MapOutputStatistics analog): the skew/coalesce
        # detectors read these without re-opening any map file
        self._rp_bytes = [0] * self.n
        self._rp_rows = [0] * self.n

    # ---------------- map side ----------------------------------------
    def write_map_partition(self, mpid: int, pieces_per_reduce):
        """pieces_per_reduce: list over reduce pid of lists of
        HostSubBatch. Serialization runs on the writer thread pool; the
        file itself is written sequentially with a trailing index."""
        path = os.path.join(self.dir, f"map-{mpid}.bin")

        def ser(sb: HostSubBatch):
            with tracing.span("shuffle.serialize", "op", rows=sb.n_rows):
                buf = io.BytesIO()
                raw = write_subbatch(buf, sb, self.codec)
                return buf.getvalue(), raw

        flat = [(rp, sb) for rp in range(self.n)
                for sb in pieces_per_reduce[rp]]
        if self.writer_threads > 1 and len(flat) > 1:
            with cf.ThreadPoolExecutor(
                    self.writer_threads,
                    thread_name_prefix="tpu-shufwrite") as pool:
                # tpulint: allow[wait-under-lock] serializer pool is private, CPU/file-bound, and takes no locks or permits — join under the exchange build lock cannot cycle
                done = list(pool.map(lambda t: ser(t[1]), flat))
        else:
            done = [ser(sb) for _, sb in flat]
        blocks = [b for b, _ in done]
        nbytes = sum(map(len, blocks))
        index = []  # (offset, length) per reduce partition
        with tracing.span("shuffle.write", "io", bytes=nbytes,
                          blocks=len(blocks)), open(path, "wb") as f:
            bi = 0
            for rp in range(self.n):
                start = f.tell()
                for sb in pieces_per_reduce[rp]:
                    f.write(blocks[bi])
                    bi += 1
                index.append((start, f.tell() - start))
            idx_off = f.tell()
            for off, ln in index:
                f.write(struct.pack("<QQ", off, ln))
            f.write(struct.pack("<QI", idx_off, self.n))
        with self._lock:  # concurrent map workers share the metrics dict
            racedep.note_access("LocalShuffle._map_files", mpid,
                                write=True)
            self.metrics["bytesWritten"] += nbytes
            self.metrics["rawBytesWritten"] += sum(raw for _, raw in done)
            self.metrics["blocksWritten"] += len(blocks)
            for rp in range(self.n):
                self._rp_bytes[rp] += index[rp][1]
                self._rp_rows[rp] += sum(sb.n_rows
                                         for sb in pieces_per_reduce[rp])
            self._map_files[mpid] = path

    # ---------------- reduce side --------------------------------------
    def _segment_extent(self, f, rpid: int):
        f.seek(-12, os.SEEK_END)
        idx_off, _n = struct.unpack("<QI", f.read(12))
        f.seek(idx_off + 16 * rpid)
        return struct.unpack("<QQ", f.read(16))

    def _block_ranges(self, path: str, rpid: int):
        """(offset, length) of each serialized block in this partition's
        segment — length prefixes only, payloads are skipped (cheap)."""
        blocks = []
        with open(path, "rb") as f:
            off, ln = self._segment_extent(f, rpid)
            pos, end = off, off + ln
            while pos < end:
                f.seek(pos)
                (blen,) = struct.unpack("<Q", f.read(8))
                blocks.append((pos, 8 + blen))
                pos += 8 + blen
        return blocks

    def read_reduce_partition(self, rpid: int, chunk: int = 0,
                              nchunks: int = 1) -> List[HostSubBatch]:
        """Sub-batches of one reduce partition; with nchunks > 1 only the
        blocks of serialized-byte slice `chunk` are read AND decoded
        (adaptive skew split must not re-materialize the whole partition
        per slice)."""
        from .serializer import wire_spec
        specs = [wire_spec(f.dtype) for f in self.schema.fields]

        with self._lock:
            racedep.note_access("LocalShuffle._map_files")
            files = [self._map_files[k] for k in sorted(self._map_files)]

        selected = None
        if nchunks > 1:
            per_file = [self._block_ranges(p, rpid) for p in files]
            total = sum(ln for blocks in per_file for _, ln in blocks)
            bounds = [total * c // nchunks for c in range(nchunks + 1)]
            selected = []
            acc = 0
            for blocks in per_file:
                sel = []
                for pos, ln in blocks:
                    if bounds[chunk] <= acc < bounds[chunk + 1]:
                        sel.append((pos, ln))
                    acc += ln
                selected.append(sel)

        def read_one(args) -> List[HostSubBatch]:
            fi, path = args
            out = []
            with tracing.span("shuffle.read", "io", map_file=fi), \
                    open(path, "rb") as f:
                if selected is None:
                    off, ln = self._segment_extent(f, rpid)
                    f.seek(off)
                    seg = io.BytesIO(f.read(ln))
                else:
                    chunks = []
                    for pos, ln in selected[fi]:
                        f.seek(pos)
                        chunks.append(f.read(ln))
                    seg = io.BytesIO(b"".join(chunks))
            while True:
                sb = read_subbatch(seg, specs, self.codec)
                if sb is None:
                    break
                out.append(sb)
            return out

        if self.reader_threads > 1 and len(files) > 1:
            with cf.ThreadPoolExecutor(
                    self.reader_threads,
                    thread_name_prefix="tpu-shufread") as pool:
                results = list(pool.map(read_one, enumerate(files)))
        else:
            results = [read_one((i, p)) for i, p in enumerate(files)]
        return [sb for r in results for sb in r]

    def partition_stats(self) -> List[int]:
        """EXACT serialized bytes per reduce partition, accumulated at
        write time (the MapOutputStatistics analog feeding adaptive
        re-planning) — no map-file re-reads on the replan path."""
        with self._lock:
            return list(self._rp_bytes)

    def partition_row_stats(self) -> List[int]:
        """Rows per reduce partition, accumulated at write time."""
        with self._lock:
            return list(self._rp_rows)

    def reduce_batch_slice(self, rpid: int, chunk: int,
                           nchunks: int) -> Optional[DeviceBatch]:
        """One byte-balanced block slice of a reduce partition (adaptive
        skew split: a skewed partition becomes nchunks tasks; only this
        slice's blocks are read + decoded)."""
        return self._device_batch(
            self.read_reduce_partition(rpid, chunk, nchunks))

    def reduce_batch(self, rpid: int) -> Optional[DeviceBatch]:
        """Concat this partition's sub-batches on host, one H2D."""
        return self._device_batch(self.read_reduce_partition(rpid))

    def _device_batch(self, subs) -> Optional[DeviceBatch]:
        import jax
        total = sum(sb.n_rows for sb in subs)
        if total == 0:
            return None
        cap = bucket_capacity(total)
        with tracing.span("shuffle.assemble", "op", rows=total,
                          blocks=len(subs)):
            bufs = [self._assemble([sb.cols[ci] for sb in subs],
                                   [sb.n_rows for sb in subs], f.dtype,
                                   cap)
                    for ci, f in enumerate(self.schema.fields)]
        with tracing.span("shuffle.upload", "op", rows=total,
                          capacity=cap):
            dev = jax.device_put(bufs)
        if self._arena is not None:
            self._arena.reset()  # safe: device_put copied the buffers
        cols = [Column.build(f.dtype, total, d)
                for f, d in zip(self.schema.fields, dev)]
        return DeviceBatch(Table(self.schema.names, cols), total)

    def _assemble(self, cols, ns, dtype, cap):
        """Concatenate one column's sub-batch host buffers into padded
        device-ready buffers; recurses through list/struct children."""
        validity = np.zeros(cap, np.bool_)
        pos = 0
        for c, n in zip(cols, ns):
            validity[pos:pos + n] = c["validity"][:n]
            pos += n
        if isinstance(dtype, (dt.ArrayType, dt.MapType)):
            kid_ns = [int(c["children"][0]["_n"]) for c in cols]
            child_total = sum(kid_ns)
            offs = [np.zeros(1, np.int32)]
            shift = 0
            p = 0
            for c, n, kn in zip(cols, ns, kid_ns):
                o = c["offsets"][:n + 1].astype(np.int32)
                offs.append(o[1:] + shift)
                shift += kn
                p += n
            off = np.concatenate(offs)
            off = np.concatenate(
                [off, np.full(cap + 1 - len(off),
                              off[-1] if len(off) else 0, np.int32)])
            child_cap = bucket_capacity(max(child_total, 1))
            kid = self._assemble([c["children"][0] for c in cols], kid_ns,
                                 Column.element_dtype(dtype), child_cap)
            kid["_n"] = np.int64(child_total)
            return {"validity": validity, "offsets": off,
                    "children": [kid]}
        if isinstance(dtype, dt.StructType):
            kids = []
            for fi, f in enumerate(dtype.fields):
                kid = self._assemble([c["children"][fi] for c in cols],
                                     ns, f.dtype, cap)
                kid["_n"] = np.int64(sum(ns))
                kids.append(kid)
            return {"validity": validity, "children": kids}
        if dtype.is_variable_width:
            datas, offs = [], [np.zeros(1, np.int32)]
            shift = 0
            for c, n in zip(cols, ns):
                datas.append(c["data"])
                o = c["offsets"][:n + 1]
                offs.append(o[1:].astype(np.int32) + shift)
                shift += len(c["data"])
            data = (np.concatenate(datas) if datas
                    else np.zeros(0, np.uint8))
            dcap = bucket_capacity(max(len(data), 1))
            data = np.concatenate(
                [data, np.zeros(dcap - len(data), np.uint8)])
            off = np.concatenate(offs)
            off = np.concatenate(
                [off, np.full(cap + 1 - len(off), off[-1], np.int32)])
            return {"data": data, "validity": validity, "offsets": off}
        np_dt = _np_dtype_for(dtype)
        if isinstance(dtype, dt.DecimalType) and dtype.is_decimal128:
            data = np.zeros((cap, 2), np_dt)
        else:
            data = self._arena_zeros(cap, np_dt)
        pos = 0
        for c, n in zip(cols, ns):
            data[pos:pos + n] = c["data"][:n]
            validity[pos:pos + n] = c["validity"][:n]
            pos += n
        return {"data": data, "validity": validity}

    def _arena_zeros(self, count: int, np_dt) -> np.ndarray:
        """Assembly buffer from the native host arena (RMM-host-pool
        analog); heap fallback when absent or full."""
        import jax
        from ..utils.native import HostArena, native_lib
        # On the CPU backend device_put may ALIAS host memory, so arena
        # reset would corrupt live batches; accelerators always copy H2D.
        if jax.default_backend() == "cpu":
            return np.zeros(count, np.dtype(np_dt))
        if self._arena is None and native_lib() is not None:
            try:
                # the shuffle-assembly arena draws from the GLOBAL host
                # budget (HostAlloc analog); denied -> heap fallback
                from ..memory.host import HostBudgetExceeded, host_manager
                hm = host_manager()
                try:
                    hm.reserve(256 << 20)
                except HostBudgetExceeded:
                    raise MemoryError("host budget")
                try:
                    self._arena = HostArena(256 << 20)
                    self._arena_reserved = True
                except MemoryError:
                    hm.release(256 << 20)
                    raise
            except MemoryError:
                self._arena = None
        if self._arena is not None:
            arr = self._arena.alloc_array(count, np_dt)
            if arr is not None:
                arr[:] = 0
                return arr
        return np.zeros(count, np.dtype(np_dt))

    def cleanup(self):
        import shutil
        if getattr(self, "_arena_reserved", False):
            # return the arena's host-budget reservation (one per
            # shuffle exchange; leaking it would starve the budget)
            from ..memory.host import host_manager
            host_manager().release(256 << 20)
            self._arena_reserved = False
        if self._arena is not None:
            try:
                self._arena.close()
            except Exception:
                pass
            self._arena = None
        shutil.rmtree(self.dir, ignore_errors=True)
