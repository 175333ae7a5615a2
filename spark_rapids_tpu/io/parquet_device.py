"""Device Parquet decode orchestration.

Reference: GpuParquetScan.scala:3364 (Table.readParquet decodes column
chunks on the accelerator) and the COALESCING reader (:2523) that
stitches chunks into ONE buffer for ONE device decode. TPU shape of the
same idea:

  host:   read RAW column-chunk bytes into pinned staging buffers,
          parse page headers + RLE run headers (O(pages + runs)), and —
          for snappy chunks — decompress pages IN PARALLEL on the
          multithreaded prefetch pool, off the compute thread; then lay
          the value bytes out for the device: PLAIN values and the
          dictionary's values contiguous, bit-packed index and level
          runs gathered by bit width, a piece table that places them
  device: the `pq_*` programs of ops/parquet_decode.py: reshape-and-
          shift value assembly, bit unpacking, piece stitching,
          dictionary lookup, def-level -> validity, string extents —
          shapes static per bucket.

Eligible chunks (everything else falls back to the pyarrow host path,
per column, with a reason counter): UNCOMPRESSED or SNAPPY; flat INT32/
INT64/FLOAT/DOUBLE/BYTE_ARRAY, and FIXED_LEN_BYTE_ARRAY decimals of at
most 8 bytes (precision <= 18); PLAIN and RLE_DICTIONARY/
PLAIN_DICTIONARY data pages in any mix (a writer's dictionary that
outgrows its page limit gives way to PLAIN pages), with an index bit
width of each page's own; v1 (RLE def levels) and v2 (uncompressed-
levels layout) data pages. `sql.parquet.deviceSnappy` additionally
moves qualifying pages' snappy decompression itself onto the device
(ops/parquet_decode.pq_snappy_expand).
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..profiler import tracing
from . import parquet_thrift as pt

__all__ = ["chunk_device_plan", "decode_chunk_device",
           "eligible_chunks", "fallback_reasons", "DeviceChunk",
           "DecodeFallback"]

_PHYS_WIDTH = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}
_FLBA = "FIXED_LEN_BYTE_ARRAY"
_OK_PHYS = set(_PHYS_WIDTH) | {"BYTE_ARRAY", _FLBA}
_OK_CODECS = {"UNCOMPRESSED", "SNAPPY"}

_OK_ENCODINGS = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                 "BIT_PACKED"}

# dictionary pages past this entry count skip the host extent walk
_MAX_DICT_VALUES = 1 << 20
# string output buffers past this bound fall back (pathological blowup)
_MAX_STRING_BYTES = 1 << 30


class DecodeFallback(Exception):
    """A chunk the device decode leaves to the host, and why: `cause` is
    `malformed` (bytes that do not parse as the footer and page headers
    say) or `shape` (a layout the device decode does not take). The
    scan counts it as `deviceDecodeFallback.<cause>`."""

    def __init__(self, cause: str, detail: str):
        super().__init__(f"{cause}: {detail}")
        self.cause = cause
        self.detail = detail


def _malformed(detail: str) -> DecodeFallback:
    return DecodeFallback("malformed", detail)


class DeviceChunk:
    """Host-parsed metadata for one device-decodable column chunk."""

    def __init__(self, name: str, physical: str, nullable: bool,
                 raw, pages: List[pt.PageInfo], num_values: int,
                 staging=None, dev_pages=None, width: int = 0):
        self.name = name
        self.physical = physical
        self.nullable = nullable
        self.raw = raw                # bytes | memoryview (live prefix)
        self.pages = pages
        self.num_values = num_values
        # bytes of one value (a fixed-width type's, FLBA's type length)
        self.width = width
        # staging-pool leases backing `raw`; released via close()
        self.staging = staging or []
        # device-snappy work: (slot_off, comp np.uint8, el_dst, el_lit,
        # el_src, n_el, out_len) per page decompressed ON device
        self.dev_pages = dev_pages or []
        self.uploaded = None          # device uint8 chunk (set by decode)

    def close(self, sync: bool = False):
        """Return staging buffers to the pool. With sync=True, joins the
        upload first — mandatory on real accelerators where the H2D
        copy may still be reading the host buffer (the prefetch worker
        pays this wait, not the compute thread)."""
        if sync and self.uploaded is not None:
            try:
                import jax
                # tpulint: allow[block-sync] prefetch-thread join: pool
                jax.block_until_ready(self.uploaded)  # reuse must not
                # race the in-flight H2D copy (never the compute thread)
            except Exception:
                pass
        for b in self.staging:
            b.release()
        self.staging = []


def _classify(col, name: str, scol) -> Optional[Tuple[str, str]]:
    """(category, detail) why this chunk cannot device-decode, or None
    when it is eligible. Categories are the fallback-counter keys:
    codec / type / encoding / nested. `scol` is the column's schema
    entry (its FIXED_LEN_BYTE_ARRAY length and converted type)."""
    if "." in name:
        return ("nested", "nested column (repetition levels)")
    if col.compression not in _OK_CODECS:
        return ("codec", f"codec {col.compression}")
    if col.physical_type not in _OK_PHYS:
        return ("type", f"physical type {col.physical_type}")
    if col.physical_type == _FLBA and not (
            scol.converted_type == "DECIMAL" and 1 <= scol.length <= 8):
        return ("type", f"physical type {_FLBA}({scol.length}) "
                        f"{scol.converted_type} (decimals of at most 8 "
                        f"bytes decode on the device)")
    bad = set(col.encodings) - _OK_ENCODINGS
    if bad:
        return ("encoding", f"encoding {'/'.join(sorted(bad))}")
    return None


def _classified(pf, rg: int, columns: List[str]):
    """(name, column index, _classify verdict) of each of `columns`
    present in row group `rg`."""
    md = pf.metadata
    names = {}
    for ci in range(md.num_columns):
        col = md.row_group(rg).column(ci)
        names[".".join(col.path_in_schema.split("."))] = ci
    for name in columns:
        ci = names.get(name)
        if ci is not None:
            yield name, ci, _classify(md.row_group(rg).column(ci), name,
                                      pf.schema.column(ci))


def eligible_chunks(pf, rg: int, columns: List[str]) -> Dict[str, int]:
    """Map column name -> column index for chunks the device path can
    decode in row group `rg`."""
    return {name: ci for name, ci, why in _classified(pf, rg, columns)
            if why is None}


def fallback_reasons(pf, rg: int,
                     columns: List[str]) -> Dict[str, Tuple[str, str]]:
    """Per-column (category, detail) for the columns of `columns` that
    CANNOT device-decode in row group `rg` (the why-did-this-scan-fall-
    back answer, fed to metrics + the plan auditor)."""
    return {name: why for name, _ci, why in _classified(pf, rg, columns)
            if why is not None}


# ----------------------------------------------------------------------
# snappy: host tag parse (device kernel input) + pool decompression
# ----------------------------------------------------------------------
def _parse_snappy_elements(buf, start: int, end: int):
    """Walk one snappy-compressed span's tag stream into an element
    table for ops/parquet_decode.pq_snappy_expand: O(elements) host
    work, literal bytes untouched. Returns (out_len, dst[], is_lit[],
    src[]) where src is a buffer offset for literals and a back-offset
    for copies. Raises ThriftError on a malformed stream."""
    p = start
    # preamble: varint uncompressed length
    out_len = 0
    shift = 0
    while True:
        if p >= end:
            raise pt.ThriftError("snappy preamble past end")
        b = buf[p]
        p += 1
        out_len |= (b & 0x7F) << shift
        if not b & 0x80:
            break
        shift += 7
        if shift > 35:
            raise pt.ThriftError("snappy preamble varint too long")
    dst_l: List[int] = []
    lit_l: List[int] = []
    src_l: List[int] = []
    dst = 0
    while dst < out_len:
        if p >= end:
            raise pt.ThriftError("snappy tag past end")
        tag = buf[p]
        t = tag & 3
        if t == 0:                          # literal
            ln = (tag >> 2) + 1
            p += 1
            if ln > 60:
                nb = ln - 60
                if p + nb > end:
                    raise pt.ThriftError("snappy literal len past end")
                ln = 0
                for j in range(nb):
                    ln |= buf[p + j] << (8 * j)
                ln += 1
                p += nb
            if p + ln > end:
                raise pt.ThriftError("snappy literal bytes past end")
            dst_l.append(dst)
            lit_l.append(1)
            src_l.append(p - start)    # relative to the compressed span
            p += ln
        else:                               # copy
            if t == 1:
                if p + 2 > end:
                    raise pt.ThriftError("snappy copy1 past end")
                ln = ((tag >> 2) & 7) + 4
                off = ((tag >> 5) << 8) | buf[p + 1]
                p += 2
            elif t == 2:
                if p + 3 > end:
                    raise pt.ThriftError("snappy copy2 past end")
                ln = (tag >> 2) + 1
                off = buf[p + 1] | (buf[p + 2] << 8)
                p += 3
            else:
                if p + 5 > end:
                    raise pt.ThriftError("snappy copy4 past end")
                ln = (tag >> 2) + 1
                off = (buf[p + 1] | (buf[p + 2] << 8)
                       | (buf[p + 3] << 16) | (buf[p + 4] << 24))
                p += 5
            if off <= 0 or off > dst:
                raise pt.ThriftError("snappy copy offset out of range")
            dst_l.append(dst)
            lit_l.append(0)
            src_l.append(off)
            ln = min(ln, out_len - dst)
        dst += ln
    return out_len, dst_l, lit_l, src_l


def _snappy_codec():
    import pyarrow as pa
    return pa.Codec("snappy")


def _decompress_page(codec, src, out, out_off: int, expect: int):
    """Decompress one page payload into `out[out_off:out_off+expect]`."""
    buf = codec.decompress(bytes(src), expect)
    got = np.frombuffer(buf, np.uint8, len(buf))
    if len(got) != expect:
        raise pt.ThriftError(
            f"snappy page decompressed to {len(got)}, expected {expect}")
    out[out_off:out_off + expect] = got


def chunk_device_plan(pf, path: str, rg: int, ci: int,
                      name: str, nullable: bool, pool=None,
                      decomp_pool=None, device_snappy: bool = False,
                      metrics=None) -> DeviceChunk:
    """Read raw bytes + parse page metadata for one column chunk.
    Snappy chunks come back REASSEMBLED: page payloads decompressed
    (in parallel on `decomp_pool`, or host-inline) into one contiguous
    staging buffer whose PageInfo offsets mirror the uncompressed
    layout — the downstream device decode is codec-blind. With
    `device_snappy`, qualifying pages instead carry a host-parsed
    element table and decompress on device. Raises DecodeFallback
    (nothing leased stays leased)."""
    import time as _time

    with tracing.span("io.read", "io"):
        col = pf.metadata.row_group(rg).column(ci)
        width = (pf.schema.column(ci).length if col.physical_type == _FLBA
                 else _PHYS_WIDTH.get(col.physical_type, 0))
        start = col.data_page_offset
        if col.has_dictionary_page and col.dictionary_page_offset is not None:
            start = min(start, col.dictionary_page_offset)
        size = col.total_compressed_size
        staging = []
        try:
            if pool is not None:
                lease = pool.acquire(size)
                staging.append(lease)
                with open(path, "rb") as f:
                    f.seek(start)
                    if f.readinto(lease.view()) != size:
                        raise _malformed("column chunk past end of file")
                raw = memoryview(lease.array)[:size]
            else:
                with open(path, "rb") as f:
                    f.seek(start)
                    raw = f.read(size)
            try:
                pages = pt.parse_page_headers(raw, col.num_values)
            except pt.ThriftError as e:
                raise _malformed(str(e)) from e
            for p in pages:
                if p.page_type not in (pt.DATA_PAGE, pt.DATA_PAGE_V2):
                    continue
                if p.encoding not in (pt.PLAIN, pt.PLAIN_DICTIONARY,
                                      pt.RLE_DICTIONARY):
                    raise DecodeFallback("shape",
                                         f"page encoding {p.encoding}")
                if (p.page_type == pt.DATA_PAGE and nullable
                        and p.def_level_encoding != pt.RLE):
                    raise DecodeFallback(
                        "shape", "def levels not RLE/bit-packed hybrid")
                if (p.page_type == pt.DATA_PAGE_V2
                        and p.rep_levels_byte_length > 0):
                    raise DecodeFallback("shape", "repetition levels")

            dev_pages = []
            if col.compression == "SNAPPY":
                raw, pages, dev_pages = _reassemble(
                    raw, pages, pool, staging, decomp_pool,
                    device_snappy and not nullable, metrics, _time)
        except BaseException:
            for b in staging:
                b.release()
            raise
        return DeviceChunk(name, col.physical_type, nullable, raw, pages,
                           col.num_values, staging=staging,
                           dev_pages=dev_pages, width=width)


def _reassemble(raw, pages, pool, staging, decomp_pool, device_snappy,
                metrics, _time):
    """Decompress a snappy chunk's pages into one buffer laid out as the
    uncompressed chunk: (buffer, pages re-pointed, device-snappy
    pages)."""
    t0 = _time.perf_counter()
    total_out = sum(max(p.uncompressed_size, 0) for p in pages)
    if pool is not None:
        out_lease = pool.acquire(total_out)
        staging.append(out_lease)
        out = out_lease.array
    else:
        out = np.zeros(max(total_out, 1), np.uint8)
    new_pages = []
    dev_pages = []
    tasks = []                    # (src span, out_off, expect)
    dst = 0
    for p in pages:
        usize = max(p.uncompressed_size, 0)
        new_pages.append(replace(p, data_offset=dst, compressed_size=usize))
        off, end = p.data_offset, p.data_offset + p.compressed_size
        if p.page_type == pt.DATA_PAGE_V2:
            # v2 keeps levels UNCOMPRESSED ahead of the data section
            lvl = max(p.rep_levels_byte_length, 0) \
                + max(p.def_levels_byte_length, 0)
            lvl = min(lvl, min(p.compressed_size, usize))
            out[dst:dst + lvl] = np.frombuffer(raw[off:off + lvl], np.uint8)
            if p.data_compressed:
                tasks.append((raw[off + lvl:end], dst + lvl, usize - lvl))
            else:
                out[dst + lvl:dst + usize] = np.frombuffer(
                    raw[off + lvl:end], np.uint8)
        elif (device_snappy and p.page_type == pt.DATA_PAGE
              and p.encoding == pt.PLAIN):
            try:
                out_len, dl, ll, sl = _parse_snappy_elements(raw, off, end)
            except pt.ThriftError:
                out_len = -1
            if out_len != usize:
                tasks.append((raw[off:end], dst, usize))
            else:
                comp = np.frombuffer(raw[off:end], np.uint8)
                # tpulint: allow[host-sync] python lists, no device data
                el = [np.asarray(x, np.int32) for x in (dl, ll, sl)]
                dev_pages.append((dst, comp, el[0], el[1], el[2], len(dl),
                                  out_len))
        else:
            tasks.append((raw[off:end], dst, usize))
        dst += usize
    codec = _snappy_codec()
    try:
        if decomp_pool is not None and len(tasks) > 1:
            # per-page, parallel across pages: pyarrow's snappy releases
            # the GIL, so the prefetch pool really fans out
            list(decomp_pool.map(
                lambda t: _decompress_page(codec, t[0], out, t[1], t[2]),
                tasks))
        else:
            for src, ooff, expect in tasks:
                _decompress_page(codec, src, out, ooff, expect)
    except Exception as e:
        raise _malformed(f"snappy: {e}") from e
    if metrics is not None:
        metrics.add("decompressBusySecs", _time.perf_counter() - t0)
        metrics.add("decompressedBytes", total_out)
    return memoryview(out)[:total_out], new_pages, dev_pages


# ----------------------------------------------------------------------
# host layout: what the device reads, and the tables that place it
# ----------------------------------------------------------------------
class _Stream:
    """One RLE/bit-packed hybrid stream (dictionary indices or definition
    levels) laid out for the device: its bit-packed runs' bytes gathered
    by bit width, one `pq_unpack` launch a width, and the pieces that
    place the unpacked values and the RLE runs in the stream."""

    def __init__(self):
        self.groups: Dict[int, list] = {}   # bw -> [byte spans, values]
        self.pieces: List[list] = []        # [dst, count, bw (0: RLE), src]

    def add(self, runs: List[pt.RleRun], bw: int, dst0: int):
        for r in runs:
            if r.count <= 0:
                continue
            if r.is_packed and bw > 0:
                g = self.groups.setdefault(bw, [[], 0])
                ngroups = -(-r.count // 8)
                self.pieces.append([dst0 + r.out_start, r.count, bw, g[1]])
                g[0].append((r.byte_offset, ngroups * bw))
                g[1] += 8 * ngroups
            else:
                # an RLE run, or bit-packed at width 0 (all zero)
                value = 0 if r.is_packed else r.value
                if value >= 1 << 31:
                    raise _malformed(f"RLE value {value} past int32")
                self.pieces.append([dst0 + r.out_start, r.count, 0, value])

    def host(self, rb: np.ndarray, cap: int):
        """(payloads [(bw, offset, uint8[rows, ROW_BYTES])], src length,
        (piece table, its length, dense)): what `_stream_device` uploads
        and launches."""
        from ..ops import parquet_decode as pd
        base, off, need = {}, 0, 2 * cap
        payloads = []
        for bw, (spans, nv) in self.groups.items():
            base[bw] = off
            # 16 groups (128 values) a row, in a few row counts (so a few
            # shapes): at most cap / 128 where the values fit, so the src
            # buffer of 2 * cap holds every width's rows from where its
            # values start (more only where runs pad past the rows)
            rows = min(pd.bucket_len(-(-nv // 128), floor=1024), cap // 128)
            if 128 * rows < nv:
                rows = pd.bucket_len(-(-nv // 128))
            need = max(need, off + 128 * rows)
            buf = np.zeros(rows * 16 * bw, np.uint8)
            at = 0
            for boff, nb in spans:
                if boff + nb > rb.shape[0]:
                    raise _malformed("bit-packed run past end of chunk")
                buf[at:at + nb] = rb[boff:boff + nb]
                at += nb
            rowed = np.zeros((rows, pd.ROW_BYTES), np.uint8)
            rowed[:, :16 * bw] = buf.reshape(rows, 16 * bw)
            payloads.append((bw, off, rowed))
            off += nv
        return payloads, pd.bucket_len(need), \
            _piece_table(self.pieces, base, cap)


def _piece_table(pieces, base, cap: int):
    """Merge adjacent pieces that continue each other and pad into an
    int32[4, K] table (dst, count, src, bit-packed) with its length;
    `dense` when the table is short enough for dynamic slices."""
    from ..ops import parquet_decode as pd
    merged: List[list] = []
    for dst, cnt, bw, src in pieces:
        if bw:
            src += base[bw]
        if merged:
            m = merged[-1]
            if m[2] == bw and m[0] + m[1] == dst and (
                    m[3] + m[1] == src if bw else m[3] == src):
                m[1] += cnt
                continue
        merged.append([dst, cnt, bw, src])
    dense = len(merged) <= pd.DENSE_PIECES
    k = pd.DENSE_PIECES if dense else pd.bucket_len(len(merged))
    table = np.zeros((4, k), np.int32)
    table[0, :] = cap                     # padding rows start past the end
    for j, (dst, cnt, bw, src) in enumerate(merged):
        table[:, j] = (dst, cnt, src, 1 if bw else 0)
    return table, len(merged), dense


def _ones(rb: np.ndarray, runs: List[pt.RleRun]) -> int:
    """Definition levels equal to 1 (non-null values) in a bit-width-1
    run list."""
    n = 0
    for r in runs:
        if r.is_packed:
            nb = -(-r.count // 8)
            if r.byte_offset + nb > rb.shape[0]:
                raise _malformed("def-level run past end of chunk")
            bits = np.unpackbits(rb[r.byte_offset:r.byte_offset + nb],
                                 bitorder="little")
            n += int(bits[:r.count].sum())
        elif r.value == 1:
            n += r.count
    return n


class _Layout:
    """A chunk's pages walked on the host (`_layout`)."""

    def __init__(self):
        self.defs = _Stream()
        self.idx = _Stream()
        self.nulls = False
        self.dict_page = None
        self.plain: List[tuple] = []    # (payload offset, values, bytes)
        self.segs: List[list] = []      # [dst, count, from PLAIN, src]
        self.n_dict = 0                 # values of dictionary pages
        self.n_plain = 0                # values of PLAIN pages
        self.has_dict = False
        self.max_page_values = 1


def _hybrid_runs(raw, start: int, end: int, n: int, bw: int):
    runs = pt.parse_hybrid_runs(raw, start, end, n, bw)
    if sum(r.count for r in runs) != n:
        raise _malformed(f"hybrid stream of {n} values ends early")
    return runs


def _layout(c: DeviceChunk, rb: np.ndarray, cap: int) -> _Layout:
    """Walk every data page: its definition levels (and whether any value
    is null), its value section, its index runs. O(pages + runs) but for
    the def-level bits of a chunk that has nulls. Raises
    DecodeFallback."""
    lay = _Layout()
    row = packed = 0
    for p in c.pages:
        if p.page_type == pt.DICTIONARY_PAGE:
            if lay.dict_page is not None:
                raise _malformed("a second dictionary page")
            lay.dict_page = p
            continue
        if p.page_type not in (pt.DATA_PAGE, pt.DATA_PAGE_V2):
            continue
        off = p.data_offset
        end = p.data_offset + p.compressed_size
        nv = p.num_values
        runs = None
        if p.page_type == pt.DATA_PAGE_V2:
            rep = max(p.rep_levels_byte_length, 0)
            lvl = rep + max(p.def_levels_byte_length, 0)
            if c.nullable and p.def_levels_byte_length > 0:
                runs = _hybrid_runs(c.raw, off + rep, off + lvl, nv, 1)
            off += lvl
        elif c.nullable:
            # v1: [int32 LE length][RLE/bit-packed hybrid, bit width 1]
            if off + 4 > end:
                raise _malformed("def-level length past end of page")
            ln = int.from_bytes(bytes(rb[off:off + 4]), "little")
            runs = _hybrid_runs(c.raw, off + 4, off + 4 + ln, nv, 1)
            off += 4 + ln
        if off > end:
            raise _malformed("level sections past end of page")
        nn = nv
        if runs is not None:
            nn = _ones(rb, runs)
            lay.defs.add(runs, 1, row)
        lay.nulls |= nn != nv
        lay.max_page_values = max(lay.max_page_values, nn)
        if p.encoding == pt.PLAIN:
            if c.width and off + nn * c.width > end:
                raise _malformed("PLAIN values past end of page")
            lay.plain.append((off, lay.n_plain, nn * c.width))
            seg = [packed, nn, True, cap + lay.n_plain]
            lay.n_plain += nn
        else:
            lay.has_dict = True
            if nn:
                if off >= end:
                    raise _malformed("index section missing")
                bw = int(rb[off])
                if bw > 32:
                    raise _malformed(f"dict index bit width {bw}")
                lay.idx.add(_hybrid_runs(c.raw, off + 1, end, nn, bw), bw,
                            lay.n_dict)
            seg = [packed, nn, False, lay.n_dict]
            lay.n_dict += nn
        last = lay.segs[-1] if lay.segs else None
        if (last is not None and last[2] == seg[2]
                and last[0] + last[1] == seg[0]
                and last[3] + last[1] == seg[3]):
            last[1] += nn
        elif nn:
            lay.segs.append(seg)
        packed += nn
        row += nv
    if row != c.num_values:
        raise _malformed(f"pages hold {row} values, footer {c.num_values}")
    if lay.has_dict and lay.dict_page is None:
        raise _malformed("dictionary-encoded pages, no dictionary page")
    return lay


def _segments(lay: _Layout, cap: int):
    """(merge, segment table, dense): whether the packed stream stitches
    dictionary and PLAIN pages, and the table that does."""
    merge = lay.has_dict and bool(lay.plain)
    pieces = [[d, n, 1, s] for d, n, _plain, s in lay.segs]
    table, npc, dense = _piece_table(pieces, {1: 0}, cap)
    return merge, (table, npc), dense


def _values_array(buf: np.ndarray, c: DeviceChunk):
    """The device's view of `buf`, contiguous values: int32/int64 words
    for the little-endian types (a float's bits), or for a
    FIXED_LEN_BYTE_ARRAY decimal its bytes as rows of 128 values."""
    if c.physical == _FLBA:
        return buf.reshape(-1, 128 * c.width)
    return buf.view(np.int32 if c.width == 4 else np.int64)


def _fixed_host(c: DeviceChunk, rb: np.ndarray, lay: _Layout, cap: int):
    """The PLAIN values (contiguous, in page order) and the dictionary's
    values as `_values_array`s, the dictionary's bucketed length, and
    the PLAIN pages the device decompresses [(byte offset in the PLAIN
    array, device page)]."""
    from ..ops import parquet_decode as pd
    w = c.width
    plain = dict_raw = None
    dict_len = 0
    patches = []
    if lay.plain or not lay.has_dict:
        dev = {pg[0]: pg for pg in c.dev_pages}
        # as long as the rows: one shape for every chunk of a capacity
        plain = np.zeros(cap * w, np.uint8)
        for off, first, nbytes in lay.plain:
            at = first * w
            if off in dev:
                patches.append((at, dev[off]))
            else:
                plain[at:at + nbytes] = rb[off:off + nbytes]
        if not patches:             # else patched as bytes on the device
            plain = _values_array(plain, c)
    if lay.has_dict:
        dp = lay.dict_page
        nbytes = dp.num_values * w
        if nbytes > dp.compressed_size:
            raise _malformed("dictionary values past end of page")
        dict_len = pd.bucket_len(max(dp.num_values, 1))
        dict_raw = np.zeros(max(dict_len, 128) * w, np.uint8)
        dict_raw[:nbytes] = rb[dp.data_offset:dp.data_offset + nbytes]
        dict_raw = _values_array(dict_raw, c)
    return plain, patches, dict_raw, dict_len


def _walk_byte_array_extents(buf, off: int, end: int, n: int):
    """Host walk of a PLAIN BYTE_ARRAY section's [len][bytes] chain
    (dictionary pages only — n is small). Returns (starts, lens)
    int32[n] or raises ThriftError."""
    starts = np.zeros(n, np.int32)
    lens = np.zeros(n, np.int32)
    p = off
    for i in range(n):
        if p + 4 > end:
            raise pt.ThriftError("byte-array extent walk past end")
        ln = int.from_bytes(bytes(buf[p:p + 4]), "little")
        if ln < 0 or p + 4 + ln > end:
            raise pt.ThriftError("byte-array length out of range")
        starts[i] = p + 4
        lens[i] = ln
        p += 4 + ln
    return starts, lens


def _strings_host(c: DeviceChunk, rb: np.ndarray, lay: _Layout):
    """Host tables of a BYTE_ARRAY chunk (dictionary extents, PLAIN page
    table) and the bound of the decoded bytes (dcap)."""
    from ..ops import parquet_decode as pd
    bound = 0
    dstart = dlen = plain_off = plain_first = None
    if lay.has_dict:
        dp = lay.dict_page
        ndict = dp.num_values
        if ndict > _MAX_DICT_VALUES:
            raise DecodeFallback("shape", f"string dictionary of {ndict}")
        try:
            starts, lens = _walk_byte_array_extents(
                rb, dp.data_offset, dp.data_offset + dp.compressed_size,
                ndict)
        except pt.ThriftError as e:
            raise _malformed(str(e)) from e
        d = pd.bucket_len(max(ndict, 1))
        dstart = np.zeros(d, np.int32)
        dlen = np.zeros(d, np.int32)
        dstart[:ndict], dlen[:ndict] = starts, lens
        bound += lay.n_dict * max(int(lens.max()) if ndict else 0, 1)
    if lay.plain:
        bound += sum(p.compressed_size for p in c.pages
                     if p.page_type in (pt.DATA_PAGE, pt.DATA_PAGE_V2))
        k = pd.bucket_len(len(lay.plain))
        plain_off = np.zeros(k, np.int32)
        plain_first = np.full(k, lay.n_plain, np.int32)
        for j, (off, first, _nb) in enumerate(lay.plain):
            plain_off[j], plain_first[j] = off, first
    if bound > _MAX_STRING_BYTES:
        raise DecodeFallback("shape", f"string bytes bound {bound}")
    return (dstart, dlen, plain_off, plain_first), \
        pd.bucket_len(max(bound, 1), floor=128)


def _snappy_page(pg):
    """Decompress one device-snappy page on the device."""
    import jax.numpy as jnp

    from ..ops import parquet_decode as pd
    _slot, comp, dl, ll, sl, n_el, out_len = pg
    e = pd.bucket_len(max(n_el, 1))
    dst = np.full(e, out_len, np.int32)
    lit = np.zeros(e, np.int32)
    srcs = np.zeros(e, np.int32)
    dst[:n_el], lit[:n_el], srcs[:n_el] = dl, ll, sl
    cap_out = pd.bucket_len(max(out_len, 1), floor=128)
    kbits = max(1, (cap_out - 1).bit_length())
    return pd.pq_snappy_expand(
        jnp.asarray(comp), jnp.asarray(dst), jnp.asarray(lit),
        jnp.asarray(srcs), n_el, out_len, kbits=kbits, cap=cap_out)


def _stream_device(stream_host, uploaded):
    """Launch a stream's unpacks: (src buffer, (piece table, length))."""
    from ..ops import parquet_decode as pd
    payloads, src_cap, (table, npc, _dense) = stream_host
    bufs, table_dev = uploaded
    src = pd.pq_zeros(cap=src_cap)
    for (bw, off, _b), buf in zip(payloads, bufs):
        src = pd.pq_unpack(src, buf, np.int32(off), np.int32(bw))
    return src, (table_dev, np.int32(npc))


def decode_chunk_device(c: DeviceChunk, cap: int, metrics=None,
                        dtype: Optional[str] = None):
    """Decode one chunk at capacity `cap`. Fixed-width chunks return
    (device values, device validity), the values as numpy dtype name
    `dtype` (the physical type's own without it); BYTE_ARRAY chunks
    return (data bytes, validity, offsets). Raises DecodeFallback where
    the chunk's bytes or layout leave it to the host."""
    import time as _time

    import jax

    from ..ops import parquet_decode as pd

    with tracing.span("io.decode", "io"):
        rb = np.frombuffer(c.raw, np.uint8)
        try:
            lay = _layout(c, rb, cap)
        except pt.ThriftError as e:
            raise _malformed(str(e)) from e
        idx = lay.idx.host(rb, cap) if lay.has_dict else None
        defs = lay.defs.host(rb, cap) if lay.nulls else None
        merge, segs, dense_seg = _segments(lay, cap)
        strings = c.physical == "BYTE_ARRAY"
        if strings:
            host, dcap = _strings_host(c, rb, lay)
            patches = []
        else:
            plain, patches, dict_raw, dict_len = _fixed_host(c, rb, lay,
                                                             cap)
            host = (plain, dict_raw)
        streams = [s for s in (idx, defs) if s is not None]
        with tracing.span("io.upload", "io"):
            t0 = _time.perf_counter()
            send = [host, [([b for _bw, _o, b in s[0]], s[2][0])
                           for s in streams], segs[0]]
            if strings:
                if c.staging:
                    # the full pow2 staging buffer: stable shape
                    send.append(c.staging[-1].array)
                else:
                    send.append(rb)
            got = jax.device_put(send)
            if metrics is not None:
                # dispatch-time on async backends (docs/observability.md)
                metrics.add("uploadSecs", _time.perf_counter() - t0)
                metrics.add("uploadedBytes", sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(send)))
        host_dev, streams_dev, seg_table = got[0], got[1], got[2]
        dev = [_stream_device(s, u) for s, u in zip(streams, streams_dev)]
        idx_dev = dev.pop(0) if idx is not None else (None, None)
        def_dev = dev.pop(0) if defs is not None else (None, None)
        seg_dev = (seg_table, np.int32(segs[1]))
        flags = dict(
            cap=cap, merge=merge, nulls=lay.nulls, dense_seg=dense_seg,
            dense_idx=idx[2][2] if idx is not None else True,
            dense_def=defs[2][2] if defs is not None else True)
        n = np.int32(c.num_values)
        if strings:
            dstart, dlen, plain_off, plain_first = host_dev
            chunk = got[3]
            for pg in c.dev_pages:
                chunk = pd.pq_patch(chunk, _snappy_page(pg),
                                    np.int32(pg[0]), np.int32(pg[6]))
            c.uploaded = chunk
            kbits = max(1, max(lay.max_page_values - 1, 1).bit_length())
            return pd.pq_strings(
                chunk, dstart, dlen, idx_dev[0], idx_dev[1], plain_off,
                plain_first, np.int32(len(lay.plain)),
                np.int32(lay.n_plain), seg_dev, def_dev[0], def_dev[1], n,
                dcap=dcap, kbits=kbits, **flags)
        plain_dev, dict_dev = host_dev
        if patches:
            for at, pg in patches:
                plain_dev = pd.pq_patch(plain_dev, _snappy_page(pg),
                                        np.int32(at), np.int32(pg[6]))
            plain_dev = pd.pq_values(plain_dev, width=c.width,
                                     flba=c.physical == _FLBA)
        return pd.pq_fixed(
            plain_dev, dict_dev, idx_dev[0], idx_dev[1], seg_dev,
            def_dev[0], def_dev[1], n, kind=c.physical, width=c.width,
            dict_len=dict_len, dtype=dtype, **flags)
