"""Device Parquet decode orchestration (slice 2).

Reference: GpuParquetScan.scala:3364 (Table.readParquet decodes column
chunks on the accelerator) and the COALESCING reader (:2523) that
stitches chunks into ONE buffer for ONE device decode. TPU shape of the
same idea:

  host:   read RAW column-chunk bytes into pinned staging buffers,
          parse page headers + RLE run tables (O(pages + runs), no
          value bytes touched), and — for snappy chunks — decompress
          pages IN PARALLEL on the multithreaded prefetch pool, off
          the compute thread
  device: ONE uint8 upload per chunk; PLAIN lane assembly, hybrid
          run expansion (def levels, dictionary indices), dictionary
          gather, BYTE_ARRAY offset extraction via pointer doubling,
          def-level->validity + packed-value scatter — all jitted with
          shapes static per (pages, runs, capacity) bucket.

Slice-2 eligibility (everything else falls back to the pyarrow host
path, per column, with a reason counter): UNCOMPRESSED or SNAPPY
chunks; flat INT32/INT64/FLOAT/DOUBLE/BYTE_ARRAY physical types; PLAIN
or RLE_DICTIONARY/PLAIN_DICTIONARY data pages; v1 (RLE def levels) and
v2 (uncompressed-levels layout) data pages. `sql.parquet.deviceSnappy`
additionally moves qualifying pages' snappy decompression itself onto
the device (ops/parquet_decode.snappy_expand).
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..profiler import tracing
from . import parquet_thrift as pt

__all__ = ["chunk_device_plan", "decode_chunk_device",
           "eligible_chunks", "fallback_reasons", "DeviceChunk"]

_PHYS_WIDTH = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}
_PHYS_NP = {"INT32": "int32", "INT64": "int64",
            "FLOAT": "float32", "DOUBLE": "float64"}
_OK_PHYS = set(_PHYS_WIDTH) | {"BYTE_ARRAY"}
_OK_CODECS = {"UNCOMPRESSED", "SNAPPY"}

_OK_ENCODINGS = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                 "BIT_PACKED"}

# dictionary pages past this entry count skip the host extent walk
_MAX_DICT_VALUES = 1 << 20
# string output buffers past this bound fall back (pathological blowup)
_MAX_STRING_BYTES = 1 << 30


class DeviceChunk:
    """Host-parsed metadata for one device-decodable column chunk."""

    def __init__(self, name: str, physical: str, nullable: bool,
                 raw, pages: List[pt.PageInfo], num_values: int,
                 staging=None, dev_pages=None):
        self.name = name
        self.physical = physical
        self.nullable = nullable
        self.raw = raw                # bytes | memoryview (live prefix)
        self.pages = pages
        self.num_values = num_values
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


def _classify(col, name: str) -> Optional[Tuple[str, str]]:
    """(category, detail) why this chunk cannot device-decode, or None
    when it is eligible. Categories are the fallback-counter keys:
    codec / type / encoding / nested."""
    if "." in name:
        return ("nested", "nested column (repetition levels)")
    if col.compression not in _OK_CODECS:
        return ("codec", f"codec {col.compression}")
    if col.physical_type not in _OK_PHYS:
        return ("type", f"physical type {col.physical_type}")
    bad = set(col.encodings) - _OK_ENCODINGS
    if bad:
        return ("encoding", f"encoding {'/'.join(sorted(bad))}")
    return None


def eligible_chunks(pf, rg: int, columns: List[str]) -> Dict[str, int]:
    """Map column name -> column index for chunks the device path can
    decode in row group `rg`."""
    md = pf.metadata
    out = {}
    names = {}
    for ci in range(md.num_columns):
        col = md.row_group(rg).column(ci)
        names[".".join(col.path_in_schema.split("."))] = ci
    for name in columns:
        ci = names.get(name)
        if ci is None:
            continue
        col = md.row_group(rg).column(ci)
        if _classify(col, name) is None:
            out[name] = ci
    return out


def fallback_reasons(pf, rg: int,
                     columns: List[str]) -> Dict[str, Tuple[str, str]]:
    """Per-column (category, detail) for the columns of `columns` that
    CANNOT device-decode in row group `rg` (the why-did-this-scan-fall-
    back answer, fed to metrics + the plan auditor)."""
    md = pf.metadata
    names = {}
    for ci in range(md.num_columns):
        col = md.row_group(rg).column(ci)
        names[".".join(col.path_in_schema.split("."))] = ci
    out = {}
    for name in columns:
        ci = names.get(name)
        if ci is None:
            continue
        got = _classify(md.row_group(rg).column(ci), name)
        if got is not None:
            out[name] = got
    return out


# ----------------------------------------------------------------------
# snappy: host tag parse (device kernel input) + pool decompression
# ----------------------------------------------------------------------
def _parse_snappy_elements(buf, start: int, end: int):
    """Walk one snappy-compressed span's tag stream into an element
    table for ops/parquet_decode.snappy_expand: O(elements) host work,
    literal bytes untouched. Returns (out_len, dst[], is_lit[], src[])
    where src is a buffer offset for literals and a back-offset for
    copies. Raises ThriftError on a malformed stream."""
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
                      metrics=None) -> Optional[DeviceChunk]:
    """Read raw bytes + parse page metadata for one column chunk.
    Snappy chunks come back REASSEMBLED: page payloads decompressed
    (in parallel on `decomp_pool`, or host-inline) into one contiguous
    staging buffer whose PageInfo offsets mirror the uncompressed
    layout — the downstream device decode is codec-blind. With
    `device_snappy`, qualifying pages instead carry a host-parsed
    element table and decompress on device."""
    import time as _time

    with tracing.span("io.read", "io"):
        col = pf.metadata.row_group(rg).column(ci)
        start = col.data_page_offset
        if col.has_dictionary_page and col.dictionary_page_offset is not None:
            start = min(start, col.dictionary_page_offset)
        size = col.total_compressed_size
        staging = []
        if pool is not None:
            lease = pool.acquire(size)
            staging.append(lease)
            with open(path, "rb") as f:
                f.seek(start)
                if f.readinto(lease.view()) != size:
                    for b in staging:
                        b.release()
                    return None
            raw = memoryview(lease.array)[:size]
        else:
            with open(path, "rb") as f:
                f.seek(start)
                raw = f.read(size)
        try:
            pages = pt.parse_page_headers(raw, col.num_values)
        except pt.ThriftError:
            for b in staging:
                b.release()
            return None
        for p in pages:
            ok = True
            if p.page_type == pt.DATA_PAGE:
                if p.encoding not in (pt.PLAIN, pt.PLAIN_DICTIONARY,
                                      pt.RLE_DICTIONARY):
                    ok = False
                if nullable and p.def_level_encoding != pt.RLE:
                    ok = False
            elif p.page_type == pt.DATA_PAGE_V2:
                if p.encoding not in (pt.PLAIN, pt.PLAIN_DICTIONARY,
                                      pt.RLE_DICTIONARY):
                    ok = False
                if p.rep_levels_byte_length > 0:
                    ok = False                 # flat columns only
            if not ok:
                for b in staging:
                    b.release()
                return None

        dev_pages = []
        if col.compression == "SNAPPY":
            t0 = _time.perf_counter()
            total_out = sum(max(p.uncompressed_size, 0) for p in pages)
            if pool is not None:
                out_lease = pool.acquire(total_out)
                staging.append(out_lease)
                out = out_lease.array
            else:
                out = np.zeros(max(total_out, 1), np.uint8)
            new_pages = []
            tasks = []                    # (src span, out_off, expect)
            dst = 0
            for p in pages:
                usize = max(p.uncompressed_size, 0)
                np_page = replace(p, data_offset=dst, compressed_size=usize)
                new_pages.append(np_page)
                off, end = p.data_offset, p.data_offset + p.compressed_size
                if p.page_type == pt.DATA_PAGE_V2:
                    # v2 keeps levels UNCOMPRESSED ahead of the data section
                    lvl = max(p.rep_levels_byte_length, 0) \
                        + max(p.def_levels_byte_length, 0)
                    lvl = min(lvl, min(p.compressed_size, usize))
                    out[dst:dst + lvl] = np.frombuffer(
                        raw[off:off + lvl], np.uint8)
                    if p.data_compressed:
                        tasks.append((raw[off + lvl:end], dst + lvl,
                                      usize - lvl))
                    else:
                        out[dst + lvl:dst + usize] = np.frombuffer(
                            raw[off + lvl:end], np.uint8)
                elif (device_snappy and p.page_type == pt.DATA_PAGE
                      and p.encoding == pt.PLAIN and not nullable):
                    try:
                        out_len, dl, ll, sl = _parse_snappy_elements(
                            raw, off, end)
                    except pt.ThriftError:
                        tasks.append((raw[off:end], dst, usize))
                    else:
                        if out_len != usize:
                            tasks.append((raw[off:end], dst, usize))
                        else:
                            comp = np.frombuffer(raw[off:end], np.uint8)
                            # tpulint: allow[host-sync] python lists, no
                            el = [np.asarray(x, np.int32)  # device data
                                  for x in (dl, ll, sl)]
                            dev_pages.append(
                                (dst, comp, el[0], el[1], el[2], len(dl),
                                 out_len))
                else:
                    tasks.append((raw[off:end], dst, usize))
                dst += usize
            codec = _snappy_codec()
            try:
                if decomp_pool is not None and len(tasks) > 1:
                    # per-page, parallel across pages: pyarrow's snappy
                    # releases the GIL, so the prefetch pool really fans out
                    list(decomp_pool.map(
                        lambda t: _decompress_page(codec, t[0], out, t[1],
                                                   t[2]), tasks))
                else:
                    for src, ooff, expect in tasks:
                        _decompress_page(codec, src, out, ooff, expect)
            except Exception:
                for b in staging:
                    b.release()
                return None
            if metrics is not None:
                metrics.add("decompressBusySecs",
                            _time.perf_counter() - t0)
                metrics.add("decompressedBytes", total_out)
            raw = memoryview(out)[:total_out]
            pages = new_pages
        return DeviceChunk(name, col.physical_type, nullable, raw, pages,
                           col.num_values, staging=staging,
                           dev_pages=dev_pages)


def _parse_sections(c: DeviceChunk):
    """Split every data page into (def-level runs, value section).
    Returns (def_runs, plain_pages, dict_pages, dict_page) where
    def_runs: list[pt.RleRun] with ABSOLUTE out_start,
    plain_pages: [(payload_off, first_row)],
    dict_pages:  [(bit_width, runs, first_row, num_values)],
    dict_page:   PageInfo | None. Handles v1 (length-prefixed RLE def
    levels) and v2 (separate uncompressed level sections) layouts."""
    def_runs: List[pt.RleRun] = []
    plain_pages: List[Tuple[int, int]] = []
    dict_idx_pages: List[Tuple[int, List[pt.RleRun], int, int]] = []
    dict_page = None
    row = 0
    for p in c.pages:
        if p.page_type == pt.DICTIONARY_PAGE:
            dict_page = p
            continue
        if p.page_type not in (pt.DATA_PAGE, pt.DATA_PAGE_V2):
            continue
        off = p.data_offset
        end = p.data_offset + p.compressed_size
        if p.page_type == pt.DATA_PAGE_V2:
            lvl = max(p.rep_levels_byte_length, 0) \
                + max(p.def_levels_byte_length, 0)
            if c.nullable:
                if p.def_levels_byte_length > 0:
                    runs = pt.parse_hybrid_runs(
                        c.raw, off + max(p.rep_levels_byte_length, 0),
                        off + lvl, p.num_values, 1)
                    for r in runs:
                        def_runs.append(pt.RleRun(
                            row + r.out_start, r.count, r.is_packed,
                            r.value, r.byte_offset))
                else:
                    # no level section: every value present
                    def_runs.append(pt.RleRun(row, p.num_values, False,
                                              value=1))
            off += lvl
        elif c.nullable:
            # v1: [int32 LE length][RLE/bit-packed hybrid, bit width 1]
            ln = int.from_bytes(bytes(c.raw[off:off + 4]), "little")
            runs = pt.parse_hybrid_runs(c.raw, off + 4, off + 4 + ln,
                                        p.num_values, 1)
            for r in runs:
                def_runs.append(pt.RleRun(
                    row + r.out_start, r.count, r.is_packed, r.value,
                    r.byte_offset))
            off += 4 + ln
        if p.encoding == pt.PLAIN:
            plain_pages.append((off, row))
        else:                                  # dictionary indices
            bw = c.raw[off] if off < len(c.raw) else 255
            if bw > 32:
                # spec max is 32; a corrupt/hostile byte here must route
                # to the host fallback, not overflow the run tables
                raise pt.ThriftError(f"dict index bit width {bw}")
            runs = pt.parse_hybrid_runs(c.raw, off + 1, end,
                                        p.num_values, bw)
            # index runs address the PACKED (non-null) value stream;
            # out_start is patched on device via per-page valid counts
            dict_idx_pages.append((bw, runs, row, p.num_values))
        row += p.num_values
    return def_runs, plain_pages, dict_idx_pages, dict_page


def _chunk_device_bytes(c: DeviceChunk, metrics=None):
    """Upload the (reassembled) chunk bytes; patch in device-snappy
    pages. The upload keeps the staging buffer's pow2 capacity so
    shapes repeat across chunks."""
    import time as _time

    import jax.numpy as jnp

    from ..ops import parquet_decode as pd

    if c.staging:
        src = c.staging[-1].array       # full pow2 buffer: stable shape
    elif isinstance(c.raw, (bytes, bytearray, memoryview)):
        src = np.frombuffer(c.raw, np.uint8)
    else:
        src = c.raw
    t0 = _time.perf_counter()
    chunk_dev = jnp.asarray(src)
    if metrics is not None:
        # dispatch-time on async backends (docs/observability.md)
        metrics.add("uploadSecs", _time.perf_counter() - t0)
        metrics.add("uploadedBytes", int(src.nbytes))
    for (slot, comp, dl, ll, sl, n_el, out_len) in c.dev_pages:
        E = pd.bucket_len(max(n_el, 1))
        dst = np.full(E, out_len, np.int32)
        lit = np.zeros(E, np.int32)
        srcs = np.zeros(E, np.int32)
        dst[:n_el], lit[:n_el], srcs[:n_el] = dl, ll, sl
        cap_out = pd.bucket_len(max(out_len, 1), floor=128)
        kbits = max(1, (cap_out - 1).bit_length())
        page = pd.snappy_expand(
            jnp.asarray(comp), jnp.asarray(dst), jnp.asarray(lit),
            jnp.asarray(srcs), n_el, out_len, kbits, cap_out)
        chunk_dev = chunk_dev.at[slot:slot + out_len].set(
            page[:out_len])
    c.uploaded = chunk_dev
    return chunk_dev


def _dict_indices(c: DeviceChunk, valid, dict_idx_pages, cap: int):
    """Expand the per-page RLE/bit-packed index runs into ONE packed
    index stream (int32[pcap]): run out_starts are page-relative to the
    packed stream, rebased by per-page valid counts on device."""
    import jax.numpy as jnp

    from ..ops import parquet_decode as pd

    n = c.num_values
    bws = {bw for bw, _, _, _ in dict_idx_pages}
    if len(bws) != 1:
        return None                   # one static bit width per chunk
    bw = bws.pop()
    allruns: List[pt.RleRun] = []
    run_page_row = []
    for _bw, runs, row, _nv in dict_idx_pages:
        for r in runs:
            allruns.append(r)
            run_page_row.append(row)
    if not allruns:
        return None
    vcnt = jnp.cumsum(valid.astype(jnp.int32))
    R = pd.bucket_len(len(allruns))
    rs = np.zeros(R, np.int32)
    rc = np.zeros(R, np.int32)
    rp = np.zeros(R, np.int32)
    rv = np.zeros(R, np.int32)
    rb = np.zeros(R, np.int32)
    prow = np.zeros(R, np.int32)
    for i, r in enumerate(allruns):
        rs[i], rc[i], rp[i] = r.out_start, r.count, int(r.is_packed)
        rv[i], rb[i] = r.value, r.byte_offset
        prow[i] = run_page_row[i]
    prow_dev = jnp.asarray(prow)
    page_val_base = jnp.where(
        prow_dev > 0,
        vcnt[jnp.clip(prow_dev - 1, 0, cap - 1)], 0)
    rs_abs = jnp.asarray(rs) + page_val_base
    # pad rows past the live runs to the sentinel (total packed)
    total_packed = vcnt[jnp.clip(jnp.asarray(n - 1), 0, cap - 1)]
    live = jnp.arange(R) < len(allruns)
    rs_abs = jnp.where(live, rs_abs, total_packed).astype(jnp.int32)
    chunk_dev = c.uploaded
    idx = pd.expand_hybrid(
        chunk_dev, rs_abs, jnp.asarray(rc), jnp.asarray(rp),
        jnp.asarray(rv), jnp.asarray(rb), len(allruns), n, bw,
        pd.bucket_len(max(n, 1), floor=128))
    return idx


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


def _decode_strings(c: DeviceChunk, valid, cap: int, plain_pages,
                    dict_idx_pages, dict_page):
    """BYTE_ARRAY decode: per-row extents (length extraction) ->
    exclusive prefix-sum offsets -> byte gather into the chunked
    string layout. Returns (data uint8[dcap], validity, offsets) or
    None (fallback)."""
    import jax.numpy as jnp

    from ..ops import parquet_decode as pd

    n = c.num_values
    if plain_pages:
        payload_total = sum(
            p.compressed_size for p in c.pages
            if p.page_type in (pt.DATA_PAGE, pt.DATA_PAGE_V2))
        if payload_total > _MAX_STRING_BYTES:
            return None
        dcap = pd.bucket_len(max(payload_total, 1), floor=128)
        P = pd.bucket_len(len(plain_pages))
        po = np.zeros(P, np.int32)
        pr = np.full(P, n, np.int32)
        maxv = 1
        for i, (off, row) in enumerate(plain_pages):
            po[i], pr[i] = off, row
        for p in c.pages:
            if p.page_type in (pt.DATA_PAGE, pt.DATA_PAGE_V2):
                maxv = max(maxv, p.num_values)
        chunk_dev = c.uploaded
        if c.nullable:
            vcnt = jnp.cumsum(valid.astype(jnp.int32))
            pr_dev = jnp.asarray(pr)
            prev_row = jnp.clip(pr_dev - 1, 0, cap - 1)
            first_val = jnp.where(pr_dev > 0, vcnt[prev_row], 0) \
                .astype(jnp.int32)
            total_packed = vcnt[jnp.clip(jnp.asarray(n - 1), 0,
                                         cap - 1)]
        else:
            first_val = jnp.asarray(pr)
            total_packed = jnp.asarray(n, jnp.int32)
        kbits = max(1, (max(maxv - 1, 1)).bit_length())
        pcap = pd.bucket_len(max(n, 1), floor=128)
        starts, lens = pd.byte_array_index(
            chunk_dev, jnp.asarray(po), first_val, len(plain_pages),
            total_packed, kbits, pcap)
        row_start, row_len = pd.rows_from_packed(
            starts, lens, valid, n, cap)
    elif dict_idx_pages:
        if dict_page is None:
            return None
        ndict = dict_page.num_values
        if ndict > _MAX_DICT_VALUES:
            return None
        try:
            dstarts, dlens = _walk_byte_array_extents(
                c.raw, dict_page.data_offset,
                dict_page.data_offset + dict_page.compressed_size,
                ndict)
        except pt.ThriftError:
            return None
        max_len = int(dlens.max()) if ndict else 0
        bound = max(n, 1) * max(max_len, 1)
        if bound > _MAX_STRING_BYTES:
            return None
        dcap = pd.bucket_len(max(bound, 1), floor=128)
        idx = _dict_indices(c, valid, dict_idx_pages, cap)
        if idx is None:
            return None
        D = pd.bucket_len(max(ndict, 1))
        ds = np.zeros(D, np.int32)
        dl = np.zeros(D, np.int32)
        ds[:ndict], dl[:ndict] = dstarts, dlens
        row_start, row_len = pd.dict_rows(
            idx, jnp.asarray(ds), jnp.asarray(dl), valid, n, cap)
    else:
        return None
    data, offsets = pd.assemble_strings(
        c.uploaded, row_start, row_len, n, cap, dcap)
    new_valid = valid & (jnp.arange(cap) < n)
    return data, new_valid, offsets


def decode_chunk_device(c: DeviceChunk, cap: int, metrics=None):
    """Decode one chunk at capacity `cap`. Fixed-width chunks return
    (device values, device validity); BYTE_ARRAY chunks return
    (data bytes, validity, offsets). Returns None when a page shape
    defeats the slice (caller falls back to host decode)."""
    import jax.numpy as jnp

    from ..ops import parquet_decode as pd

    with tracing.span("io.decode", "io"):
        try:
            def_runs, plain_pages, dict_idx_pages, dict_page = \
                _parse_sections(c)
        except pt.ThriftError:
            return None                   # malformed page section: fallback
        if plain_pages and dict_idx_pages:
            return None                   # mixed-encoding chunk: fallback
        with tracing.span("io.upload", "io"):
            chunk_dev = _chunk_device_bytes(c, metrics)
        n = c.num_values

        # -- def levels -> validity + per-page non-null counts -------------
        if c.nullable and def_runs:
            R = pd.bucket_len(len(def_runs))
            rs = np.full(R, n, np.int32)
            rc = np.zeros(R, np.int32)
            rp = np.zeros(R, np.int32)
            rv = np.zeros(R, np.int32)
            rb = np.zeros(R, np.int32)
            for i, r in enumerate(def_runs):
                rs[i], rc[i], rp[i] = r.out_start, r.count, int(r.is_packed)
                rv[i], rb[i] = r.value, r.byte_offset
            def_levels = pd.expand_hybrid(
                chunk_dev, jnp.asarray(rs), jnp.asarray(rc),
                jnp.asarray(rp), jnp.asarray(rv), jnp.asarray(rb),
                len(def_runs), n, 1, cap)
            valid = def_levels == 1
        else:
            i = jnp.arange(cap, dtype=jnp.int32)
            valid = i < n
            def_levels = valid.astype(jnp.int32)

        if c.physical == "BYTE_ARRAY":
            return _decode_strings(c, valid, cap, plain_pages,
                                   dict_idx_pages, dict_page)

        width = _PHYS_WIDTH[c.physical]
        np_name = _PHYS_NP[c.physical]

        # -- packed value stream -------------------------------------------
        if plain_pages:
            P = pd.bucket_len(len(plain_pages))
            po = np.zeros(P, np.int32)
            pr = np.full(P, n, np.int32)      # first ROW of page (sentinel n)
            for i, (off, row) in enumerate(plain_pages):
                po[i], pr[i] = off, row
            if c.nullable:
                # PLAIN stores non-null values only: first VALUE index of
                # each page = count of valid rows before the page (device)
                vcnt = jnp.cumsum(valid.astype(jnp.int32))
                pr_dev = jnp.asarray(pr)
                prev_row = jnp.clip(pr_dev - 1, 0, cap - 1)
                first_val = jnp.where(pr_dev > 0, vcnt[prev_row], 0) \
                    .astype(jnp.int32)
            else:
                first_val = jnp.asarray(pr)
            packed = pd.decode_plain_fixed(
                chunk_dev, jnp.asarray(po), first_val,
                len(plain_pages), n, width, cap)
        elif dict_idx_pages:
            if dict_page is None:
                return None
            ndict = dict_page.num_values
            dcap = pd.bucket_len(max(ndict, 1), floor=128)
            d_po = np.zeros(8, np.int32)
            d_pr = np.full(8, ndict, np.int32)
            d_po[0], d_pr[0] = dict_page.data_offset, 0
            dict_words = pd.decode_plain_fixed(
                chunk_dev, jnp.asarray(d_po), jnp.asarray(d_pr), 1,
                ndict, width, dcap)
            idx = _dict_indices(c, valid, dict_idx_pages, cap)
            if idx is None:
                return None
            packed = dict_words[jnp.clip(idx, 0, dcap - 1)]
        else:
            return None

        if c.nullable:
            words, valid = pd.apply_def_levels(def_levels, packed, 1, n, cap)
        else:
            words = packed[:cap] if packed.shape[0] >= cap else jnp.pad(
                packed, (0, cap - packed.shape[0]))
            words = jnp.where(valid, words, 0)
        vals = pd.words_to_device(words, np_name)
        return vals, valid
