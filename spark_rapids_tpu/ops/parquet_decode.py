"""Device-side Parquet decode kernels.

The reference decodes column chunks on the accelerator
(GpuParquetScan.scala:3364 Table.readParquet; chunked readers :2523,
:3134). TPU shape of the same idea: the host reads the chunk, parses
page headers and run headers (io/parquet_thrift.py, O(pages + runs)) and
lays the value bytes out for the device (io/parquet_device.py):

  - PLAIN fixed-width values and the dictionary page's values as one
    contiguous byte run each, so a value is a row of a [n, width] byte
    matrix: a reshape and shifts, little-endian for INT32/INT64/FLOAT/
    DOUBLE and big-endian two's complement, sign-extended, for a
    FIXED_LEN_BYTE_ARRAY decimal;
  - the bit-packed runs of an RLE/bit-packed hybrid stream (dictionary
    indices, definition levels) gathered by bit width, so a group of
    eight values is a row of a [groups, bit_width] byte matrix
    (`pq_unpack`, one launch a width, the width as data: a chunk's index
    pages may each carry their own);
  - a table of pieces that places them: `count` values from `src` of
    the unpacked values (or an RLE value) at `dst` of the stream. The
    same table stitches a chunk whose dictionary pages give way to
    PLAIN pages.

A stream of at most DENSE_PIECES pieces is stitched with one dynamic
slice a piece. A per-element gather is left only where the data asks for
one: a dictionary past SMALL_DICT entries, a stream of many pieces (real
nulls), strings. Every program here is named `pq_*`, so the device's
trace shows a chunk's decode as `jit_pq_*` and nothing else.

Shapes are static per bucket (pow2 lengths); the bytes, offsets and
counts are data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .gather import row_of_unit

__all__ = ["bucket_len", "pq_zeros", "pq_unpack", "pq_fixed",
           "pq_strings", "pq_snappy_expand", "pq_patch", "pq_values",
           "SMALL_DICT", "DENSE_PIECES", "ROW_BYTES"]

# a dictionary of at most this many entries is looked up by
# compare-select over its entries, not by a gather an element
SMALL_DICT = 64
# a piece table up to this length is stitched by dynamic slices
DENSE_PIECES = 16
# bytes of a row of bit-packed payload: 16 groups of eight values at the
# widest bit width, 32
ROW_BYTES = 512


def bucket_len(n: int, floor: int = 8) -> int:
    """Pow2 bucket for table and buffer lengths so jit shapes repeat
    across chunks."""
    c = floor
    while c < n:
        c <<= 1
    return c


# ----------------------------------------------------------------------
# traced helpers (no program of their own)
# ----------------------------------------------------------------------
def _sext32(w, bits: int):
    """Bitcast uint32 `w` to int32, sign-extending its low `bits`."""
    sh = 32 - bits
    if sh == 0:
        return lax.bitcast_convert_type(w, jnp.int32)
    return lax.bitcast_convert_type(w << sh, jnp.int32) >> sh


def _select(x, sel):
    """Rows of byte matrix `x` times a 0/1/256 selection matrix on the
    MXU: each output is one byte, or two bytes as a 16-bit chunk, picked
    out of its row; bf16 holds a byte exactly and f32 accumulates the
    sum of two exactly. This moves bytes between lanes (the de-interleave
    a [values, width] byte layout needs) with no gather and no lane-
    padded [n, width] array. Returns uint32 columns."""
    y = jnp.dot(x.astype(jnp.bfloat16), jnp.asarray(sel, jnp.bfloat16),
                preferred_element_type=jnp.float32)
    return y.astype(jnp.int32).astype(jnp.uint32)


@functools.lru_cache(maxsize=None)
def _be_matrix(width: int) -> np.ndarray:
    """[128 * width bytes, 128 * chunks]: lane t of a row of 128
    big-endian `width`-byte values gets its value as 16-bit chunks,
    least significant first."""
    chunks = (width + 1) // 2
    sel = np.zeros((128 * width, 128 * chunks), np.float32)
    for t in range(128):
        for e in range(width):           # e: byte significance, 0 lowest
            sel[t * width + width - 1 - e, (e // 2) * 128 + t] = \
                256.0 if e % 2 else 1.0
    return sel


def _be_words(x, width: int):
    """uint8[R, 128 * width] big-endian two's-complement integers of
    `width` bytes -> R * 128 values, sign-extended: int32 for width <= 4,
    else int64 (a FIXED_LEN_BYTE_ARRAY decimal)."""
    c = _select(x, _be_matrix(width))
    c = [c[:, k * 128:(k + 1) * 128] for k in range(c.shape[1] // 128)]
    lo = c[0] | (c[1] << 16) if len(c) > 1 else c[0]
    if width <= 4:
        return _sext32(lo, 8 * width).reshape(-1)
    hi = c[2] | (c[3] << 16) if len(c) > 3 else c[2]
    hi = _sext32(hi, 8 * (width - 4))
    return ((hi.astype(jnp.int64) << 32)
            | lo.astype(jnp.int64)).reshape(-1)


def _expand(src_vals, pieces, cap: int, dense: bool):
    """A stream of `cap` values from a piece table (int32[4, K]: dst,
    cnt, src, bp; and its live length): piece j puts
    cnt[j] values at dst[j], read from src_vals[src[j]:] where bp[j],
    else the value src[j] (an RLE run). Positions no piece covers hold
    garbage; the caller masks past the stream's end."""
    table, npc = pieces
    dst, cnt, src, bp = table[0], table[1], table[2], table[3]
    i = jnp.arange(cap, dtype=jnp.int32)
    if dense:
        z = jnp.zeros(cap, src_vals.dtype)
        padded = jnp.concatenate([z, src_vals, z])

        def body(j, out):
            shifted = lax.dynamic_slice(padded, (src[j] - dst[j] + cap,),
                                        (cap,))
            val = jnp.where(bp[j] == 1, shifted,
                            src[j].astype(src_vals.dtype))
            hit = (i >= dst[j]) & (i < dst[j] + cnt[j])
            return jnp.where(hit, val, out)
        return lax.fori_loop(0, npc, body, z)
    pid = row_of_unit(dst, dst.shape[0], cap)
    pid = jnp.minimum(pid, jnp.maximum(npc - 1, 0))
    at = jnp.clip(src[pid] + (i - dst[pid]), 0, src_vals.shape[0] - 1)
    return jnp.where(bp[pid] == 1, src_vals[at],
                     src[pid].astype(src_vals.dtype))


def _lookup(table, idx):
    """table[idx]: compare-select over a small table's entries, else a
    gather (idx clipped into the table)."""
    d = table.shape[0]
    if d <= SMALL_DICT:
        k = jnp.arange(d, dtype=jnp.int32)
        hit = idx[:, None] == k[None, :]
        return jnp.sum(jnp.where(hit, table[None, :], 0), axis=1,
                       dtype=table.dtype)
    return jnp.take(table, jnp.clip(idx, 0, d - 1), axis=0)


def _rows(packed, def_src, def_pieces, n, cap: int, nulls: bool,
          dense_def: bool):
    """Packed (non-null) value arrays -> row positions (0 where null),
    and the validity."""
    i = jnp.arange(cap, dtype=jnp.int32)
    if not nulls:
        valid = i < n
        return [jnp.where(valid, p, 0) for p in packed], valid
    lvl = _expand(def_src, def_pieces, cap, dense_def)
    valid = (lvl == 1) & (i < n)
    vidx = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0, cap - 1)
    return [jnp.where(valid, p[vidx], 0) for p in packed], valid


def _le32_at(chunk, pos):
    """Little-endian uint32 read at arbitrary byte positions (gather of
    four lanes; out-of-range positions clip and yield garbage the
    caller masks)."""
    nb = chunk.shape[0]
    w = jnp.zeros(pos.shape, jnp.int32)
    for b in range(4):
        byte = chunk[jnp.clip(pos + b, 0, nb - 1)].astype(jnp.int32)
        w = w | (byte << (8 * b))
    return w


def _byte_array_index(chunk, page_payload_off, page_first_val, n_pages,
                      total, kbits: int, cap: int):
    """Locate every value of PLAIN BYTE_ARRAY pages: (byte_start,
    byte_len) int32[cap] into `chunk`, in the order of the pages.

    The [uint32 len][bytes] stream is a linked list (each length tells
    where the next one starts), so value positions are found by pointer
    doubling: a jump table next[b] = b + 4 + le32(b) over every byte
    position, squared kbits times; value i applies the 2^k jump for
    each set bit of its within-page ordinal. `kbits` must cover the max
    per-page value count; page_first_val rows past n_pages carry the
    sentinel `total`."""
    i = jnp.arange(cap, dtype=jnp.int32)
    pg = row_of_unit(page_first_val, page_payload_off.shape[0], cap)
    pg = jnp.minimum(pg, jnp.maximum(n_pages - 1, 0))
    k = jnp.maximum(i - page_first_val[pg], 0)
    pos = page_payload_off[pg]
    nb = chunk.shape[0]
    b = jnp.arange(nb, dtype=jnp.int32)
    nxt = jnp.clip(b + 4 + _le32_at(chunk, b), 0, nb - 1) \
        .astype(jnp.int32)
    for bit in range(kbits):
        take = ((k >> bit) & 1).astype(jnp.bool_)
        pos = jnp.where(take, nxt[jnp.clip(pos, 0, nb - 1)], pos)
        if bit != kbits - 1:
            nxt = nxt[nxt]
    live = i < total
    lens = jnp.clip(_le32_at(chunk, pos), 0, nb)
    return (jnp.where(live, pos + 4, 0).astype(jnp.int32),
            jnp.where(live, lens, 0).astype(jnp.int32))


def _assemble_strings(chunk, row_start, row_len, total, cap: int,
                      dcap: int):
    """Gather per-row byte ranges of `chunk` into the engine's chunked
    string layout: (data uint8[dcap], offsets int32[cap+1]). Offsets
    come from an exclusive prefix sum of the (null-masked) lengths;
    bytes move via the scatter+cummax byte->row ownership map."""
    i = jnp.arange(cap, dtype=jnp.int32)
    row_len = jnp.where(i < total, jnp.maximum(row_len, 0), 0)
    off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(row_len).astype(jnp.int32)])
    rob = row_of_unit(off, cap, dcap)
    pos = jnp.arange(dcap, dtype=jnp.int32)
    src = row_start[rob] + (pos - off[rob])
    nb = chunk.shape[0]
    data = chunk[jnp.clip(src, 0, nb - 1)]
    data = jnp.where(pos < off[cap], data, 0).astype(jnp.uint8)
    return data, off


# ----------------------------------------------------------------------
# programs
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cap",))
def pq_zeros(cap: int):
    """The int32[cap] buffer `pq_unpack` writes a stream's groups into."""
    return jnp.zeros(cap, jnp.int32)


@jax.jit
def pq_unpack(buf, payload, off, bw):
    """Unpack bit-packed groups of eight `bw`-bit values (LSB first, as
    the hybrid encoding packs them) and write them into int32 `buf` at
    `off`. payload: uint8[rows, ROW_BYTES], a row the 16 * bw bytes of 16
    groups (128 values) and zeros after. `bw` (1..32) is data, so one
    program serves every width: the selection matrix that brings each
    value's bytes to its lane (`_select`) is built here from it, then a
    shift and a mask. No gather, and the values come out in stream
    order."""
    t = jnp.arange(128, dtype=jnp.int32)
    s = (t * bw) & 7                                  # bit offset in byte
    first = (t * bw) >> 3                             # first byte
    rel = jnp.arange(ROW_BYTES, dtype=jnp.int32)[:, None] - first[None, :]
    used = (rel >= 0) & (rel < (s + bw + 7) // 8)     # bytes of value t
    sel = jnp.concatenate(
        [jnp.where(used & (rel // 2 == k), jnp.where(rel % 2 == 1, 256.0,
                                                     1.0), 0.0)
         for k in range(3)], axis=1)
    c = _select(payload, sel)
    su = s.astype(jnp.uint32)
    v = (c[:, :128] | (c[:, 128:256] << 16)) >> su
    v = v | jnp.where(su > 0, c[:, 256:] << (32 - su), 0)
    v = v & (jnp.full((), 0xFFFFFFFF, jnp.uint32)
             >> (32 - bw).astype(jnp.uint32))
    return lax.dynamic_update_slice(
        buf, lax.bitcast_convert_type(v.reshape(-1), jnp.int32), (off,))


@functools.partial(jax.jit, static_argnames=(
    "kind", "width", "dict_len", "dtype", "cap", "merge", "nulls",
    "dense_idx", "dense_seg", "dense_def"))
def pq_fixed(plain, dict_raw, idx_src, idx_pieces, seg_pieces, def_src,
             def_pieces, n, kind: str, width: int, dict_len: int, dtype,
             cap: int,
             merge: bool, nulls: bool, dense_idx: bool, dense_seg: bool,
             dense_def: bool):
    """Decode one fixed-width chunk to (values[cap], validity[cap]).

    plain: the PLAIN values of the chunk's PLAIN pages, contiguous, `cap`
    long (None without such pages); dict_raw: the dictionary's values (None without
    dictionary pages). Both as int32/int64 words of the little-endian
    types (a float's bits), or for a FIXED_LEN_BYTE_ARRAY decimal as its
    bytes, uint8[rows, 128 * width]; dict_len: the dictionary's
    bucketed length; idx_src + idx_pieces: the index stream of
    the dictionary pages in their order; seg_pieces (with `merge`): the
    packed stream's pages, from the looked-up dictionary values
    (src < cap) or the PLAIN values (src - cap); def_src + def_pieces
    (with `nulls`): the definition levels. kind is the physical type
    (FIXED_LEN_BYTE_ARRAY: a big-endian decimal of `width` bytes); the
    values come out as numpy dtype name `dtype` (None: the type's own)."""
    be = kind == "FIXED_LEN_BYTE_ARRAY"
    parts = []
    if dict_raw is not None:
        table = (_be_words(dict_raw, width) if be else dict_raw)[:dict_len]
        idx = _expand(idx_src, idx_pieces, cap, dense_idx)
        parts.append(_lookup(table, idx))
    if plain is not None:
        vals = _be_words(plain, width) if be else plain
        parts.append(vals)
    if merge:
        packed = _expand(jnp.concatenate(parts), seg_pieces, cap,
                         dense_seg)
    else:
        packed = parts[0]
    (words,), valid = _rows((packed,), def_src, def_pieces, n, cap, nulls,
                            dense_def)
    if kind == "FLOAT":
        words = lax.bitcast_convert_type(words, jnp.float32)
    elif kind == "DOUBLE":
        words = lax.bitcast_convert_type(words, jnp.float64)
    elif be:
        words = words.astype(jnp.int64)
    return (words if dtype is None else words.astype(dtype)), valid


@functools.partial(jax.jit, static_argnames=(
    "cap", "dcap", "kbits", "merge", "nulls", "dense_idx", "dense_seg",
    "dense_def"))
def pq_strings(chunk, dstart, dlen, idx_src, idx_pieces, plain_off,
               plain_first, n_plain_pages, n_plain, seg_pieces, def_src,
               def_pieces, n, cap: int, dcap: int, kbits: int,
               merge: bool, nulls: bool, dense_idx: bool,
               dense_seg: bool, dense_def: bool):
    """Decode one BYTE_ARRAY chunk to (data uint8[dcap], validity[cap],
    offsets int32[cap+1]). Each packed value is a (start, len) extent
    into `chunk`: a dictionary entry's (dstart/dlen, None without
    dictionary pages) by the index stream, or a PLAIN value's found by
    pointer doubling over its page (plain_off None without PLAIN pages);
    seg_pieces stitch the two as in pq_fixed."""
    starts, lens = [], []
    if dstart is not None:
        idx = _expand(idx_src, idx_pieces, cap, dense_idx)
        starts.append(_lookup(dstart, idx))
        lens.append(_lookup(dlen, idx))
    if plain_off is not None:
        s, ln = _byte_array_index(chunk, plain_off, plain_first,
                                  n_plain_pages, n_plain, kbits, cap)
        starts.append(s)
        lens.append(ln)
    if merge:
        start = _expand(jnp.concatenate(starts), seg_pieces, cap,
                        dense_seg)
        length = _expand(jnp.concatenate(lens), seg_pieces, cap,
                         dense_seg)
    else:
        start, length = starts[0], lens[0]
    (start, length), valid = _rows((start, length), def_src, def_pieces, n,
                                   cap, nulls, dense_def)
    data, offsets = _assemble_strings(chunk, start, length, n, cap, dcap)
    return data, valid, offsets


@functools.partial(jax.jit, static_argnames=("kbits", "cap"))
def pq_snappy_expand(comp, el_dst, el_lit, el_src, n_el, out_len,
                     kbits: int, cap: int):
    """Device snappy decompression of ONE page from its host-parsed
    element table (the nvcomp-snappy analog; conf
    `sql.parquet.deviceSnappy`).

    Each output byte first maps to its owning element (scatter+cummax).
    Literal bytes resolve directly to a compressed-buffer position
    (encoded as -(pos+1)); copy bytes point at an EARLIER output byte
    (i - back_offset — overlapping copies included, since the target is
    always strictly earlier). kbits pointer-doubling rounds
    (src = src[src]) then resolve every byte to a literal source, and
    one gather materializes the page. el_dst rows past n_el must carry
    the sentinel out_len."""
    i = jnp.arange(cap, dtype=jnp.int32)
    eid = row_of_unit(el_dst, el_dst.shape[0], cap)
    eid = jnp.minimum(eid, jnp.maximum(n_el - 1, 0))
    within = i - el_dst[eid]
    lit = el_lit[eid].astype(jnp.bool_)
    src = jnp.where(lit, -(el_src[eid] + within) - 1, i - el_src[eid])
    for _ in range(kbits):
        t = jnp.clip(src, 0, cap - 1)
        src = jnp.where(src >= 0, src[t], src)
    nb = comp.shape[0]
    out = comp[jnp.clip(-src - 1, 0, nb - 1)]
    return jnp.where(i < out_len, out, 0).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("width", "flba"))
def pq_values(buf, width: int, flba: bool):
    """uint8 values patched on the device -> the layout pq_fixed reads
    (rows of 128 values for a FIXED_LEN_BYTE_ARRAY decimal, else
    int32/int64 words)."""
    if flba:
        return buf.reshape(-1, 128 * width)
    return lax.bitcast_convert_type(
        buf.reshape(-1, width), jnp.int32 if width == 4 else jnp.int64)


@jax.jit
def pq_patch(buf, page, slot, length):
    """buf with buf[slot:slot+length] = page[:length] (a page the device
    decompressed, put where the host left its bytes out)."""
    nb = buf.shape[0]
    i = jnp.arange(nb, dtype=jnp.int32)
    z = jnp.zeros(nb, buf.dtype)
    shifted = lax.dynamic_slice(jnp.concatenate([z, page, z]),
                                (nb - slot,), (nb,))
    return jnp.where((i >= slot) & (i < slot + length), shifted, buf)
