"""Columnar shuffle wire format — the kudo analog.

(reference: jni kudo.KudoSerializer + GpuColumnarBatchSerializer.scala.)
A flat, length-prefixed binary layout per sub-batch: little-endian header,
then per column validity/data(/offsets) raw buffers. No compression by
default (nvcomp analog is a conf'd host codec). Written/read with numpy
memoryviews — zero object overhead, mmap-friendly.

Layout:
  u32 magic 'KTPU' | u32 n_cols | u64 n_rows
  per column: u8 has_offsets | u8 n_children | u64 validity_bytes |
              u64 data_bytes | u64 offsets_bytes | buffers...
  then per child: u64 child_n_rows | recursive column block
(nested columns — list offsets + element child, struct field children —
serialize as recursive column blocks, the kudo nested-column analog.)
"""
from __future__ import annotations

import io
import struct
from typing import BinaryIO, Dict, List, Optional, Tuple

import numpy as np

from ..profiler import tracing
from ..utils.native import pack_validity, unpack_validity

__all__ = ["write_subbatch", "read_subbatch", "HostSubBatch", "wire_spec",
           "cv_shuffle_bufs", "slice_host_col"]


def cv_shuffle_bufs(cv) -> Dict:
    """Device buffer tree of a (possibly nested) CV for the map-side bulk
    D2H fetch."""
    d = {"validity": cv.validity}
    if cv.offsets is not None:
        d["offsets"] = cv.offsets
    if cv.children:
        d["children"] = [cv_shuffle_bufs(c) for c in cv.children]
    else:
        d["data"] = cv.data
    return d


def slice_host_col(cb: Dict, lo: int, hi: int) -> Dict:
    """Slice fetched host buffers to rows [lo, hi), rebasing offsets to 0
    and recursively slicing list element ranges / struct children.
    Assumes dense offsets (map-side columns come out of a compacting
    gather, which rebuilds them dense)."""
    out = {"validity": np.asarray(cb["validity"])[lo:hi]}
    if "offsets" in cb:
        off = np.asarray(cb["offsets"])
        o = off[lo:hi + 1].astype(np.int32)
        base = int(o[0]) if len(o) else 0
        out["offsets"] = o - base
        end = int(o[-1]) if len(o) else 0
        if "children" in cb:
            kid = slice_host_col(cb["children"][0], base, end)
            kid["_n"] = np.int64(end - base)
            out["children"] = [kid]
        else:
            out["data"] = np.asarray(cb["data"])[base:end]
    elif "children" in cb:
        kids = []
        for c in cb["children"]:
            kid = slice_host_col(c, lo, hi)
            kid["_n"] = np.int64(hi - lo)
            kids.append(kid)
        out["children"] = kids
    else:
        out["data"] = np.asarray(cb["data"])[lo:hi]
    return out

_MAGIC = 0x4B545056  # v2: validity bit order is LSB-first


class HostSubBatch:
    """Host-side compacted rows of one shuffle partition: per column a
    dict with 'validity', 'data', and optionally 'offsets' (rebased to 0)."""

    def __init__(self, cols: List[Dict[str, np.ndarray]], n_rows: int):
        self.cols = cols
        self.n_rows = n_rows

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for c in self.cols for b in c.values())


def _write_col(body: io.BytesIO, c: Dict[str, np.ndarray]):
    off = c.get("offsets")
    kids = c.get("children", [])
    validity = pack_validity(c["validity"])
    data = (np.ascontiguousarray(c["data"]) if "data" in c
            else np.zeros(0, np.uint8))
    body.write(struct.pack("<BBQQQ", 1 if off is not None else 0,
                           len(kids), validity.nbytes, data.nbytes,
                           off.nbytes if off is not None else 0))
    body.write(validity.tobytes())
    body.write(data.tobytes())
    if off is not None:
        body.write(np.ascontiguousarray(off).tobytes())
    for k in kids:
        body.write(struct.pack("<Q", int(k["_n"])))
        _write_col(body, k)


def write_subbatch(out: BinaryIO, sb: HostSubBatch, codec=None) -> int:
    """Write one length-prefixed block; returns the serialized bytes
    before the codec (what `out` receives is 8 + the codec's output)."""
    body = io.BytesIO()
    body.write(struct.pack("<IIQ", _MAGIC, len(sb.cols), sb.n_rows))
    for c in sb.cols:
        _write_col(body, c)
    raw = body.getvalue()
    n_raw = len(raw)
    if codec is not None:
        with tracing.span("shuffle.compress", "op", bytes=n_raw):
            raw = codec.compress(raw)
    out.write(struct.pack("<Q", len(raw)))
    out.write(raw)
    return n_raw


def wire_spec(dtype) -> Dict:
    """Per-column wire layout derived from the SQL type:
    {"np": numpy dtype, "items": fixed items/row, "var": has offsets,
     "nested": bool, "children": [spec...]}."""
    from ..columnar import dtypes as dt
    if isinstance(dtype, (dt.ArrayType, dt.MapType)):
        from ..columnar.column import Column
        return {"np": np.dtype(np.uint8), "items": 1, "var": True,
                "nested": True,
                "children": [wire_spec(Column.element_dtype(dtype))]}
    if isinstance(dtype, dt.StructType):
        return {"np": np.dtype(np.uint8), "items": 1, "var": False,
                "nested": True,
                "children": [wire_spec(f.dtype) for f in dtype.fields]}
    items = 2 if (isinstance(dtype, dt.DecimalType)
                  and dtype.is_decimal128) else 1
    return {"np": dtype.np_dtype or np.dtype(np.int8), "items": items,
            "var": dtype.is_variable_width, "nested": False,
            "children": []}


def _read_col(buf, pos: int, n_rows: int, spec: Dict):
    if pos + 26 > len(buf):
        raise IOError("corrupt shuffle block: truncated column header")
    has_off, n_kids, vb, db, ob = struct.unpack_from("<BBQQQ", buf, pos)
    pos += 26
    if n_kids != len(spec["children"]):
        raise IOError(f"corrupt shuffle block: {n_kids} children, "
                      f"expected {len(spec['children'])}")
    if pos + vb + db + (ob if has_off else 0) > len(buf):
        raise IOError("corrupt shuffle block: buffer lengths exceed "
                      "block size")
    if vb * 8 < n_rows:
        raise IOError("corrupt shuffle block: validity buffer shorter "
                      f"than {n_rows} rows")
    item = spec["np"].itemsize
    if not has_off and not spec["nested"] and \
            (db % item or db // item < n_rows * spec["items"]):
        raise IOError(f"corrupt shuffle block: data buffer {db}B for "
                      f"{n_rows} rows of {spec['np']}")
    if has_off and ob < 4 * (n_rows + 1):
        raise IOError(f"corrupt shuffle block: offsets buffer {ob}B "
                      f"for {n_rows} rows")
    vbits = np.frombuffer(buf, np.uint8, vb, pos)
    pos += vb
    validity = unpack_validity(vbits, n_rows)
    col = {"validity": validity}
    if not spec["nested"]:
        data = np.frombuffer(buf, spec["np"], db // item, pos)
        if spec["items"] > 1 and not has_off:
            if data.shape[0] != n_rows * spec["items"]:
                raise IOError("corrupt shuffle block: limb count mismatch")
            data = data.reshape(n_rows, spec["items"])
        col["data"] = data
    pos += db
    if has_off:
        col["offsets"] = np.frombuffer(buf, np.int32, ob // 4, pos)
        pos += ob
    kids = []
    for ks in spec["children"]:
        if pos + 8 > len(buf):
            raise IOError("corrupt shuffle block: truncated child header")
        (child_n,) = struct.unpack_from("<Q", buf, pos)
        pos += 8
        kc, pos = _read_col(buf, pos, child_n, ks)
        kc["_n"] = np.int64(child_n)
        kids.append(kc)
    if kids:
        col["children"] = kids
    return col, pos


def read_subbatch(inp: BinaryIO, specs, codec=None) -> \
        Optional[HostSubBatch]:
    """specs: per-column wire_spec trees."""
    hdr = inp.read(8)
    if len(hdr) < 8:
        return None
    (blen,) = struct.unpack("<Q", hdr)
    with tracing.span("shuffle.decode", "op", bytes=blen):
        return _decode_block(inp, blen, specs, codec)


def _decode_block(inp: BinaryIO, blen: int, specs, codec) -> HostSubBatch:
    raw = inp.read(blen)
    if len(raw) < blen:
        raise IOError(f"truncated shuffle block: {len(raw)}/{blen} bytes")
    if codec is not None:
        with tracing.span("shuffle.decompress", "op", bytes=blen):
            raw = codec.decompress(raw)
    buf = memoryview(raw)
    if len(buf) < 16:
        raise IOError("corrupt shuffle block: short header")
    magic, n_cols, n_rows = struct.unpack_from("<IIQ", buf, 0)
    if magic != _MAGIC:
        raise IOError(f"corrupt shuffle block: bad magic {magic:#x}")
    if n_cols != len(specs):
        raise IOError(f"corrupt shuffle block: {n_cols} columns, "
                      f"expected {len(specs)}")
    pos = 16
    cols = []
    for ci in range(n_cols):
        col, pos = _read_col(buf, pos, n_rows, specs[ci])
        cols.append(col)
    return HostSubBatch(cols, n_rows)
