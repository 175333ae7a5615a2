#!/usr/bin/env python
"""Check the device Parquet decode against pyarrow's, chunk by chunk.

  python tools/pq_device_check.py FILE.parquet [FILE ...] [--columns a,b]

Every column chunk of the named columns (all, without --columns) goes
through the scan's device path (io/parquet_device.py: chunk_device_plan
-> decode_chunk_device) and is compared with pyarrow.parquet's decode
of the same row group, bit for bit: the validity, and every valid
value (a decimal as its unscaled integer, a float as its bits, a string
as its bytes). A chunk the device path leaves to the host counts as a
fallback. Prints one JSON line a file and a last line of totals; exits
1 on any difference or fallback.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..")))


def _want(col):
    """(values as numpy, validity) of a pyarrow ChunkedArray."""
    import pyarrow as pa
    arr = col.combine_chunks()
    valid = ~np.asarray(arr.is_null().to_numpy(zero_copy_only=False))
    t = arr.type
    if pa.types.is_decimal(t):
        words = np.frombuffer(arr.buffers()[1], np.int64)
        vals = words[2 * arr.offset:2 * (arr.offset + len(arr)):2]
    elif pa.types.is_string(t) or pa.types.is_binary(t):
        vals = [v.as_py() for v in arr]
        vals = [v.encode() if isinstance(v, str) else v for v in vals]
    else:
        if pa.types.is_date32(t):
            arr = arr.cast(pa.int32())
        vals = np.asarray(arr.fill_null(0).to_numpy(zero_copy_only=False))
    return vals, valid


def _same(got, want_vals, want_valid, nrows):
    """Number of rows whose validity or valid value differs."""
    if len(got) == 3:
        data, valid, offsets = (np.asarray(x) for x in got)
        valid = valid[:nrows]
        bad = int((valid != want_valid).sum())
        for r in np.flatnonzero(valid & want_valid):
            if bytes(data[offsets[r]:offsets[r + 1]]) != want_vals[r]:
                bad += 1
        return bad
    vals, valid = (np.asarray(x)[:nrows] for x in got)
    want_vals = np.asarray(want_vals)
    if vals.dtype.kind == "f":
        vals = vals.view(np.int32 if vals.itemsize == 4 else np.int64)
        want_vals = want_vals.astype(vals.dtype.str.replace("i", "f")) \
            .view(vals.dtype)
    both = valid & want_valid
    return int((valid != want_valid).sum()
               + (vals[both] != want_vals.astype(vals.dtype)[both]).sum())


def check_file(path, columns=None):
    import pyarrow.parquet as pq

    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.io.parquet_device import (DecodeFallback,
                                                    chunk_device_plan,
                                                    decode_chunk_device,
                                                    eligible_chunks)
    pf = pq.ParquetFile(path)
    names = pf.schema_arrow.names if columns is None else columns
    out = {"file": os.path.basename(path), "chunks": 0, "rows": 0,
           "fallback": [], "wrong": []}
    for rg in range(pf.metadata.num_row_groups):
        nrows = pf.metadata.row_group(rg).num_rows
        cap = bucket_capacity(nrows)
        elig = eligible_chunks(pf, rg, names)
        table = pf.read_row_group(rg, columns=names)
        for name in names:
            if name not in elig:
                out["fallback"].append([rg, name, "ineligible"])
                continue
            c = chunk_device_plan(pf, path, rg, elig[name], name,
                                  pf.schema_arrow.field(name).nullable)
            try:
                got = decode_chunk_device(c, cap)
            except DecodeFallback as e:
                out["fallback"].append([rg, name, e.cause])
                continue
            finally:
                c.close(sync=True)
            want_vals, want_valid = _want(table.column(name))
            bad = _same(got, want_vals, want_valid, nrows)
            out["chunks"] += 1
            out["rows"] += nrows
            if bad:
                out["wrong"].append([rg, name, bad])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--columns", default=None,
                    help="comma-separated columns (default: every one)")
    args = ap.parse_args(argv)
    cols = args.columns.split(",") if args.columns else None
    total = {"chunks": 0, "fallback": 0, "wrong": 0}
    for path in args.files:
        pf_cols = cols
        if cols:
            import pyarrow.parquet as pq
            have = set(pq.ParquetFile(path).schema_arrow.names)
            pf_cols = [c for c in cols if c in have]
        got = check_file(path, pf_cols)
        print(json.dumps(got), flush=True)
        total["chunks"] += got["chunks"]
        total["fallback"] += len(got["fallback"])
        total["wrong"] += len(got["wrong"])
    print(json.dumps(total), flush=True)
    return 1 if total["fallback"] or total["wrong"] else 0


if __name__ == "__main__":
    sys.exit(main())
