"""Milliseconds an execution spends on the host's side of the Parquet path:
the union of `srt.io.read` (file bytes, page headers, snappy) and
`srt.io.upload` (H2D of the chunk) from io/parquet_device.py."""
from benchmarks.harness import spans


def read(run):
    return spans.union_ms(run, "srt.io.read", "srt.io.upload")
