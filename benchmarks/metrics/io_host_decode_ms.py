"""Milliseconds an execution spends decoding Parquet column chunks on the
host inside the device-decode scan: the union of `srt.io.host_decode`
(pyarrow's read of the row group's leftover columns and their upload,
exec/nodes.py). 0.0 where the scan opened its `srt.io.` spans and none of
these (every chunk decoded on the device); silent where no `srt.io.` span
opened (tables cached in HBM read no file)."""
from benchmarks.harness import spans

NAMES = ("srt.io.host_decode",)


def read(run):
    if not spans.spans(run, lambda n: n.startswith("srt.io.")):
        return None
    return spans.union_ms(run, *NAMES)
