"""Milliseconds an execution spends inside `srt.fetch` (copy to the host
and the wait for it), as the union over threads."""
from benchmarks.harness import spans


def read(run):
    return spans.union_ms(run, "srt.fetch")
