"""Milliseconds an execution spends planning: the union of the program's
`srt.plan` spans (session.py, around logical -> physical) in the traced
slice, per execution."""
from benchmarks.harness import spans


def read(run):
    return spans.union_ms(run, "srt.plan")
