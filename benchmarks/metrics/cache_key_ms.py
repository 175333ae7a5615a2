"""Milliseconds an execution spends keying its launches in the program
cache: the union of the program's `srt.cache.key` spans
(runtime/program_cache.py, the walk over a launch's arguments that builds
its signature, before the program is looked up) in the traced slice, per
execution."""
from benchmarks.harness import spans


def read(run):
    return spans.union_ms(run, "srt.cache.key")
