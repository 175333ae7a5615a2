"""Milliseconds an execution spends on the map side of the one-chip
exchange's host work: the union over threads of `srt.shuffle.slice`,
`.serialize`, `.compress` and `.write` (harness/shuffle.py). Silent where the
trace holds no `srt.shuffle.` span."""
from benchmarks.harness import shuffle


def read(run):
    return shuffle.union_ms(run, shuffle.WRITE)
