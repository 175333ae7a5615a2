"""Milliseconds an execution spends in the shuffle's codec: the union over
threads of `srt.shuffle.compress` and `.decompress` (harness/shuffle.py); 0
where blocks cross uncompressed. Silent where the trace holds no
`srt.shuffle.` span."""
from benchmarks.harness import shuffle


def read(run):
    return shuffle.union_ms(run, shuffle.CODEC)
