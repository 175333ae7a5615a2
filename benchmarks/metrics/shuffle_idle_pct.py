"""Of the device's idle time in the traced slice, the share whose gap's
middle lies inside an `srt.shuffle.` span on any thread: the idle time the
one-chip exchange's host phases hold the chip back (harness/shuffle.py).
Silent where the trace holds no such span."""
from benchmarks.harness import shuffle


def read(run):
    return shuffle.idle_pct(run)
