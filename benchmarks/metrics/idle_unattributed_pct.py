"""Of the device's idle time in the traced slice, the share that lies inside
no span of the program finer than `srt.query`, `srt.admit` and `srt.collect`:
the idle time that still has no name."""
from benchmarks.harness import spans


def read(run):
    return spans.idle_unattributed_pct(run)
