"""Times an execution waits for device memory on the host: `srt.fetch`
spans (utils/transfer.py:fetch, the program's one D2H chokepoint) that start
in the traced slice, per execution."""
from benchmarks.harness import spans


def read(run):
    return spans.count_per_execution(run, "srt.fetch")
