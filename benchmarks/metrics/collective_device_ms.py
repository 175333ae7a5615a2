"""Milliseconds an execution's `all-to-all` operations run on a chip: the
union of those events of the `XLA Ops` line, mean over the device planes.
Silent where the trace shows none (one chip, or a program with no collective)."""
from benchmarks.harness import meshtrace, spans


def read(run):
    per_plane = meshtrace.per_plane_ns(run, meshtrace.is_collective)
    if not per_plane or not any(per_plane):
        return None
    return sum(per_plane) / len(per_plane) / 1e6 / spans.window(run)[2]
