"""What a traced run says about each chip of a mesh: every `/device:TPU:n`
plane of the loaded trace on its own, where harness/tracereduce.py gives
their mean. Times are unions of the `XLA Ops` intervals (of `XLA Modules`
where a plane has no op line) cut to the traced window. Every reader here
returns None where there is nothing to read."""
from benchmarks.harness import spans
from benchmarks.harness.tracereduce import MODULES, OPS

COLLECTIVE = ("all-to-all", "alltoall", "all_to_all")


def is_collective(name):
    name = name.lower()
    return any(word in name for word in COLLECTIVE)


def per_plane_ns(run, keep=lambda name: True):
    """For each device plane, in plane order, the nanoseconds of the window
    in which an operation whose name `keep` takes was running."""
    w = spans.window(run)
    if not w:
        return None
    lo, hi, _ = w
    planes = run["trace"]["devices"]
    return [spans.covered_ns(
        [(s, e) for name, s, e in (lines.get(OPS) or lines.get(MODULES) or [])
         if keep(name)], lo, hi) for _, lines in sorted(planes.items())]
