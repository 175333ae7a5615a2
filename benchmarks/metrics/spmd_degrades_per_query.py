"""`srt.spmd.degrade` spans an execution: stages that fell back from the
fused program to the round-based exchange (over budget, or a fault). The mesh
configuration guarantees 0."""
from benchmarks.harness import spans


def read(run):
    return spans.count_per_execution(run, "srt.spmd.degrade")
