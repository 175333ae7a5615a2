"""Milliseconds an execution spends inside `srt.spmd.collective`: the host's
view of the fused SPMD stages, from the launch of a stage's program over the
mesh to its parked results (union over threads)."""
from benchmarks.harness import spans


def read(run):
    return spans.union_ms(run, "srt.spmd.collective")
