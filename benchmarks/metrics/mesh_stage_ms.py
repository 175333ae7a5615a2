"""Milliseconds an execution spends on the host's side of the SPMD stages:
the union of the staging span (`srt.spmd.stage`: the map side run into staged
batches) and the stage operator's timers (`srt.SpmdStageExec.*`)."""
from benchmarks.harness import spans


def read(run):
    found = spans.spans(run, lambda n: n == "srt.spmd.stage"
                        or n.startswith("srt.SpmdStageExec."))
    if found is None:
        return None
    lo, hi, executions = spans.window(run)
    return spans.covered_ns(found, lo, hi) / 1e6 / executions
