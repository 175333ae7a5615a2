"""The session's self time per execution: the union of `srt.query` and
`srt.admit` less the part that any other `srt.` span covers, on any thread:
what the session does around planning, prewarm and the collect (admission,
snapshots of metrics, ledger and xla counters, the event log)."""
from benchmarks.harness import spans


def read(run):
    return spans.self_ms(run, "srt.query", "srt.admit")
