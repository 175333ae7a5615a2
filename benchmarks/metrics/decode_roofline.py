"""The least time an execution's Parquet decode programs could take on
this chip, over the time they took (`decode_device_ms`), in %. Bound by
memory: the least bytes they write (harness/decode.py: the columns the
query reads, each once at its declared width) over the chip's HBM
bandwidth (harness/peaks.json). A least count over a published peak reads
low, never over 100. Silent where the trace holds no decode program, off
the chip, and for a mix of several queries."""
from benchmarks.harness import decode


def read(run):
    ms = decode.device_ms(run)
    if not ms or not run["peaks"]:
        return None
    least = decode.least_bytes(run)
    if least is None:
        return None
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
