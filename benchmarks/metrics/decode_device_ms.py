"""Milliseconds an execution's Parquet decode programs run on a chip: the
`XLA Modules` events of the traced slice named `jit_pq_*`
(harness/decode.py), mean over the device planes. Silent where the trace
holds none."""
from benchmarks.harness import decode


def read(run):
    return decode.device_ms(run)
