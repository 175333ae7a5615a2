"""Milliseconds an execution's programs of 128-bit decimal arithmetic run on
a chip: the `XLA Modules` events of the traced slice whose name ends in
`_d128` (harness/d128.py), mean over the device planes. Silent where the
trace holds none."""
from benchmarks.harness import d128


def read(run):
    return d128.device_ms(run)
