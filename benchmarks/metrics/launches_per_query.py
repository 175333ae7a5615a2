"""Programs launched on the device per execution, from the device's own
`XLA Modules` line: every launch, the eager jnp ones too (where
`dispatches_per_query` counts the call sites that count themselves)."""
from benchmarks.harness import spans


def read(run):
    return spans.launches_per_execution(run)
