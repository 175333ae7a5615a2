"""xla_stats `compiles` over the window: 0 is expected; a compile inside the
window is reported, not hidden."""


def read(run):
    return run["window"]["counters"]["compiles"]
