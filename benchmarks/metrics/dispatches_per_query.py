"""xla_stats `dispatches` over the window, per completed execution."""


def read(run):
    w = run["window"]
    if not w["completed"]:
        return None
    return w["counters"]["dispatches"] / w["completed"]
