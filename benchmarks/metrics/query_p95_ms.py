"""Nearest-rank 95th percentile over all the executions of the window."""


def read(run):
    ordered = sorted(run["window"]["latencies"])
    return 1e3 * ordered[max(0, -(-95 * len(ordered) // 100) - 1)]
