"""The least time the traced executions could take on this chip, over the
device's busy time in them, in %. The least time is bound by memory: the
bytes of the columns each query has to read, once, plus its result
(harness/bytecount.py), over the chip's HBM bandwidth (harness/peaks.json).
Silent where the trace shows no device operation."""


def read(run):
    t, queries = run["reduced"], run["queries"]
    if not t or not t["busy_s"] or not t["executions"] or not run["peaks"]:
        return None
    least_bytes = sum(queries[k % len(queries)]["least_bytes"]
                      for k in range(t["executions"]))
    return (100.0 * least_bytes / run["peaks"]["hbm_bytes_per_s"]
            / t["busy_s"])
