"""The least time an execution's programs of 128-bit decimal arithmetic could
take on this chip, over the time they took (`d128_device_ms`), in %. Bound by
memory: the least bytes they read (harness/d128.py: the rows that reach the
arithmetic, counted from the generated tables, times the declared widths of
the columns it reads) over the chip's HBM bandwidth (harness/peaks.json). A
least count over a published peak reads low, never over 100. Silent where the
trace holds no such program, for a query that is not counted and for a mix of
several."""
from benchmarks.harness import d128


def read(run):
    ms = d128.device_ms(run)
    if not ms or not run["peaks"] or len(run["queries"]) != 1:
        return None
    query = run["queries"][0]["query"]
    if query not in d128.COUNTED:
        return None
    tables = d128.run_tables(run)
    if tables is None:
        return None
    least = d128.least_bytes(query, run["config"]["schema"],
                             run["cardinality"], tables)
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
