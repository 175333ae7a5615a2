"""Base-table rows the completed executions scanned (a constant of the
configuration for each query of the mix, from the schema) over the whole
elapsed window, the execution in flight at its end run to its own end."""


def read(run):
    w, queries = run["window"], run["queries"]
    rows = sum(queries[k % len(queries)]["rows"]
               for k, answer in enumerate(w["answers"]) if answer is not None)
    return rows / w["elapsed_s"] if rows else None
