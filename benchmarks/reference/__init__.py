"""Plain references: one file a query, `q<n>.py`, found by the traffic file's
`reference`. Each has `reference(tables)` (exact, on unscaled integers) and
`control(tables)` (the same query with decimals computed in float32: the
step that would tempt a later PR, and a breach of "decimals exact"), both
returning an `Answer`. Nothing here imports the program."""
