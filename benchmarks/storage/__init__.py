"""Where a configuration keeps its tables: `storage/<kind>.py`, found by the
configuration's `storage.kind`. Each has `Storage(session, spec, work_dir)`
with `load(tables)`, `scans()` (the {table: DataFrame} a fresh query tree is
built on, asked for once per execution) and `close()`."""
