"""TPC-H tables from --seed, as pyarrow Tables. A table's generator is
`datagen/<table>.py` with `generate(sf, seed, made)`, found by the table's
name: a later PR adds `part.py` and edits nothing. `made` holds what this
call has made already (orders is reckoned from lineitem's numbers)."""
import importlib

from benchmarks.datagen.common import unscaled  # noqa: F401  (re-exported)


def generate(tables, sf, seed):
    """{name: pyarrow Table} for the named tables."""
    made = {}
    if {"lineitem", "orders"} <= set(tables):   # made once, used by both
        from benchmarks.datagen import lineitem
        made["_lineitem_numbers"] = lineitem.numbers(sf, seed)
    for t in tables:
        made[t] = importlib.import_module(
            "benchmarks.datagen." + t).generate(sf, seed, made)
    return {t: made[t] for t in tables}
