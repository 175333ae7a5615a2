"""The programs of 128-bit decimal arithmetic: their time on the device and
the least bytes they have to read.

A cached program whose body computes in decimals past 18 digits is named
`jit_<ExecClass>_<tag>_d128` (spark_rapids_tpu/exec/base.py:D128_MARK, PR 37),
so on the device's `XLA Modules` line its events are the ones whose name,
before the fingerprint in brackets, ends in the mark: whichever operator
holds the arithmetic, and with no counter of the program's.

The least bytes are counted for TPC-H Q9, the one query counted so far, from
the generated tables alone: the lineitem rows whose part's name holds the
colour are the rows the five inner joins let through (every lineitem row
finds its supplier, its partsupp row, its order and its nation), and the
amount reads four decimals of each, at their declared widths
(harness/bytecount.py): l_extendedprice, l_discount, ps_supplycost,
l_quantity. Nothing for the output, which a fused program need never write.
"""
import argparse
import sys

from benchmarks.harness import bytecount, spans
from benchmarks.harness.tracereduce import MODULES

MARK = "_d128"
AMOUNT_READS = [("lineitem", "l_extendedprice"), ("lineitem", "l_discount"),
                ("partsupp", "ps_supplycost"), ("lineitem", "l_quantity")]
COUNTED = {9: "green"}      # query number -> the colour its filter asks for


def marked(name):
    """Is this `XLA Modules` event a program of 128-bit decimal arithmetic?"""
    return name.split("(")[0].endswith(MARK)


def device_ms(run):
    """Milliseconds an execution's marked programs run on a chip: the union
    of their events cut to the traced window, mean over the device planes.
    None where there is no window or the trace holds no marked program (a
    query with no such arithmetic, or a program from before the mark)."""
    w = spans.window(run)
    if not w:
        return None
    lo, hi, executions = w
    per_plane = [spans.covered_ns(
        [(s, e) for name, s, e in lines.get(MODULES, []) if marked(name)],
        lo, hi) for lines in run["trace"]["devices"].values()]
    if not any(per_plane):
        return None
    return sum(per_plane) / len(per_plane) / 1e6 / executions


def amount_rows(tables, colour):
    """Lineitem rows whose part's name holds `colour`."""
    import numpy as np
    import pyarrow.compute as pc
    part = tables["part"]
    keys = part["p_partkey"].to_numpy()[pc.match_substring(
        part["p_name"], colour).to_numpy(zero_copy_only=False)]
    return int(np.isin(tables["lineitem"]["l_partkey"].to_numpy(),
                       keys).sum())


def least_bytes(query, schema, cardinality, tables):
    """Bytes an execution of `query`'s 128-bit programs has to read, or None
    for a query that is not counted."""
    if query not in COUNTED:
        return None
    width = sum(bytecount.width(schema[t][c], cardinality)
                for t, c in AMOUNT_READS)
    return amount_rows(tables, COUNTED[query]) * width


def run_tables(run):
    """What `least_bytes` reads of the running cell's tables, generated
    again: PART, and LINEITEM's l_partkey alone (its numeric streams; no
    text is made). A reader is handed no table, so seed and scale come as
    run.py takes them, from the run's own command line (`--seed`; `--sf` in
    a rehearsal, else the configuration's scale factor). None where the
    process was not started as a run of a cell."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--sf", type=float)
    args = ap.parse_known_args(sys.argv[1:])[0]
    if args.seed is None:
        return None
    import pyarrow as pa
    from benchmarks.datagen import lineitem, part
    sf = run["config"]["scale_factor"] if args.sf is None else args.sf
    return {"part": part.generate(sf, args.seed, {}),
            "lineitem": pa.table({"l_partkey": lineitem.numbers(
                sf, args.seed)["l_partkey"]})}
