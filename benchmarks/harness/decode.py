"""The device decode of Parquet column chunks: its programs' time on the
device and the least bytes they have to write.

Every device program of a chunk's decode is named `pq_*`
(spark_rapids_tpu/ops/parquet_decode.py), so on the device's
`XLA Modules` line its events are the ones named `jit_pq_`: whatever
column, page layout or operator downstream, and with no counter of the
program's. A cell whose tables sit in HBM, or a program from before the
name, has none.

The least bytes are the columns the running query reads, each once at
its declared width (harness/bytecount.py): what a decode has to write
for the operators above it, whoever implements it. They come from the
configuration's schema and row counts, and a varchar's from the column
generated again from the run's seed. A reader is handed no traffic, so
the query's reads are the traffic of the cell the process was started
for: `--workload` on its command line, as harness/d128.py takes
`--seed`.
"""
import argparse
import os
import sys

from benchmarks.harness import bytecount, manifest, programs

PREFIX = "jit_pq_"


def device_ms(run):
    """Milliseconds an execution's decode programs run on a chip (the
    union of their events in the traced window, mean over the planes), or
    None where the trace holds none."""
    return programs.device_ms(run, PREFIX)


def _args():
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--sf", type=float)
    return ap.parse_known_args(sys.argv[1:])[0]


def least_bytes(run):
    """Bytes of the columns the run's one query reads, each once at its
    declared width; None where the process was not started as a run of a
    cell, or the cell's mix holds more than one query."""
    args = _args()
    if args.workload is None:
        return None
    queries = manifest.cell(os.path.dirname(manifest.BENCH_DIR),
                            args.workload)["traffic"]["queries"]
    if len(queries) != 1:
        return None
    reads = queries[0]["reads"]
    schema, cardinality = run["config"]["schema"], run["cardinality"]
    tables = None
    wide = sorted(t for t, cols in reads.items() if any(
        bytecount.VARCHAR.fullmatch(schema[t][c]) for c in cols))
    if wide:
        if args.seed is None:
            return None
        from benchmarks import datagen
        sf = run["config"]["scale_factor"] if args.sf is None else args.sf
        tables = datagen.generate(wide, sf, args.seed)
    return sum(bytecount.read_bytes(schema, cardinality, t, c, tables)
               for t, cols in reads.items() for c in cols)
