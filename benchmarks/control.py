#!/usr/bin/env python3
"""The control of `correct`: each query's reference computed with decimals in
float32 (a breach of "decimals exact", and the step that would tempt a later
PR) is put in the program's place and has to come out NOT correct, at the
cell's own size. Needs no chip and no program. One JSON line a seed and query:
`control_mismatches` has to be above 0 and `reference_mismatches` 0.

  python3 benchmarks/control.py --sf 1 --seeds 11 12 13
  python3 benchmarks/control.py --sf 10 --seeds 11 12 13 --traffic q6
"""
import argparse
import glob
import importlib
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import datagen  # noqa: E402
from benchmarks.harness import compare  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--traffic", nargs="+", default=None,
                    help="only these mixes (default: every traffic file)")
    args = ap.parse_args(argv)
    ok = True
    for path in sorted(glob.glob(os.path.join(HERE, "traffic", "*.json"))):
        if args.traffic and os.path.basename(path)[:-5] not in args.traffic:
            continue
        with open(path) as f:
            queries = json.load(f)["queries"]
        for query, seed in itertools.product(queries, args.seeds):
            ref = importlib.import_module(
                "benchmarks.reference." + query["reference"])
            tables = datagen.generate(sorted(query["reads"]), args.sf, seed)
            t0 = time.perf_counter()
            answer = ref.reference(tables)
            secs = time.perf_counter() - t0
            control = compare.mismatches(
                compare.to_table(ref.control(tables)), answer)
            same = compare.mismatches(compare.to_table(answer), answer)
            ok = ok and control > 0 and same == 0
            print(json.dumps({
                "traffic": os.path.basename(path)[:-5],
                "query": query["query"], "sf": args.sf,
                "seed": seed, "reference_seconds": secs,
                "control_mismatches": control, "limit": 0,
                "reference_mismatches": same}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
