"""BENCHMARK.json and the files it names. A cell is one entry of
`workloads`: its configuration is the `file` of the entry of `configs` it
names, its traffic mix is `traffic/<traffic>.json` beside this directory,
and a metric's reader is `metrics/<name>.py`: so a later PR adds
a cell, a configuration or a metric by adding files and entries only."""
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(root, name):
    """The named cell with its configuration and traffic files read."""
    bench = load(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"name": name, "chips": entry["chips"], "bench": bench,
            "config": config, "traffic": traffic}


def cardinality(config, sf):
    """Rows of every table of the schema's key ranges at scale `sf`."""
    rows = {t: max(int(n * sf), 1) for t, n in config["rows_per_sf"].items()}
    rows.update(config.get("fixed_rows", {}))
    return rows


def cells_of(bench, metric):
    """The cells that report a metric: its `workloads`, or every cell (for a
    per-layer metric, every cell that reports the metric it moves)."""
    if "workloads" in metric:
        return list(metric["workloads"])
    if "moves" in metric:
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == metric["moves"])
        return cells_of(bench, moved)
    return [w["name"] for w in bench["workloads"]]


def metrics_of(bench, cell_name, kind):
    """The `end_to_end` or `per_layer` metrics that this cell reports."""
    return [m for m in bench[kind] if cell_name in cells_of(bench, m)]
