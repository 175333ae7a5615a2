"""The least time a chip's share of Q3's exchanges could take on the
interconnect, over the time its `all-to-all` operations took, in %. The least
bytes are a property of the schema, the row counts and Q3's selectivities
(harness/meshbytes.py) for as many chips as the trace has device planes; the
peak is the chip's ICI bandwidth (harness/peaks_ici.json). Bound by the
interconnect; a least count over a published peak reads low, never over 100."""
import json
import os

from benchmarks.harness import manifest, meshbytes, meshtrace, spans

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    per_plane = meshtrace.per_plane_ns(run, meshtrace.is_collective)
    if not per_plane or not any(per_plane) or len(per_plane) < 2:
        return None
    with open(os.path.join(_BENCH, "configs", "tpch_sf1_mesh4.json")) as f:
        config = json.load(f)
    with open(os.path.join(_BENCH, "harness", "peaks_ici.json")) as f:
        peak = json.load(f)["TPU v5 lite"]["ici_bytes_per_s"]
    least = meshbytes.q3_least_bytes_per_chip(
        config["schema"], manifest.cardinality(config, config["scale_factor"]),
        len(per_plane))
    seconds = sum(per_plane) / len(per_plane) / 1e9 / spans.window(run)[2]
    return 100.0 * (least / peak) / seconds
