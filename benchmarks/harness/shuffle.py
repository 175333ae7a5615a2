"""The one-chip exchange's host phases in a loaded trace. The program opens a
span at each phase of its file shuffle (spark_rapids_tpu/shuffle/local.py,
serializer.py, exec/exchange.py), on whichever thread does the work: the map
side's slice, serialize (with compress inside it) and file write; the reduce
side's file read, decode (with decompress inside it), assemble and upload.

Every reader here returns None where the trace holds no `srt.shuffle.` span:
a cell whose plan crosses no one-chip exchange (the mesh cells, q6), or a
program from before these spans existed."""
from benchmarks.harness import spans
from benchmarks.harness.tracereduce import MODULES, OPS, _clip, _union

PREFIX = "srt.shuffle."
WRITE = ("srt.shuffle.slice", "srt.shuffle.serialize", "srt.shuffle.compress",
         "srt.shuffle.write")
READ = ("srt.shuffle.read", "srt.shuffle.decompress", "srt.shuffle.decode",
        "srt.shuffle.assemble", "srt.shuffle.upload")
CODEC = ("srt.shuffle.compress", "srt.shuffle.decompress")


def _any(run):
    """The `srt.shuffle.` spans of the run, or None where it has none."""
    return spans.spans(run, lambda n: n.startswith(PREFIX)) or None


def union_ms(run, names):
    """Milliseconds an execution spends inside any span of `names`, on any
    thread; 0 where the run has shuffle spans but none of these."""
    if not _any(run):
        return None
    return spans.union_ms(run, *names)


def idle_pct(run):
    """Of the device's idle time in the window (the gaps of the union of its
    `XLA Ops`), the share whose gap's middle lies inside any `srt.shuffle.`
    span on any thread: the sweep of `spans.idle_unattributed_pct`."""
    found = _any(run)
    if not found:
        return None
    lo, hi, _ = spans.window(run)
    inside = _union(_clip(found, lo, hi))
    idle = shuffled = 0
    for lines in run["trace"]["devices"].values():
        ops = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        at = 0                           # gaps come in order: one sweep
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            idle += ge - gs
            mid = (gs + ge) // 2
            while at < len(inside) and inside[at][1] <= mid:
                at += 1
            if at < len(inside) and inside[at][0] <= mid:
                shuffled += ge - gs
    return 100.0 * shuffled / idle if idle else None
