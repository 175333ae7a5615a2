"""Whether the chips of a mesh share the work: the busiest device plane's
busy time less the least busy one's, over the mean, in %. 0 is an even split;
with everything on one of four chips it reads 400. Silent on one chip."""
from benchmarks.harness import meshtrace


def read(run):
    busy = meshtrace.per_plane_ns(run)
    if not busy or len(busy) < 2 or not any(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
