"""Device<->host transfer helpers.

Each D2H copy pays a fixed latency; issuing `copy_to_host_async` on every
leaf before `device_get` overlaps those latencies. This is the engine's single
D2H chokepoint — all exports and host syncs go through `fetch`.
"""
from __future__ import annotations

import jax

from ..profiler import tracing

__all__ = ["fetch", "fetch_int"]


def fetch(tree):
    with tracing.span("fetch", "d2h"):
        leaves = jax.tree_util.tree_leaves(tree)
        for leaf in leaves:
            copy_async = getattr(leaf, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:
                    pass
        # tpulint: allow[host-sync] the single blessed D2H chokepoint
        return jax.device_get(tree)


def fetch_int(x) -> int:
    return int(fetch(x))
