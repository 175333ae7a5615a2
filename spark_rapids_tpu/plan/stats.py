"""Bottom-up row / NDV statistics over logical plans.

The Catalyst-CBO analog (reference: spark.sql.cbo.* statistics +
FilterEstimation/JoinEstimation): every logical node gets an estimated
row count and a per-column number-of-distinct-values (NDV) estimate,
propagated bottom-up. Scans sample their first ~64K rows once (cached on
the scan node, so repeated plans of a cached DataFrame pay nothing) and
extrapolate NDV with a Chao1-style estimator; filters scale rows by the
same per-conjunct selectivities the placement CBO uses; joins apply the
classic |L|*|R| / max(ndv(lk), ndv(rk)) equi-join formula; aggregates
shrink to the product of key NDVs.

Consumers: the join-reorder pass (plan/cbo.py) ranks left-deep join
orders by these estimates. Estimates are advisory — a bad estimate can
cost performance, never correctness.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional

from . import logical as L

__all__ = ["Stats", "compute_stats", "scan_column_ndv",
           "calibration_scope", "calibration_lookup", "logical_fp",
           "join_set_fp", "attach_calibration_fps",
           "harvest_calibration", "calibration_stats",
           "clear_calibration", "export_calibration",
           "import_calibration"]

# Rows sampled (from the first batch / the arrow table head) for NDV.
SAMPLE_ROWS = 1 << 16

# ---------------------------------------------------------------------
# Session-scoped cardinality calibration (the AQE feedback loop).
#
# After a query runs, `harvest_calibration` records each operator's
# OBSERVED numOutputRows keyed by the structural fingerprint of its
# logical subtree (the same gensym-normalized expr_fp identity the
# reuse pass and result cache key on). `compute_stats` consults the
# table first, so the next plan of the same subtree — in this session —
# estimates from measurement instead of heuristics. Join subtrees also
# record under an ORDER-INDEPENDENT key (the frozenset of their flat
# relation fingerprints), which is what lets the join-reorder DP
# (plan/cbo.py) cost a relation subset by the cardinality an earlier
# order actually produced.
#
# Lookups are scoped: they only fire inside a `calibration_scope(True)`
# (Planner.plan enters it when sql.adaptive.enabled AND
# sql.adaptive.calibration.enabled), so a session that turns AQE off
# plans exactly as if the table did not exist. Entries are advisory —
# a stale entry can cost performance, never correctness.
# ---------------------------------------------------------------------
_CAL_LOCK = threading.Lock()
_CAL: Dict[Any, float] = {}
_CAL_STATS = {"calibration_hits": 0, "calibration_updates": 0}
_CAL_TLS = threading.local()


@contextmanager
def calibration_scope(enabled: bool):
    """Enable calibration lookups on this thread (planning only)."""
    prev = getattr(_CAL_TLS, "enabled", False)
    _CAL_TLS.enabled = bool(enabled)
    try:
        yield
    finally:
        _CAL_TLS.enabled = prev


def calibration_lookup(key) -> Optional[float]:
    """Observed row count for a fingerprint key, or None. Counts a hit
    only inside an enabled scope."""
    if key is None or not getattr(_CAL_TLS, "enabled", False):
        return None
    with _CAL_LOCK:
        v = _CAL.get(key)
        if v is not None:
            _CAL_STATS["calibration_hits"] += 1
        return v


def _calibration_record(key, rows: float) -> None:
    with _CAL_LOCK:
        _CAL[key] = float(rows)
        _CAL_STATS["calibration_updates"] += 1


def calibration_stats() -> Dict[str, int]:
    with _CAL_LOCK:
        out = dict(_CAL_STATS)
        out["calibration_entries"] = len(_CAL)
        return out


def clear_calibration() -> None:
    with _CAL_LOCK:
        _CAL.clear()
        for k in _CAL_STATS:
            _CAL_STATS[k] = 0


def export_calibration():
    """The calibration table as a picklable [(key, rows), ...] — the
    fleet warm-state payload (fleet/member.py). Keys are nested tuples
    of primitives (logical_fp/join_set_fp), so they survive the wire
    intact."""
    with _CAL_LOCK:
        return list(_CAL.items())


def import_calibration(table) -> int:
    """Merge a peer's exported calibration table. Peer entries only
    fill HOLES — a locally observed row count reflects THIS process's
    data view and always wins. Returns entries adopted."""
    if not table:
        return 0
    adopted = 0
    with _CAL_LOCK:
        for key, rows in table:
            key = _freeze(key)
            if key in _CAL:
                continue
            _CAL[key] = float(rows)
            adopted += 1
        if adopted:
            _CAL_STATS["calibration_updates"] += adopted
    return adopted


def _freeze(key):
    """Normalize list-shaped wire keys back to the tuple form the
    fingerprint functions produce (defensive: pickle preserves tuples,
    but a JSON-bounced payload would not)."""
    if isinstance(key, list):
        return tuple(_freeze(k) for k in key)
    return key


def logical_fp(node: L.LogicalPlan):
    """CARDINALITY fingerprint of a logical subtree, memoized on the
    node (`_*_cache` convention, so expr_fp skips the memo attr).

    Row counts are invariant to projection placement and column
    pruning, so the fingerprint hashes only the cardinality skeleton —
    scans, filter conditions, join how/keys, grouping keys, limits —
    and SEES THROUGH row-preserving wrappers (Project/Sort/Window/
    Repartition). That invariance is load-bearing: lookups fire at the
    join-reorder stage (pre-prune) while harvest keys come from the
    final converted tree (post-prune); a full structural fp would never
    match across the two, and its repr/hash cost scales with embedded
    bound-expression trees."""
    fp = getattr(node, "_calib_fp_cache", None)
    if fp is None:
        fp = node._calib_fp_cache = _card_fp(node)
    return fp


def _card_fp(node: L.LogicalPlan):
    from ..runtime.program_cache import expr_fp, exprs_fp
    if isinstance(node, (L.Project, L.Sort, L.Repartition, L.WindowOp)):
        return logical_fp(node.children[0])   # row-preserving
    if isinstance(node, L.Filter):
        return ("F", expr_fp(node.condition),
                logical_fp(node.children[0]))
    if isinstance(node, L.Join):
        return ("J", node.how,
                exprs_fp(node.left_keys), exprs_fp(node.right_keys),
                expr_fp(getattr(node, "condition", None)),
                logical_fp(node.children[0]),
                logical_fp(node.children[1]))
    if isinstance(node, L.Aggregate):
        # groups depend on keys only — different agg columns over the
        # same keys legitimately share one observation
        return ("A", exprs_fp(node.keys), logical_fp(node.children[0]))
    if isinstance(node, L.Limit):
        return ("L", int(node.n), logical_fp(node.children[0]))
    if isinstance(node, L.Union):
        return ("U",) + tuple(logical_fp(c) for c in node.children)
    if isinstance(node, L.InMemoryScan):
        return ("S", "mem", id(node.arrow))   # session-scoped identity
    if isinstance(node, L.ParquetScan):
        # rows depend on the files, the pushed row-group filters, and
        # the data version — not on the projected column subset
        return ("S", "parquet", tuple(node.paths),
                expr_fp(node.filters), expr_fp(node.snapshot))
    if isinstance(node, L.CachedScan):
        # rows are the cached table's, whichever of its columns the plan
        # reads: a pruned view (post-prune harvest) keys like its leaf
        # (pre-prune lookup)
        return ("S", "cached", id(node.table_id))
    if isinstance(node, L.Expand):
        return ("X", "Expand", len(node.include_masks),
                logical_fp(node.children[0]))
    if not node.children:
        paths = getattr(node, "paths", None) or getattr(node, "path",
                                                        None)
        if paths:
            return ("S", type(node).__qualname__,
                    tuple(paths) if not isinstance(paths, str)
                    else paths)
        return ("S", type(node).__qualname__, id(node))
    # unknown operator: type + child skeletons. Two same-typed siblings
    # over one child could falsely share — advisory rows only, never a
    # correctness risk.
    return ("X", type(node).__qualname__) + tuple(
        logical_fp(c) for c in node.children)


def _flatten_rels(node: L.LogicalPlan):
    """Relations of a flat inner-equi join chain, seeing through the
    pass-through projections session.join leaves between chained joins
    — the SAME flattening discipline as cbo._flatten_chain, so a jset
    key harvested from an executed join matches the key the reorder
    pass looks up for the same relation set. A non-inner join anywhere
    poisons the chain (order is semantics there, so subset keys would
    lie)."""
    from .cbo import _is_passthrough, _reorderable_join
    if isinstance(node, L.Project) and _is_passthrough(node) \
            and _reorderable_join(node.children[0]):
        return _flatten_rels(node.children[0])
    if isinstance(node, L.Join):
        if not _reorderable_join(node):
            return None
        l = _flatten_rels(node.children[0])
        r = _flatten_rels(node.children[1])
        if l is None or r is None:
            return None
        return l + r
    return [node]


def join_set_fp(node: L.LogicalPlan):
    """Order-independent key for an inner-equi join subtree: the
    frozenset of its flat relations' fingerprints. Any join order over
    the same relation set produces the same multiset of output rows,
    so one observed cardinality prices every order."""
    if not isinstance(node, L.Join):
        return None
    rels = _flatten_rels(node)
    if rels is None or len(rels) < 2:
        return None
    # fp tuples are hashable by construction (expr_fp falls back to
    # ("id", id) for anything that isn't) — hash them directly; repr()
    # would stringify embedded foreign values (arrow buffers!) at
    # data-proportional cost
    return ("jset", frozenset(logical_fp(r) for r in rels))


def attach_calibration_fps(logical: L.LogicalPlan, physical) -> None:
    """Stamp the planning-time fingerprints onto the physical node so
    post-run harvest can key observations without re-deriving the
    logical tree. Underscore attrs are invisible to the reuse pass's
    node_fp, so attachments never split exchange-reuse identity."""
    if physical is None or not getattr(_CAL_TLS, "enabled", False):
        return
    physical._calib_fp = logical_fp(logical)
    jfp = join_set_fp(logical)
    if jfp is not None:
        physical._calib_set_fp = jfp


def harvest_calibration(root_exec, ctx) -> int:
    """Record observed output cardinalities of a finished run into the
    calibration table. Skipped wholesale when the tree contains a
    limit/top-k (truncated pulls underreport every producer below
    them) and when the conf gates calibration off. Returns the number
    of entries recorded."""
    from ..config import ADAPTIVE_CALIBRATION, ADAPTIVE_ENABLED
    conf = getattr(ctx, "conf", None)
    if conf is None or not (conf.get(ADAPTIVE_ENABLED)
                            and conf.get(ADAPTIVE_CALIBRATION)):
        return 0
    nodes, stack, seen = [], [root_exec], set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        tname = type(node).__name__
        if "Limit" in tname or "TopK" in tname:
            return 0
        nodes.append(node)
        stack.extend(node.children)
    recorded = 0
    for node in nodes:
        ms = ctx.metrics.get(node._op_id)
        if ms is None:
            continue
        rows = ms.get("numOutputRows", 0)
        if not rows or rows <= 0:
            continue
        for attr in ("_calib_fp", "_calib_set_fp"):
            key = getattr(node, attr, None)
            if key is not None:
                _calibration_record(key, float(rows))
                recorded += 1
    return recorded


class Stats:
    """Row estimate + lazy per-column NDV lookup. `rows` is None when the
    subtree has no estimable source. `ndv_of(name)` returns an NDV
    estimate for an output column or None when unknown."""

    __slots__ = ("rows", "_ndv_of")

    def __init__(self, rows: Optional[float],
                 ndv_of: Optional[Callable[[str], Optional[float]]] = None):
        self.rows = rows
        self._ndv_of = ndv_of or (lambda name: None)

    def ndv_of(self, name: str) -> Optional[float]:
        nd = self._ndv_of(name)
        if nd is None:
            return None
        if self.rows is not None:
            nd = min(nd, self.rows)
        return max(nd, 1.0)


def _chao1(counts, sample_n: int, total_rows: float) -> float:
    """Extrapolate sample distinct count to the full column: Chao1
    lower-bound estimator d + f1^2/(2*f2); an all-singleton sample is
    read as a unique(-ish) column."""
    import numpy as np
    d = int(counts.shape[0])
    if sample_n >= total_rows:
        return float(d)
    f1 = int(np.count_nonzero(counts == 1))
    f2 = int(np.count_nonzero(counts == 2))
    if f1 >= sample_n or (f1 == d and f2 == 0):
        return float(total_rows)        # every sampled value unique
    est = d + (f1 * f1) / (2.0 * max(f2, 1))
    return float(min(max(est, d), total_rows))


def _sample_arrow_column(node: L.LogicalPlan, name: str):
    """First-SAMPLE_ROWS slice of a scan column as a pyarrow array, or
    None when the scan cannot serve one cheaply."""
    if isinstance(node, L.InMemoryScan):
        if name not in node.arrow.schema.names:
            return None
        return node.arrow.column(name).slice(0, SAMPLE_ROWS)
    if isinstance(node, L.CachedScan):
        if not node.batches or name not in node.schema.names:
            return None
        from ..exec.nodes import _batch_to_arrow
        at = getattr(node, "_stats_sample_cache", None)
        if at is None:
            at = _batch_to_arrow(node.batches[0]).slice(0, SAMPLE_ROWS)
            node._stats_sample_cache = at
        if name not in at.schema.names:
            return None
        return at.column(name)
    return None


def scan_column_ndv(node: L.LogicalPlan, name: str) -> Optional[float]:
    """NDV estimate for one scan column, sampled once and cached on the
    node (leaf nodes survive re-planning, so the sample is paid once per
    DataFrame, not once per query execution)."""
    cache: Dict[str, Optional[float]] = getattr(node, "_ndv_cache", None)
    if cache is None:
        cache = node._ndv_cache = {}
    if name in cache:
        return cache[name]
    ndv: Optional[float] = None
    try:
        from .planner import _estimate_rows
        rows = _estimate_rows(node)
        arr = _sample_arrow_column(node, name)
        if arr is not None and rows:
            import numpy as np
            import pyarrow.compute as pc
            vc = pc.value_counts(arr)
            counts = np.asarray(vc.field("counts"))
            ndv = _chao1(counts, len(arr), float(rows))
    except Exception:
        ndv = None
    cache[name] = ndv
    return ndv


def _proj_ndv_map(exprs) -> Dict[str, Optional[str]]:
    """Output name -> source column name for pass-through / renamed
    columns; computed expressions map to None (NDV unknown)."""
    from ..expr.expressions import Alias, ColumnRef
    out: Dict[str, Optional[str]] = {}
    for e in exprs:
        if isinstance(e, ColumnRef):
            out[e.name] = e.name
        elif isinstance(e, Alias) and isinstance(e.child, ColumnRef):
            out[e.name] = e.child.name
        else:
            out[getattr(e, "name", "?")] = None
    return out


def _key_name(expr) -> Optional[str]:
    """Single column name a join/group key resolves to, else None."""
    from .optimizer import refs_of
    refs = refs_of(expr)
    if refs is not None and len(refs) == 1:
        return next(iter(refs))
    return None


def _join_rows(node: L.Join, ls: Stats, rs: Stats) -> Optional[float]:
    if ls.rows is None or rs.rows is None:
        return None
    if node.how in ("left_semi", "left_anti"):
        return ls.rows * (0.5 if node.how == "left_semi" else 0.5)
    rows = ls.rows * rs.rows
    for lk, rk in zip(node.left_keys, node.right_keys):
        ln, rn = _key_name(lk), _key_name(rk)
        ndv_l = (ls.ndv_of(ln) if ln else None) or ls.rows
        ndv_r = (rs.ndv_of(rn) if rn else None) or rs.rows
        rows /= max(ndv_l, ndv_r, 1.0)
    if node.how in ("left", "full"):
        rows = max(rows, ls.rows)
    if node.how in ("right", "full"):
        rows = max(rows, rs.rows)
    return rows


def compute_stats(node: L.LogicalPlan) -> Stats:
    """Bottom-up (rows, ndv) estimate for a logical subtree. Inside a
    calibration scope, an observed cardinality for this exact subtree
    overrides the analytic row estimate (NDV propagation unchanged —
    observation measures rows, not distincts)."""
    s = _compute_stats_raw(node)
    rows = calibration_lookup(logical_fp(node)) \
        if getattr(_CAL_TLS, "enabled", False) else None
    if rows is not None:
        s = Stats(rows, s._ndv_of)
    return s


def _compute_stats_raw(node: L.LogicalPlan) -> Stats:
    from .cbo import _selectivity
    from .planner import _estimate_rows

    if isinstance(node, (L.InMemoryScan, L.CachedScan, L.ParquetScan,
                         L.TextScan)):
        rows = _estimate_rows(node)
        return Stats(None if rows is None else float(rows),
                     lambda n, _nd=node: scan_column_ndv(_nd, n))

    if isinstance(node, L.Filter):
        cs = compute_stats(node.children[0])
        rows = (None if cs.rows is None
                else cs.rows * _selectivity(node.condition))
        return Stats(rows, cs._ndv_of)

    if isinstance(node, L.Project):
        cs = compute_stats(node.children[0])
        m = _proj_ndv_map(node.exprs)

        def ndv(n, _m=m, _cs=cs):
            src = _m.get(n)
            return None if src is None else _cs.ndv_of(src)
        return Stats(cs.rows, ndv)

    if isinstance(node, L.Join):
        ls = compute_stats(node.children[0])
        rs = compute_stats(node.children[1])
        rows = _join_rows(node, ls, rs)
        lnames = set(node.left.schema.names)

        def ndv(n, _ls=ls, _rs=rs, _ln=lnames):
            return _ls.ndv_of(n) if n in _ln else _rs.ndv_of(n)
        return Stats(rows, ndv)

    if isinstance(node, L.Aggregate):
        cs = compute_stats(node.children[0])
        if cs.rows is None:
            return Stats(None)
        groups = 1.0
        known = True
        for k in node.keys:
            kn = _key_name(k)
            nd = cs.ndv_of(kn) if kn else None
            if nd is None:
                known = False
                break
            groups *= nd
        rows = min(groups, cs.rows) if known else \
            min(cs.rows, max(cs.rows ** 0.75, 1.0))
        key_names = {k.name for k in node.keys}

        def ndv(n, _cs=cs, _keys=key_names):
            return _cs.ndv_of(n) if n in _keys else None
        return Stats(max(rows, 1.0), ndv)

    if isinstance(node, L.Limit):
        cs = compute_stats(node.children[0])
        rows = (node.n if cs.rows is None
                else min(float(node.n), cs.rows))
        return Stats(float(rows), cs._ndv_of)

    if isinstance(node, L.Union):
        parts = [compute_stats(c) for c in node.children]
        if any(p.rows is None for p in parts):
            return Stats(None)
        return Stats(sum(p.rows for p in parts))

    if isinstance(node, (L.Sort, L.Repartition, L.WindowOp)):
        cs = compute_stats(node.children[0])
        return Stats(cs.rows, cs._ndv_of)

    rows = _estimate_rows(node)
    return Stats(None if rows is None else float(rows))
