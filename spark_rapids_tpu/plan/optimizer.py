"""Logical optimizations: filter pushdown + column pruning.

The reference gets both from Spark Catalyst for free; standalone we do
them here:
- `push_filters` moves Filter conditions below pass-through Projects and
  into the matching side of Joins (inner: both sides; left/semi/anti:
  left only; right: right only), so joins see pre-filtered inputs.
- `prune` flows required attributes top-down through
  Project/Filter/Aggregate/Sort/Limit/Join chains and shrinks scans AND
  join gather widths (the join expansion gathers only surviving columns).
"""
from __future__ import annotations

from typing import Optional, Set

from ..expr.expressions import Alias, BoundRef, ColumnRef, Expression
from . import logical as L

__all__ = ["optimize", "refs_of", "push_filters"]


def refs_of(e: Expression) -> Optional[Set[str]]:
    """Column names referenced by an unbound expression tree.
    None = unknown (contains a raw BoundRef) — disables pruning."""
    if isinstance(e, ColumnRef):
        return {e.name}
    if isinstance(e, BoundRef):
        return None
    out: Set[str] = set()
    for c in e.children:
        if c is None:
            continue
        r = refs_of(c)
        if r is None:
            return None
        out |= r
    return out


def _refs_of_all(exprs) -> Optional[Set[str]]:
    out: Set[str] = set()
    for e in exprs:
        if e is None:
            continue
        r = refs_of(e)
        if r is None:
            return None
        out |= r
    return out


def prune(plan: L.LogicalPlan,
          required: Optional[Set[str]]) -> L.LogicalPlan:
    if isinstance(plan, L.InMemoryScan):
        if required is not None:
            names = [n for n in plan.arrow.schema.names if n in required]
            if len(names) < len(plan.arrow.schema.names):
                return L.InMemoryScan(plan.arrow.select(names))
        return plan
    if isinstance(plan, L.CachedScan):
        # views of the resident columns (nothing is copied), memoised on
        # the leaf: a fresh tree over the same cache plans the same node
        return plan if required is None else plan.pruned(required)
    if isinstance(plan, L.ParquetScan):
        if required is not None:
            names = [f.name for f in plan.schema.fields
                     if f.name in required]
            if len(names) < len(plan.schema.fields):
                return L.ParquetScan(plan.paths, columns=names,
                                     dv=plan.dv)
        return plan
    if isinstance(plan, L.TextScan):
        if required is not None:
            names = [f.name for f in plan.schema.fields
                     if f.name in required]
            if len(names) < len(plan.schema.fields):
                return L.TextScan(plan.paths, plan.fmt, plan._full_schema,
                                  names, plan.options)
        return plan
    if isinstance(plan, L.Project):
        exprs = plan.exprs
        if required is not None:
            kept = [e for e in exprs if e.name in required]
            if kept:
                exprs = kept
        child_req = _refs_of_all(exprs)
        child = prune(plan.child, child_req)
        return L.Project(child, exprs)
    if isinstance(plan, L.Filter):
        creq = None
        if required is not None:
            r = refs_of(plan.condition)
            creq = None if r is None else (required | r)
        child = prune(plan.child, creq)
        if isinstance(child, L.ParquetScan):
            # attach pushable conjuncts for row-group pruning (the
            # filterBlocks analog: GpuParquetScan.scala:679); the Filter
            # stays above for exact row filtering
            conj = extract_conjuncts(plan.condition)
            if conj:
                child = L.ParquetScan(child.paths, child._schema,
                                      child.columns,
                                      (child.filters or []) + conj,
                                      dv=child.dv)
        return L.Filter(child, plan.condition)
    if isinstance(plan, L.Aggregate):
        creq = _refs_of_all(list(plan.keys) +
                            [a.child for _, a in plan.aggs])
        child = prune(plan.child, creq)
        return L.Aggregate(child, plan.keys, plan.aggs)
    if isinstance(plan, L.Sort):
        creq = None
        if required is not None:
            r = _refs_of_all([o.expr for o in plan.orders])
            creq = None if r is None else (required | r)
        child = prune(plan.child, creq)
        return L.Sort(child, plan.orders, plan.global_sort)
    if isinstance(plan, L.Limit):
        return L.Limit(prune(plan.child, required), plan.n)
    if isinstance(plan, L.Union):
        return L.Union([prune(c, None) for c in plan.children])
    if isinstance(plan, L.Join):
        lnames = set(plan.left.schema.names)
        rnames = set(plan.right.schema.names)
        lkr = _refs_of_all(plan.left_keys)
        rkr = _refs_of_all(plan.right_keys)
        ckr = (_refs_of_all([plan.condition])
               if plan.condition is not None else set())
        lreq = rreq = None
        if (required is not None and lkr is not None and rkr is not None
                and ckr is not None and not (lnames & rnames)):
            lreq = ({n for n in required if n in lnames} | lkr
                    | (ckr & lnames))
            rreq = ({n for n in required if n in rnames} | rkr
                    | (ckr & rnames))
        return L.Join(prune(plan.left, lreq), prune(plan.right, rreq),
                      plan.left_keys, plan.right_keys, plan.how,
                      condition=plan.condition)
    if isinstance(plan, L.WindowOp):
        return L.WindowOp(prune(plan.child, None), plan.wcols)
    if isinstance(plan, L.Repartition):
        return L.Repartition(prune(plan.child, None), plan.num_partitions,
                             plan.keys)
    return plan


def _rebuild(plan: L.LogicalPlan, kids) -> L.LogicalPlan:
    """Reconstruct a node over new children (re-binding expressions)."""
    if isinstance(plan, L.Project):
        return L.Project(kids[0], plan.exprs)
    if isinstance(plan, L.Filter):
        return L.Filter(kids[0], plan.condition)
    if isinstance(plan, L.Aggregate):
        return L.Aggregate(kids[0], plan.keys, plan.aggs)
    if isinstance(plan, L.Sort):
        return L.Sort(kids[0], plan.orders, plan.global_sort)
    if isinstance(plan, L.Limit):
        return L.Limit(kids[0], plan.n)
    if isinstance(plan, L.Union):
        return L.Union(kids)
    if isinstance(plan, L.Join):
        return L.Join(kids[0], kids[1], plan.left_keys, plan.right_keys,
                      plan.how, condition=plan.condition)
    if isinstance(plan, L.WindowOp):
        return L.WindowOp(kids[0], plan.wcols)
    if isinstance(plan, L.Repartition):
        return L.Repartition(kids[0], plan.num_partitions, plan.keys)
    return plan


def extract_conjuncts(cond: Expression):
    """Pull (name, op, literal) conjuncts usable for row-group stats
    pruning out of a condition; non-matching branches are skipped (they
    simply don't prune)."""
    from ..expr.expressions import (And, ColumnRef, Eq, Ge, Gt, Le, Lt,
                                    Literal)
    out = []

    def walk(e):
        if isinstance(e, And):
            walk(e.children[0])
            walk(e.children[1])
            return
        ops = {Ge: ">=", Gt: ">", Le: "<=", Lt: "<", Eq: "="}
        t = type(e)
        if t in ops and len(e.children) == 2:
            l, r = e.children
            flip = {">=": "<=", ">": "<", "<=": ">=", "<": ">", "=": "="}
            if isinstance(l, ColumnRef) and isinstance(r, Literal) \
                    and r.value is not None:
                out.append((l.name, ops[t], r.value))
            elif isinstance(r, ColumnRef) and isinstance(l, Literal) \
                    and l.value is not None:
                out.append((r.name, flip[ops[t]], l.value))

    walk(cond)
    return out


def _passthrough_names(project: L.Project) -> Set[str]:
    """Output names that are plain same-named column references."""
    out = set()
    for e in project.exprs:
        if isinstance(e, ColumnRef):
            out.add(e.name)
    return out


def _split_conjuncts(cond: Expression):
    from ..expr.expressions import And
    if isinstance(cond, And):
        return (_split_conjuncts(cond.children[0])
                + _split_conjuncts(cond.children[1]))
    return [cond]


def _and_all(preds):
    from ..expr.expressions import And
    out = preds[0]
    for p in preds[1:]:
        out = And(out, p)
    return out


def extract_within(cond: Expression, names: Set[str]):
    """Weaker predicate IMPLIED by `cond` that references only `names`,
    or None (Spark's extractPredicatesWithinOutputSet): And keeps either
    side, Or needs both. Lets an OR-of-ANDs filter like TPC-H q7's
    (supp='FR' AND cust='DE') OR (supp='DE' AND cust='FR') push
    supp IN ('FR','DE') below the join."""
    from ..expr.expressions import And, Or
    if isinstance(cond, And):
        a = extract_within(cond.children[0], names)
        b = extract_within(cond.children[1], names)
        if a is not None and b is not None:
            return And(a, b)
        return a if a is not None else b
    if isinstance(cond, Or):
        a = extract_within(cond.children[0], names)
        b = extract_within(cond.children[1], names)
        if a is not None and b is not None:
            return Or(a, b)
        return None
    refs = refs_of(cond)
    if refs is not None and refs <= names:
        return cond
    return None


def push_filters(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Sink Filters below pass-through Projects and into Join sides:
    whole one-sided conjuncts move (and are removed above); derived
    OR-extracted predicates are ADDED below while the original filter
    stays (necessary-not-sufficient)."""
    kids = [push_filters(c) for c in plan.children]
    plan = _rebuild(plan, kids)
    if not isinstance(plan, L.Filter):
        return plan
    child = plan.child
    refs = refs_of(plan.condition)
    if refs is None:
        return plan
    if isinstance(child, L.Project) and refs <= _passthrough_names(child):
        return L.Project(
            push_filters(L.Filter(child.child, plan.condition)),
            child.exprs)
    if isinstance(child, L.Join):
        lnames = set(child.left.schema.names)
        rnames = set(child.right.schema.names)
        # names present on BOTH sides are ambiguous in the join output:
        # a conjunct touching one stays above; one-side-only conjuncts
        # still push (the common on=['k'] natural-join shape)
        shared = lnames & rnames
        lonly = lnames - shared
        ronly = rnames - shared
        left_ok = child.how in ("inner", "left", "left_semi", "left_anti")
        right_ok = child.how in ("inner", "right")
        lparts, rparts, rest = [], [], []
        for c in _split_conjuncts(plan.condition):
            r = refs_of(c)
            if r is not None and r & shared:
                rest.append(c)
            elif r is not None and r <= lnames and left_ok:
                lparts.append(c)
            elif r is not None and r <= rnames and right_ok:
                rparts.append(c)
            else:
                rest.append(c)
        # derived one-sided weakenings of the residual conjuncts
        for c in rest:
            if refs_of(c) is not None and refs_of(c) & shared:
                continue
            if left_ok:
                d = extract_within(c, lonly)
                if d is not None and refs_of(d) != refs_of(c):
                    lparts.append(d)
            if right_ok:
                d = extract_within(c, ronly)
                if d is not None and refs_of(d) != refs_of(c):
                    rparts.append(d)
        if not lparts and not rparts:
            return plan
        new_left = child.left
        new_right = child.right
        if lparts:
            new_left = push_filters(L.Filter(new_left, _and_all(lparts)))
        if rparts:
            new_right = push_filters(L.Filter(new_right,
                                              _and_all(rparts)))
        out = L.Join(new_left, new_right, child.left_keys,
                     child.right_keys, child.how,
                     condition=child.condition)
        if rest:
            return L.Filter(out, _and_all(rest))
        return out
    return plan


def rewrite_distinct_aggs(plan: L.LogicalPlan) -> L.LogicalPlan:
    """count(DISTINCT x) -> two-level hash aggregation (the
    single-distinct-child case of Catalyst's RewriteDistinctAggregates):
    an inner DISTINCT Aggregate over (keys..., x) deduplicates, an outer
    Count over the deduped value finishes. Both levels ride
    HashAggregateExec's bucketed hash pass (incl. the hash-once string
    keying) instead of CollectAggExec's full multi-chunk lexsort — the
    q16 straggler shape. Count skips nulls, so the inner null-x group
    drops out in the outer Count exactly like count(DISTINCT)."""
    from ..expr.aggregates import Count, CountDistinct

    def rewrite(node):
        kids = [rewrite(c) for c in node.children]
        node = _rebuild(node, kids)
        if not (isinstance(node, L.Aggregate) and node.aggs
                and all(type(a) is CountDistinct for _, a in node.aggs)):
            return node
        # one shared distinct child only (multiple distinct children
        # need an Expand; keep those on the sort path)
        if len({repr(a.child) for _, a in node.aggs}) != 1:
            return node
        key_names = [k.name for k in node.keys]
        if len(set(key_names)) != len(key_names):
            return node
        val = "__cd_val"
        if val in key_names:
            return node
        x = node.aggs[0][1].child
        inner = L.Aggregate(node.children[0],
                            node.keys + [Alias(x, val)], [])
        outer = L.Aggregate(inner, [ColumnRef(nm) for nm in key_names],
                            [(nm, Count(ColumnRef(val)))
                             for nm, _ in node.aggs])
        return outer

    return rewrite(plan)


def optimize(plan: L.LogicalPlan, conf=None) -> L.LogicalPlan:
    # Aggregate/Project at the root define their own required set; start
    # unconstrained and let node rules narrow it.
    plan = push_filters(plan)
    if conf is not None:
        from ..config import DISTINCT_AGG_REWRITE, JOIN_REORDER_ENABLED
        if conf.get(DISTINCT_AGG_REWRITE):
            plan = rewrite_distinct_aggs(plan)
        if conf.get(JOIN_REORDER_ENABLED):
            from .cbo import reorder_joins
            plan = reorder_joins(plan, conf)
    return prune(plan, None)
