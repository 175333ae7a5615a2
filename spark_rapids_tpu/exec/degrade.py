"""Graceful device->host degradation for device-kernel failures.

A device kernel that raises a non-OOM, non-cancellation error (a
miscompile, a lost device, an injected fault) used to
fail the whole query. With `sql.exec.degradeToHost.enabled` the
operator instead re-evaluates the FAILED batch on the host interpreter
(the exec/host_fallback path), and after ``FAILURE_THRESHOLD`` device
failures on the same program stops dispatching to the device for the
remainder of the query. OOM stays with the split-retry layer
(memory/retry.py) and cancellation always propagates — degradation
must never override an explicit decision.

Each host-recovered batch counts in the operator's ``degradedToHost``
metric (EXPLAIN ANALYZE shows it); the moment an operator pins to the
host path a ``degrade_to_host`` event is queued on the ExecContext and
drained into the query's event log.
"""
from __future__ import annotations

import pyarrow as pa

from ..columnar.table import Table
from .batch import DeviceBatch

__all__ = ["should_degrade", "host_filter_batch", "host_project_batch",
           "host_fused_batch", "hostable_fused", "FAILURE_THRESHOLD"]

#: device failures on the same program before the operator stops
#: trying the device at all for this query
FAILURE_THRESHOLD = 2


def should_degrade(ctx, node, e: BaseException) -> bool:
    """Classify one device-kernel failure for `node`. True → the
    caller recovers this batch on the host path; False → the error
    must propagate (OOM belongs to split-retry, cancellation to the
    service, and everything propagates when the conf gate is off)."""
    from ..memory.retry import is_oom_error
    if is_oom_error(e):
        return False
    try:
        from ..service.query_manager import QueryCancelled
        if isinstance(e, QueryCancelled):
            return False
    except ImportError:                      # pragma: no cover
        pass
    from ..config import DEGRADE_TO_HOST
    if not bool(ctx.conf.get(DEGRADE_TO_HOST)):
        return False
    op_id = node._op_id
    n = ctx.device_failures.get(op_id, 0) + 1
    ctx.device_failures[op_id] = n
    from ..runtime.faults import note_recovery
    note_recovery("degradations")
    if n >= FAILURE_THRESHOLD and op_id not in ctx.degraded:
        # pin to host for the remainder of the query + tell the log
        ctx.degraded[op_id] = True
        ctx.pending_events.append({
            "event": "degrade_to_host", "op": type(node).__name__,
            "op_id": op_id, "failures": n, "error": repr(e)})
        # zero-length marker span: the DECISION is instant, the cost
        # (host re-execution) shows up as compute — but the trace must
        # say the query crossed onto the recovery path
        from ..profiler import tracing
        with tracing.span("degrade.to_host", "degrade", ctx,
                          op=type(node).__name__, failures=n):
            pass
    return True


def host_filter_batch(node, batch: DeviceBatch):
    """HostFilterExec's body for ONE batch: evaluate the bound
    condition over host rows, return the filtered DeviceBatch (None
    when no rows survive)."""
    from ..expr.host_eval import host_eval_rows
    from .host_fallback import _batch_rows
    at, rows = _batch_rows(batch)
    if not rows:
        return None
    keep = host_eval_rows(node.bound, rows)
    mask = pa.array([bool(k) if k is not None else False for k in keep])
    filtered = at.filter(mask)
    if filtered.num_rows == 0:
        return None
    return DeviceBatch(Table.from_arrow(filtered), filtered.num_rows)


def hostable_fused(node) -> bool:
    """True when every member of a FusedStageExec has a host
    equivalent (filters and projections — the only fusable narrow
    operators); a chain with anything else must propagate its device
    error instead of degrading."""
    return all(type(m).__name__ in ("FilterExec", "ProjectExec")
               for m in node.members)


def host_fused_batch(node, batch: DeviceBatch):
    """A FusedStageExec's member chain for ONE batch, run bottom-up on
    the host interpreter. Returns None when no rows survive a member
    filter."""
    for m in node._exec_order:
        if type(m).__name__ == "FilterExec":
            batch = host_filter_batch(m, batch)
            if batch is None:
                return None
        else:
            batch = host_project_batch(m, batch)
    return batch


def host_project_batch(node, batch: DeviceBatch):
    """HostProjectExec's body for ONE batch: evaluate every bound
    output expression over host rows, return the projected
    DeviceBatch."""
    from ..columnar.dtypes import to_arrow as dt_to_arrow
    from ..expr.host_eval import host_eval_rows
    from .host_fallback import _batch_rows
    at, rows = _batch_rows(batch)
    arrays = []
    for e, f in zip(node.bound, node.schema.fields):
        vals = host_eval_rows(e, rows)
        arrays.append(pa.array(vals, dt_to_arrow(f.dtype)))
    out = (pa.Table.from_arrays(arrays, names=list(node.schema.names))
           if arrays else pa.table({}))
    return DeviceBatch(Table.from_arrow(out), out.num_rows)
