"""Hash-aggregate execution: ungrouped reductions and grouped aggregation.

Reference algorithm (GpuAggregateExec.scala:863-894): first-pass per-batch
aggregation, then merge passes until one batch remains. TPU-first redesign:
grouping is *sort-based segmented reduction* — radix-normalized keys,
stable lexsort, keys and aggregate inputs riding one sort into key order,
boundary flags -> runs, segmented scans over the runs (`ops/groups.py`;
no row is fetched by index and no group reduced by scatter) — all
static-shape and fused into one XLA program per pass, instead of cudf's
dynamic hash tables. Capacity stays constant through a pass; dead
(filtered/padding) rows sort to the back as their own runs and are
masked out of the output.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.column import bucket_capacity
from ..columnar.table import Schema
from ..expr.aggregates import AggExpr
from ..expr.expressions import EmitCtx, Expression, UnsupportedExpr
from ..ops import sortkeys as sk
from ..ops.concat import concat_cvs, concat_masks, pad_cv, pad_mask
from ..ops.gather import take, take_strings
from ..ops.groups import RunGroups, ScatterGroups
from ..ops.kernel_utils import CV
from ..ops.partition import sorted_by_target, word_count
from ..profiler import tracing, xla_stats
from ..utils.transfer import fetch_int
from .base import ExecContext, TpuExec
from .batch import DeviceBatch
from .nodes import make_table

__all__ = ["UngroupedAggExec", "HashAggregateExec"]

# Hash-bucket first pass: O(n) scatter-reduce into this many buckets per
# round (no sort), with exact per-bucket key verification; rows whose
# bucket is owned by a different key retry the next round under a new
# seed, and any survivors fall back to the sort path. The TPU answer to
# cudf's hash groupby (reference: GpuAggregateExec first pass).
_HASH_BUCKETS = 4096
_HASH_ROUNDS = 2
_HASH_BUCKETS_MAX = 1 << 18


def _hash_buckets_for(cap: int) -> int:
    """Adaptive bucket count: ~cap/4 buckets keeps the load factor low
    enough that two rep-verify rounds absorb high-cardinality batches
    (fixed 4096 buckets sent every >8k-group batch to the sort path —
    q10's 15k customer groups cost 3s/batch there)."""
    b = _HASH_BUCKETS
    target = min(cap // 4, _HASH_BUCKETS_MAX)
    while b < target:
        b <<= 1
    return b


class UngroupedAggExec(TpuExec):
    """Reduction without grouping keys -> one row.

    The filter/project chain below collapses into the update program
    (collapse_fusable) and the cross-batch merge folds in too: ONE jitted
    dispatch per batch instead of one per operator — the whole-stage-fusion
    answer to the reference's per-kernel cudf dispatch (§3.3 hot loop)."""

    # the update program collapses the child chain itself; the fusion
    # pass must not wrap that prefix in a FusedStage (plan/fusion.py)
    fuses_child_chain = True

    def __init__(self, child: TpuExec, agg_names: Sequence[str],
                 bound_aggs: Sequence[AggExpr], schema: Schema):
        super().__init__([child], schema)
        self.agg_names = list(agg_names)
        self.aggs = list(bound_aggs)
        # fusion resolves lazily at first execute: children may be wrapped
        # after plan construction (LORE dump pass-throughs)
        self._base = None
        self._stages = None
        self._n_fused = 0

        def _update(cvs, mask):
            cvs, mask = self._stages(cvs, mask)
            ctx = EmitCtx(cvs, mask.shape[0])
            states = []
            for a in self.aggs:
                if a.child is not None:
                    cv = a.child.emit(ctx)
                else:
                    cv = CV(jnp.zeros(mask.shape[0], jnp.int8),
                            jnp.ones(mask.shape[0], jnp.bool_))
                states.append(a.update(cv, mask))
            return states

        def _update_merge(acc, cvs, mask):
            st = _update(cvs, mask)
            return [a.merge(x, y) for a, x, y in zip(self.aggs, acc, st)]

        def _finalize(states):
            out = []
            for a, s in zip(self.aggs, states):
                v, ok = a.finalize(s)
                if isinstance(v, CV):
                    out.append((v, jnp.reshape(ok, (1,))))
                else:
                    out.append((jnp.reshape(v, (1,) + tuple(v.shape)),
                                jnp.reshape(ok, (1,))))
            return out

        from ..runtime.program_cache import cached_program, exprs_fp
        self._aggs_fp = exprs_fp(self.aggs)
        # update programs inline self._stages, whose fingerprint is only
        # known after _resolve_fusion — built there
        self._raw_update = _update
        self._raw_update_merge = _update_merge
        self._update_jit = None
        self._update_merge_jit = None
        self._finalize_jit = cached_program(
            _finalize, cls="UngroupedAggExec", tag="finalize",
            key=(self._aggs_fp,))

    def num_partitions(self, ctx):
        return 1

    def describe(self):
        fused = f", fused_stages={self._n_fused}" if self._n_fused else ""
        return f"UngroupedAggExec[{self.agg_names}{fused}]"

    def _resolve_fusion(self):
        if self._base is None:
            from .base import collapse_fusable
            self._base, self._stages, self._n_fused = collapse_fusable(
                self.children[0])
            from ..runtime.program_cache import cached_program
            key = (self._aggs_fp,
                   getattr(self._stages, "_stage_fp",
                           ("inst", id(self))))
            self._update_jit = cached_program(
                self._raw_update, cls="UngroupedAggExec", tag="update",
                key=key)
            self._update_merge_jit = cached_program(
                self._raw_update_merge, cls="UngroupedAggExec",
                tag="update_merge", key=key, donate_argnums=(0,))
            self._whole_key = key

    def _whole_input_program(self):
        """ONE dispatch for the whole HBM-resident input: every batch is an
        argument, the per-batch update/merge loop unrolls inside a single
        XLA program, and finalize folds in too — zero per-batch Python
        round-trips (the deepest whole-stage fusion)."""
        def run(batches):
            acc = None
            for cvs, mask in batches:
                cvs2, mask2 = self._stages(list(cvs), mask)
                ctx = EmitCtx(cvs2, mask2.shape[0])
                st = []
                for a in self.aggs:
                    if a.child is not None:
                        cv = a.child.emit(ctx)
                    else:
                        cv = CV(jnp.zeros(mask2.shape[0], jnp.int8),
                                jnp.ones(mask2.shape[0], jnp.bool_))
                    st.append(a.update(cv, mask2))
                acc = st if acc is None else [
                    a.merge(x, y) for a, x, y in zip(self.aggs, acc, st)]
            out = []
            for a, s in zip(self.aggs, acc):
                v, ok = a.finalize(s)
                if isinstance(v, CV):
                    out.append((v, jnp.reshape(ok, (1,))))
                else:
                    out.append((jnp.reshape(v, (1,) + tuple(v.shape)),
                                jnp.reshape(ok, (1,))))
            return out
        from ..runtime.program_cache import cached_program
        return cached_program(run, cls="UngroupedAggExec", tag="whole",
                              key=self._whole_key)

    def _try_whole_input(self, ctx, m):
        """Single-dispatch path for an HBM-resident child; returns
        finalized outputs or None. No copies: batch buffers pass as
        program arguments."""
        from .nodes import CachedScanExec
        if not isinstance(self._base, CachedScanExec):
            return None
        with tracing.span("agg.whole_args", "op"):
            batches = self._base.whole_input(ctx)
            if not batches or len(batches) > 64:  # unroll bound
                return None
            if not hasattr(self, "_whole_jit"):
                self._whole_jit = self._whole_input_program()
            args = tuple((tuple(b.cvs()), b.row_mask) for b in batches)
        with m.timer("opTime"):
            out = self._whole_jit(args)
        xla_stats.count_dispatch()
        return out

    def execute_partition(self, ctx: ExecContext, pid: int):
        self._resolve_fusion()
        m = ctx.metrics_for(self._op_id)
        child = self._base
        stacked_out = self._try_whole_input(ctx, m)
        if stacked_out is not None:
            tbl = make_table(self.schema, _pad_one_row(stacked_out), 1)
            m.add("numOutputRows", 1)
            yield DeviceBatch(tbl, 1)
            return
        acc = None
        for cpid in range(child.num_partitions(ctx)):
            for batch in child.execute_partition(ctx, cpid):
                ctx.check_cancel()
                with m.timer("opTime"):
                    if acc is None:
                        acc = self._update_jit(batch.cvs(), batch.row_mask)
                    else:
                        acc = self._update_merge_jit(acc, batch.cvs(),
                                                     batch.row_mask)
                xla_stats.count_dispatch()
        if acc is None:
            # aggregate over empty input still yields one row (stages run
            # over all-dead base-schema columns)
            cvs = [CV(jnp.zeros(128, f.dtype.np_dtype or jnp.int8),
                      jnp.zeros(128, jnp.bool_),
                      jnp.zeros(129, jnp.int32)
                      if f.dtype.is_variable_width else None)
                   for f in self._base.schema.fields]
            acc = self._update_jit(cvs, jnp.zeros(128, jnp.bool_))
            xla_stats.count_dispatch()
        outs = self._finalize_jit(acc)
        xla_stats.count_dispatch()
        tbl = make_table(self.schema, _pad_one_row(outs), 1)
        m.add("numOutputRows", 1)
        yield DeviceBatch(tbl, 1)


def _pad_one_row(outs):
    """1-row (capacity-128-padded) output columns from finalized
    (value, ok) pairs; array-valued finalizes arrive as CVs with
    offsets+child already built."""
    cvs = []
    pad = 128 - 1
    with tracing.span("agg.pad", "op"):
        for (v, ok) in outs:
            valid = jnp.concatenate(
                [jnp.reshape(ok, (1,)).astype(jnp.bool_),
                 jnp.zeros(pad, jnp.bool_)])
            if isinstance(v, CV):
                off = v.offsets
                off_p = jnp.concatenate(
                    [off, jnp.full((pad,), off[-1], off.dtype)])
                cvs.append(CV(v.data, valid, off_p, v.children))
            else:
                data = jnp.concatenate(
                    [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
                cvs.append(CV(data, valid))
    return cvs


def _seg_ident(kind: str, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if kind == "min" else -jnp.inf
    if dtype == jnp.bool_:
        return kind == "min"
    return jnp.iinfo(dtype).max if kind == "min" else jnp.iinfo(dtype).min


def _seg_reduce(reducer: str, arr, live, groups):
    if reducer == "sum":
        return groups.sum(jnp.where(live, arr, jnp.zeros_like(arr)))
    if reducer == "or":
        return groups.any(live & arr.astype(jnp.bool_))
    if reducer == "min":
        return groups.min(jnp.where(live, arr, _seg_ident("min", arr.dtype)))
    if reducer == "max":
        return groups.max(jnp.where(live, arr, _seg_ident("max", arr.dtype)))
    raise ValueError(reducer)


def _col_struct(dtype: dt.DataType, cap: int = 1):
    """Shape and dtype of a fixed-width column's data array."""
    shape = (cap, 2) if (isinstance(dtype, dt.DecimalType)
                         and dtype.is_decimal128) else (cap,)
    return jax.ShapeDtypeStruct(shape, dtype.np_dtype or jnp.int8)


_NP2DT = None


def _dtype_for_np(npdt) -> dt.DataType:
    global _NP2DT
    if _NP2DT is None:
        import numpy as np
        _NP2DT = {np.dtype(np.bool_): dt.BOOL, np.dtype(np.int8): dt.INT8,
                  np.dtype(np.int16): dt.INT16, np.dtype(np.int32): dt.INT32,
                  np.dtype(np.int64): dt.INT64,
                  np.dtype(np.float32): dt.FLOAT32,
                  np.dtype(np.float64): dt.FLOAT64}
    import numpy as np
    return _NP2DT[np.dtype(npdt)]


def _packed_eq_arrays(key_cvs, keys, nchunks):
    """Per-column equality key arrays (null flag + order keys) with
    adjacent uint32 chunk words packed into uint64: halves the
    rep-gather + compare count in the hash-pass verify step."""
    out = []
    for kcv, kexpr, nc in zip(key_cvs, keys, nchunks):
        arrs = [jnp.logical_not(kcv.validity).astype(jnp.uint8)]
        arrs += sk.order_keys(kcv, kexpr.dtype, nc)
        packed = []
        i = 0
        while i < len(arrs):
            a = arrs[i]
            if (a.dtype == jnp.uint32 and i + 1 < len(arrs)
                    and arrs[i + 1].dtype == jnp.uint32):
                packed.append((a.astype(jnp.uint64) << 32)
                              | arrs[i + 1].astype(jnp.uint64))
                i += 2
            else:
                packed.append(a)
                i += 1
        out.append(packed)
    return out


def _remix_round(h1, r: int):
    """Round-r bucket hash from the base row hash: integer finalizer
    mix, so only round 0 pays the O(bytes) key walk."""
    if r == 0:
        return h1
    hm = h1.astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9 * r)
    hm = hm * jnp.uint32(0x85EBCA6B)
    hm = hm ^ (hm >> 13)
    return (hm * jnp.uint32(0xC2B2AE35)).astype(jnp.int32)


class HashAggregateExec(TpuExec):
    """Grouped aggregation via segmented reduction over sorted keys.

    Modes (reference: GpuHashAggregateExec partial/final around
    GpuShuffleExchangeExec, GpuAggregateExec.scala:1942):
      complete      — drain every child partition, merge, finalize (1 out).
      per_partition — child is key-partitioned; each partition aggregates
                      independently to final results.
      partial       — per child partition: first-pass + merges, emit ONE
                      batch of (keys..., state columns...) — the
                      exchange-input side; rows shrink to group count
                      BEFORE any shuffle.
      final         — child delivers partial-format batches (post
                      exchange); merge states and finalize.
    The filter chain below collapses into the first-pass program
    (collapse_fusable): one dispatch per input batch."""

    # the first-pass program collapses the child chain itself (filters
    # only: the collapse keeps column ordinals); the fusion pass leaves
    # that prefix alone (plan/fusion.py)
    fuses_child_chain = True
    fusion_require_ordinals = True

    def __init__(self, child: TpuExec, key_names: Sequence[str],
                 bound_keys: Sequence[Expression], agg_names: Sequence[str],
                 bound_aggs: Sequence[AggExpr], schema: Schema,
                 per_partition: bool = False, mode: Optional[str] = None):
        self.mode = mode or ("per_partition" if per_partition
                             else "complete")
        self.key_names = list(key_names)
        self.keys = list(bound_keys)
        self.agg_names = list(agg_names)
        self.aggs = list(bound_aggs)
        for a in self.aggs:
            if a.state_reducers is None:
                raise UnsupportedExpr(
                    f"{a!r} does not support grouped merge")
            if "custom" in a.state_reducers and not hasattr(
                    a, "g_merge_custom"):
                raise UnsupportedExpr(f"{a!r} lacks g_merge_custom")
            if (a.child is not None and a.child.dtype.is_variable_width
                    and type(a).__name__ not in ("Count",)):
                raise UnsupportedExpr(f"{a!r} over variable-width input")
            # First/Last keep batch order only because concat order IS the
            # stable-sort tiebreak; nothing extra needed here

        if self.mode == "partial":
            schema = self._partial_schema(child.schema)
        super().__init__([child], schema)
        # fusion resolves lazily at first execute (see UngroupedAggExec)
        self._base = None
        self._stages = None
        self._n_fused = 0

        self._update_cache = {}
        self._merge_cache = {}
        from ..runtime.program_cache import cached_program, exprs_fp
        # shared program-cache key material: same keys+aggs from a
        # different DataFrame reuse every grouped-agg program
        self._fp = (exprs_fp(self.keys), exprs_fp(self.aggs))
        self._finalize_jit = cached_program(
            self._finalize_fn, cls="HashAggregateExec", tag="finalize",
            key=self._fp)
        hashable = (dt.BooleanType, dt.ByteType, dt.ShortType,
                    dt.IntegerType, dt.DateType, dt.LongType,
                    dt.TimestampType, dt.DecimalType, dt.FloatType,
                    dt.DoubleType, dt.StringType, dt.BinaryType)
        self._hash_ok = (all(isinstance(k.dtype, hashable)
                             for k in self.keys)
                         # an agg whose g_update sorts internally (t-digest)
                         # would defeat the no-sort hash first pass
                         and all(getattr(a, "sort_free_update", True)
                                 for a in self.aggs))
        self._hash_disabled = False

    # -- partial-state wire schema --------------------------------------
    def _state_structs(self):
        """One list an aggregate of the flat state arrays' shapes and
        dtypes at capacity 128, via abstract evaluation."""
        if getattr(self, "_state_structs_c", None) is None:
            cap = 128
            out = []
            for a in self.aggs:
                cdt = a.child.dtype if a.child is not None else None
                cv = (_col_struct(cdt, cap) if cdt is not None
                      and not cdt.is_variable_width
                      else jax.ShapeDtypeStruct((cap,), jnp.int8))
                vcv = jax.ShapeDtypeStruct((cap,), jnp.bool_)
                seg = jax.ShapeDtypeStruct((cap,), jnp.int32)
                out.append(list(jax.eval_shape(
                    lambda c, v, s: a.g_update(CV(c, v), v,
                                               ScatterGroups(s, cap)),
                    cv, vcv, seg)))
            self._state_structs_c = out
        return self._state_structs_c

    def _state_np_dtypes(self):
        """The flat state array dtypes."""
        return [o.dtype for st in self._state_structs() for o in st]

    def _partial_schema(self, child_schema: Schema) -> Schema:
        from ..columnar.table import Field
        fields = []
        for nm, k in zip(self.key_names, self.keys):
            fields.append(Field(f"_k_{nm}", k.dtype))
        for si, npdt in enumerate(self._state_np_dtypes()):
            fields.append(Field(f"_s{si}", _dtype_for_np(npdt)))
        return Schema(fields)

    @property
    def _wire_schema(self) -> Schema:
        """Partial-state wire schema — the format buffered partials take
        when parked in the spill store, and the format partial mode
        emits over the exchange."""
        if getattr(self, "_wire_schema_c", None) is None:
            self._wire_schema_c = (self.schema if self.mode == "partial"
                                   else self._partial_schema(None))
        return self._wire_schema_c

    # -- spillable partial buffering (out-of-core aggregation) ----------
    def _park(self, store, part):
        """Wrap a (key_cvs, flat_states, seg_live, cap) partial as a
        partial-format DeviceBatch and register it with the spill store,
        so buffered group state demotes to host/disk under HBM pressure
        instead of dying (reference: GpuAggregateExec buffered batches
        are spillable)."""
        from ..memory.retry import retry_no_split
        ks, st, sl, cap = part
        cvs = list(ks) + [CV(s, jnp.ones(cap, jnp.bool_)) for s in st]
        tbl = make_table(self._wire_schema, cvs, cap)
        # parking reserves device budget: retry-after-spill covers the
        # transient-OOM window (AllocationRetryCoverageTracker keeps
        # this class of site inside the retry discipline)
        return retry_no_split(lambda: store.add_batch(
            DeviceBatch(tbl, cap, sl, cap), priority=8))

    def _unpark(self, h, close=True):
        b = h.materialize()
        if close:
            h.close()
        cvs = b.cvs()
        nkeys = len(self.keys)
        return ([cv for cv in cvs[:nkeys]],
                [cv.data for cv in cvs[nkeys:]], b.row_mask, b.capacity)

    def _bucket_slice_fn(self, K: int, seed: int = 0x5EED):
        """Device program extracting one of K disjoint-key hash buckets
        from a partial: live rows whose key hashes to bucket `b` are
        compacted to the front (the repartition half of the reference's
        GpuAggregateExec.scala:863-894 fallback). `seed` varies per
        recursion level — re-splitting an oversized bucket with the same
        seed would put every row back in one bucket."""
        from ..ops.gather import compact
        from ..ops.hash import partition_ids
        key_dtypes = [k.dtype for k in self.keys]

        def fn(ks, st, sl, b):
            pids = partition_ids(ks, key_dtypes, K, seed=seed)
            mask_b = sl & (pids == b)
            cvs_all = list(ks) + [CV(s, jnp.ones_like(sl)) for s in st]
            out_cvs, count = compact(cvs_all, mask_b)
            nkeys = len(ks)
            return (out_cvs[:nkeys],
                    [cv.data for cv in out_cvs[nkeys:]], count)
        from ..runtime.program_cache import cached_program
        return cached_program(fn, cls="HashAggregateExec", tag="bslice",
                              key=self._fp + (K, seed))

    def _shrink_to(self, ks, st, nlive: int):
        """Slice a live-prefix partial down to a bucketed capacity."""
        new_cap = bucket_capacity(max(nlive, 1))
        cur = ks[0].validity.shape[0] if ks else (
            st[0].shape[0] if st else new_cap)
        new_cap = min(new_cap, cur)
        idx = jnp.arange(new_cap)
        in_bounds = idx < nlive
        ks2 = []
        for kcv in ks:
            if kcv.offsets is not None:
                nbytes = fetch_int(kcv.offsets[nlive]) if nlive else 0
                byte_cap = bucket_capacity(max(nbytes, 1))
                byte_cap = min(byte_cap, kcv.data.shape[0])
                ks2.append(take_strings(kcv, idx, in_bounds=in_bounds,
                                        out_data_capacity=byte_cap))
            else:
                ks2.append(CV(kcv.data[:new_cap], kcv.validity[:new_cap]))
        st2 = [s[:new_cap] for s in st]
        return (ks2, st2, idx < nlive, new_cap)

    def num_partitions(self, ctx):
        if self.mode in ("per_partition", "partial", "final"):
            return self.children[0].num_partitions(ctx)
        return 1

    def describe(self):
        fused = f", fused_stages={self._n_fused}" if self._n_fused else ""
        return (f"HashAggregateExec[{self.mode}, keys={self.key_names}, "
                f"aggs={self.agg_names}{fused}]")

    # -- sort/segment machinery (runs inside jit) ----------------------
    @staticmethod
    def _custom(a) -> bool:
        """Does `a` reduce by its own scatters (`g_merge_custom`)?"""
        return "custom" in a.state_reducers

    def _reduce_runs(self, key_cvs, mask, nchunks, payload, reduce):
        """THE body of the sort-segmented aggregate, for update and merge
        alike: `(key_out, flat_states, seg_live)` at the input's
        capacity, group k at slot k, live groups first.

        No row is fetched by index and no group is reduced by scatter.
        The key order comes by riding (`sortkeys.lexsort_riding`), the
        permutation becomes a rank by one two-operand sort, and the row
        mask, every fixed-width key column and `payload` (the aggregate
        inputs, or the partial state columns) ride ONE sort by that rank
        as 32-bit words (`ops/partition.py`). A group is then a run:
        `reduce(groups, live, payload)` returns one tuple of state
        columns an aggregate, which `RunGroups` scans; the runs' results
        and keys, standing at each run's last row, go to slot k by one
        more ride. A var-width
        key cannot ride: its chunk words do (for the boundaries), and
        `take` gathers that one column by the permutation. A custom
        reducer still scatters by run number, over sorted inputs
        (`aggScatteredColumns` counts both)."""
        rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
        order = [jnp.logical_not(mask).astype(jnp.uint8)]  # dead rows last
        riders = [mask]
        for kcv, kexpr, nc in zip(key_cvs, self.keys, nchunks):
            words = sk.order_keys(kcv, kexpr.dtype, nc)
            order.append(jnp.logical_not(kcv.validity).astype(jnp.uint8))
            order.extend(words)
            riders.append(kcv.validity)
            riders.extend([kcv.data] if kcv.offsets is None else words)
        perm = sk.lexsort_riding(order)
        rank = jax.lax.sort((perm, rows), num_keys=1)[1]
        nk = len(riders)
        rode = sorted_by_target(rank, riders + list(payload))
        it = iter(rode[:nk])
        live = next(it)
        order = [jnp.logical_not(live).astype(jnp.uint8)]
        keys_rode = []            # (validity, data) a key; data None: var
        for kcv, kexpr, nc in zip(key_cvs, self.keys, nchunks):
            valid = next(it)
            if kcv.offsets is None:
                data = next(it)
                words = sk.order_keys(CV(data, valid), kexpr.dtype)
            else:
                data, words = None, [next(it) for _ in range(nc)]
            order.append(jnp.logical_not(valid).astype(jnp.uint8))
            order.extend(words)
            keys_rode.append((valid, data))
        groups = RunGroups(order, live)
        states = reduce(groups, live, rode[nk:])
        to_slots = [x for vd in keys_rode if vd[1] is not None for x in vd]
        if any(data is None for _, data in keys_rode):
            to_slots.append(perm)
        nk = len(to_slots)
        to_slots += [c for a, st in zip(self.aggs, states)
                     if not self._custom(a) for c in st]
        placed = groups.slots(to_slots)
        it = iter(placed)
        key_out = []
        for kcv, (_, data) in zip(key_cvs, keys_rode):
            if data is None:
                key_out.append(take(kcv, placed[nk - 1],
                                    in_bounds=groups.slot_live))
            else:
                valid = next(it)
                key_out.append(CV(next(it), valid))
        it = iter(placed[nk:])
        flat = []
        for a, st in zip(self.aggs, states):
            flat.extend(st if self._custom(a) else [next(it) for _ in st])
        return key_out, flat, groups.slot_live

    def _ride_shape(self, nchunks, payload):
        """(words, scattered) of one launch of `_reduce_runs` whose
        payload has these shapes and dtypes: the 32-bit words of a row
        that rode a sort (into key order, then to the slots:
        `aggSortWords`), and the state columns still reduced by scatter
        plus the key columns still gathered (`aggScatteredColumns`).
        Reads shapes and dtypes only."""
        flag = jax.ShapeDtypeStruct((1,), jnp.bool_)
        word = jax.ShapeDtypeStruct((1,), jnp.int32)
        var = [isinstance(k.dtype, (dt.StringType, dt.BinaryType))
               for k in self.keys]
        ordered, placed = [flag], []
        for k, nc, v in zip(self.keys, nchunks, var):
            ordered += [flag] + ([word] * nc if v
                                 else [_col_struct(k.dtype)])
            placed += [] if v else [flag, _col_struct(k.dtype)]
        placed += [word] * any(var)
        scattered = sum(var)
        for a, st in zip(self.aggs, self._state_structs()):
            if self._custom(a):
                scattered += len(st)
            else:
                placed += [jax.ShapeDtypeStruct((1,) + o.shape[1:], o.dtype)
                           for o in st]
        return word_count(ordered + list(payload)) + word_count(placed), \
            scattered

    def _count_ride(self, m, nchunks, payload):
        words, scattered = self._ride_shape(nchunks, payload)
        m.add("aggSortWords", words)
        m.add("aggScatteredColumns", scattered)

    def _update_payload(self):
        """Shapes and dtypes of what `_update_fn` lets ride: data and
        validity of each aggregate's input (validity alone where the
        input is var-width, nothing for count(*))."""
        out = []
        for a in self.aggs:
            if a.child is None:
                continue
            if not a.child.dtype.is_variable_width:
                out.append(_col_struct(a.child.dtype))
            out.append(jax.ShapeDtypeStruct((1,), jnp.bool_))
        return out

    def _hash_update_fn(self, nchunks, hash_once: bool = False):
        """Sort-free first pass: bucket rows by key hash, verify each row's
        key against its bucket's representative (canonical order-key
        equality — NaN/-0.0/null exact), segment-reduce matching rows, and
        leave collisions to the next round / sort fallback. Returns
        (key_cvs, flat_states, live, n_leftover) with capacity
        _HASH_ROUNDS * _HASH_BUCKETS.

        With `hash_once` (string keys, sql.agg.stringHashKeys.enabled)
        the bucket hash derives from the SAME packed chunk words the
        verify step compares (xxhash64-style fold, ops/hash.py) — one
        byte pass over the string keys total, instead of murmur3's
        second independent walk. Collisions stay exact: a row matches a
        bucket only when the chunk compare against the representative
        passes; hash collisions fall to the next round / sort path."""
        from ..ops.hash import hash_once_rows, murmur3_row_hash

        def fn(cvs, mask):
            cvs, mask = self._stages(cvs, mask)
            cap = mask.shape[0]
            ctx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ctx) for k in self.keys]
            key_dtypes = [k.dtype for k in self.keys]
            eq_arrays = _packed_eq_arrays(key_cvs, self.keys, nchunks)
            agg_inputs = []
            for a in self.aggs:
                if a.child is not None:
                    agg_inputs.append(a.child.emit(ctx))
                else:
                    agg_inputs.append(CV(jnp.zeros(cap, jnp.int8),
                                         jnp.ones(cap, jnp.bool_)))
            remaining = mask
            rowidx = jnp.arange(cap, dtype=jnp.int32)
            round_keys = []          # per-round rep ROW indices
            round_states = None
            round_live = []
            # hash the full (possibly var-width) keys ONCE; later rounds
            # re-bucket by mixing the base hash with an integer
            # finalizer — O(bytes) work happens a single time
            if hash_once:
                h1 = hash_once_rows(eq_arrays)
            else:
                h1 = murmur3_row_hash(key_cvs, key_dtypes, seed=42)
            for r in range(_HASH_ROUNDS):
                # escalating buckets: round 0 small (low-cardinality
                # batches — the common case — pay only 4096-slot segment
                # ops), later rounds big enough for high-card batches
                B = _HASH_BUCKETS if r == 0 else _hash_buckets_for(cap)
                h = _remix_round(h1, r)
                b = (h.astype(jnp.uint32) % jnp.uint32(B)).astype(jnp.int32)
                repmin = jax.ops.segment_min(
                    jnp.where(remaining, rowidx, cap), b, B)
                has = repmin < cap
                rep = jnp.clip(repmin, 0, cap - 1)
                rep_of_row = rep[b]
                match = remaining
                for arrs in eq_arrays:
                    for arr in arrs:
                        match = match & (arr == arr[rep_of_row])
                states_r = []
                buckets = ScatterGroups(b, B)
                for a, icv in zip(self.aggs, agg_inputs):
                    if icv.offsets is not None:
                        scv = CV(jnp.zeros(cap, jnp.int8), icv.validity)
                    else:
                        scv = icv
                    states_r.append(a.g_update(scv, match, buckets))
                flat_r = [c for s in states_r for c in s]
                round_states = ([[f] for f in flat_r] if round_states is None
                                else [o + [f] for o, f in
                                      zip(round_states, flat_r)])
                # keys are NOT gathered here: only the rep's original ROW
                # INDEX is kept — key materialization (expensive for
                # string keys at B slots) happens once, post-compaction,
                # at live-group scale in update_one
                round_keys.append(rep)
                round_live.append(has)
                remaining = remaining & ~match
            rep_rows = jnp.concatenate(round_keys)
            flat = [jnp.concatenate(parts) for parts in round_states]
            live = jnp.concatenate(round_live)
            leftover = jnp.sum(remaining.astype(jnp.int32))
            n_live = jnp.sum(live.astype(jnp.int32))
            return rep_rows, flat, live, leftover, n_live
        return fn

    def _materialize_hash_partial(self, b, rep_rows, st, sl,
                                  n_live: int):
        """Turn a hash-pass result (rep ROW indices + states + live
        mask over rounds*B slots) into a (keys, states, live, cap)
        partial at bucket_capacity(live). Key columns — expensive for
        strings — gather from the ORIGINAL batch only here, at
        live-group scale, never at bucket scale."""
        from ..ops.gather import compaction_perm, gather_cols
        cap_part = sl.shape[0]
        new_cap = min(bucket_capacity(max(n_live, 1)), cap_part)
        # gather_cols fetches var-width measures internally (host sync),
        # so this stays host-driven; the gathers themselves are jitted
        perm, _ = compaction_perm(sl)
        idx = perm[:new_cap]
        inb = jnp.arange(new_cap) < n_live
        kfn = self._update_cache.get("keyemit")
        if kfn is None:
            def kfn_(cvs, mask):
                cvs2, mask2 = self._stages(cvs, mask)
                ctx = EmitCtx(cvs2, mask2.shape[0])
                return [k.emit(ctx) for k in self.keys]
            from ..runtime.program_cache import cached_program
            kfn = cached_program(kfn_, cls="HashAggregateExec",
                                 tag="keyemit",
                                 key=self._fp + (self._stage_fp,))
            self._update_cache["keyemit"] = kfn
        key_cvs = kfn(b.cvs(), b.row_mask)
        rep2 = rep_rows[idx]
        ks2 = gather_cols(key_cvs, rep2, inb)
        st2 = [s[idx] for s in st]
        return (ks2, st2, inb, new_cap)

    def _update_fn(self, nchunks):
        def fn(cvs, mask):
            cvs, mask = self._stages(cvs, mask)
            cap = mask.shape[0]
            ctx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ctx) for k in self.keys]
            payload = []
            for a in self.aggs:
                if a.child is None:
                    continue
                cv = a.child.emit(ctx)
                if cv.offsets is None:  # var-width: Count uses validity
                    payload.append(cv.data)
                payload.append(cv.validity)

            def reduce(groups, live, rode):
                it = iter(rode)
                states = []
                for a in self.aggs:
                    if a.child is None:
                        scv = CV(jnp.zeros(cap, jnp.int8),
                                 jnp.ones(cap, jnp.bool_))
                    elif a.child.dtype.is_variable_width:
                        scv = CV(jnp.zeros(cap, jnp.int8), next(it))
                    else:
                        scv = CV(next(it), next(it))
                    states.append(a.g_update(scv, live, groups))
                return states
            return self._reduce_runs(key_cvs, mask, nchunks, payload,
                                     reduce)
        return fn

    def _merge_body(self, key_cvs, flat_states, mask, nchunks):
        """Merge partials (in trace; `_merge_partials` jits it as it
        is): the state columns ride into key order and each reduces by
        its `state_reducers`; live groups come out first."""
        def reduce(groups, live, rode):
            states = []
            i = 0
            for a in self.aggs:
                cols = rode[i:i + self._state_width(a)]
                i += len(cols)
                if self._custom(a):
                    states.append(tuple(a.g_merge_custom(cols, live,
                                                         groups)))
                else:
                    states.append(tuple(
                        _seg_reduce(r, c, live, groups)
                        for r, c in zip(a.state_reducers, cols)))
            return states
        return self._reduce_runs(key_cvs, mask, nchunks, flat_states,
                                 reduce)

    @staticmethod
    def _state_width(a) -> int:
        if HashAggregateExec._custom(a):
            return a.num_state_cols()
        return len(a.state_reducers)

    def _finalize_fn(self, key_cvs, flat_states, seg_live):
        outs = list(key_cvs)
        i = 0
        for a in self.aggs:
            k = self._state_width(a)
            s = tuple(flat_states[i:i + k])
            i += k
            v, ok = a.finalize(s)
            if isinstance(v, CV):
                # array-valued finalize (t-digest percentile lists):
                # the agg built offsets+child; AND in group liveness
                outs.append(CV(v.data, v.validity & ok & seg_live,
                               v.offsets, v.children))
            else:
                outs.append(CV(v, ok & seg_live))
        return outs

    # ------------------------------------------------------------------
    def _has_string_keys(self) -> bool:
        return any(isinstance(k.dtype, (dt.StringType, dt.BinaryType))
                   for k in self.keys)

    def _nchunks_for(self, key_cvs, mask) -> Tuple[int, ...]:
        """Static string-chunk counts; measures only live+valid rows so
        dead/padding rows cannot inflate the chunk count."""
        ncs = []
        for kcv, kexpr in zip(key_cvs, self.keys):
            if isinstance(kexpr.dtype, (dt.StringType, dt.BinaryType)):
                lens = kcv.offsets[1:] - kcv.offsets[:-1]
                lens = jnp.where(mask & kcv.validity, lens, 0)
                maxlen = fetch_int((jnp.max(lens))) if \
                    lens.shape[0] else 0
                ncs.append(sk.nchunks_for_len(max(maxlen, 1)))
            else:
                ncs.append(0)
        return tuple(ncs)

    def _batch_nchunks(self, batch: DeviceBatch) -> Tuple[int, ...]:
        """nchunks for an input batch without double-evaluating keys: zero
        for non-string keys; string keys that are plain column refs read
        offsets straight off the batch."""
        if not self._has_string_keys():
            return tuple(0 for _ in self.keys)
        from ..expr.expressions import Alias, BoundRef
        cvs = batch.cvs()
        ncs = []
        for k in self.keys:
            if not isinstance(k.dtype, (dt.StringType, dt.BinaryType)):
                ncs.append(0)
                continue
            e = k.child if isinstance(k, Alias) else k
            if isinstance(e, BoundRef):
                kcv = cvs[e.ordinal]
            else:
                kcv = k.emit(EmitCtx(cvs, batch.capacity))
            lens = kcv.offsets[1:] - kcv.offsets[:-1]
            lens = jnp.where(batch.row_mask & kcv.validity, lens, 0)
            maxlen = fetch_int((jnp.max(lens)))
            ncs.append(sk.nchunks_for_len(max(maxlen, 1)))
        return tuple(ncs)

    def _resolve_fusion(self):
        if self._base is None:
            if self.mode in ("complete", "partial", "per_partition"):
                from .base import collapse_fusable
                self._base, self._stages, self._n_fused = collapse_fusable(
                    self.children[0], require_ordinals=True)
            else:
                self._base, self._n_fused = self.children[0], 0
                self._stages = lambda cvs, mask: (cvs, mask)
                self._stages._stage_fp = ("chain",)
            # tpulint: allow[fp-unstable-attr] id(self) is the documented per-instance fallback key: unshared, never falsely shared
            self._stage_fp = getattr(self._stages, "_stage_fp",
                                     ("inst", id(self)))

    # -- whole-input fused path (HBM-cached child, one device program) --
    def _whole_grouped_program(self, nchunks, opt_cap,
                               hash_once: bool = False):
        """ONE program for the entire cached input: per-batch fused
        stages + key/input emit, concat, sort-segment aggregate, compact
        live groups to opt_cap, finalize — plus (count, overflow) so the
        host can detect optimistic-capacity misses in the same round trip
        (the whole-stage answer to the reference's multi-pass
        GpuAggregateExec when groups are few). `hash_once` derives the
        per-round bucket hashes from the equality chunk words (one byte
        pass over string keys; see _hash_update_fn)."""
        from ..ops.gather import take_strings
        from ..ops.hash import hash_once_rows, murmur3_row_hash
        key_dtypes = [k.dtype for k in self.keys]

        def run(batches):
            # per-batch fused stages + key/input emit, then concat
            key_parts = [[] for _ in self.keys]
            in_parts = [[] for _ in self.aggs]
            masks = []
            for cvs, bmask in batches:
                cvs2, mask2 = self._stages(list(cvs), bmask)
                cap_i = mask2.shape[0]
                ectx = EmitCtx(cvs2, cap_i)
                for ki, k in enumerate(self.keys):
                    key_parts[ki].append(k.emit(ectx))
                for ai, a in enumerate(self.aggs):
                    if a.child is not None:
                        in_parts[ai].append(a.child.emit(ectx))
                    else:
                        in_parts[ai].append(
                            CV(jnp.zeros(cap_i, jnp.int8),
                               jnp.ones(cap_i, jnp.bool_)))
                masks.append(mask2)
            key_cvs = [concat_cvs(ps, k.dtype)
                       for ps, k in zip(key_parts, self.keys)]
            mask = concat_masks(masks)
            cap = mask.shape[0]
            agg_inputs = []
            for parts in in_parts:
                vcat = jnp.concatenate([p.validity for p in parts])
                if parts[0].offsets is not None:
                    agg_inputs.append(CV(jnp.zeros(cap, jnp.int8), vcat))
                else:
                    agg_inputs.append(
                        CV(jnp.concatenate([p.data for p in parts]),
                           vcat))
            # hash rounds (sort-free — XLA device sorts at input scale
            # are the slow path on TPU; bucketed segment reduction is
            # O(rounds * n))
            eq_arrays = _packed_eq_arrays(key_cvs, self.keys, nchunks)
            if hash_once:
                h1 = hash_once_rows(eq_arrays)
            else:
                h1 = murmur3_row_hash(key_cvs, key_dtypes, seed=42)
            B = _HASH_BUCKETS
            remaining = mask
            rowidx = jnp.arange(cap, dtype=jnp.int32)
            round_keys = [[] for _ in self.keys]
            round_states = None
            round_live = []
            for r in range(_HASH_ROUNDS):
                h = _remix_round(h1, r)
                b = (h.astype(jnp.uint32)
                     % jnp.uint32(B)).astype(jnp.int32)
                repmin = jax.ops.segment_min(
                    jnp.where(remaining, rowidx, cap), b, B)
                has = repmin < cap
                rep = jnp.clip(repmin, 0, cap - 1)
                rep_of_row = rep[b]
                match = remaining
                for arrs in eq_arrays:
                    for arr in arrs:
                        match = match & (arr == arr[rep_of_row])
                states_r = []
                buckets = ScatterGroups(b, B)
                for a, icv in zip(self.aggs, agg_inputs):
                    scv = (CV(jnp.zeros(cap, jnp.int8), icv.validity)
                           if icv.offsets is not None else icv)
                    states_r.append(a.g_update(scv, match, buckets))
                flat_r = [c for st_ in states_r for c in st_]
                round_states = ([[f] for f in flat_r]
                                if round_states is None
                                else [o + [f] for o, f in
                                      zip(round_states, flat_r)])
                for ki, (kcv, nc) in enumerate(zip(key_cvs, nchunks)):
                    if kcv.offsets is not None:
                        bcap = min(kcv.data.shape[0],
                                   bucket_capacity(max(B * nc * 4, 4)))
                        round_keys[ki].append(take_strings(
                            kcv, rep, in_bounds=has,
                            out_data_capacity=bcap))
                    else:
                        round_keys[ki].append(take(kcv, rep,
                                                   in_bounds=has))
                round_live.append(has)
                remaining = remaining & ~match
            leftover = jnp.sum(remaining.astype(jnp.int32))
            hk = [concat_cvs(parts, kd)
                  for parts, kd in zip(round_keys, key_dtypes)]
            hflat = [jnp.concatenate(parts) for parts in round_states]
            hlive = jnp.concatenate(round_live)
            # same key can surface in several rounds: one small merge
            # (sort over ROUNDS*BUCKETS rows only) unifies them and puts
            # live groups first
            mk, mflat, mlive = self._merge_body(hk, hflat, hlive,
                                                nchunks)
            sel = jnp.arange(opt_cap, dtype=jnp.int32)
            count = jnp.sum(mlive.astype(jnp.int32))
            overflow = (count > opt_cap) | (leftover > 0)
            sl_c = mlive[sel] if mlive.shape[0] > opt_cap else \
                jnp.pad(mlive, (0, opt_cap - mlive.shape[0]))
            ks_c = []
            for kcv, nc in zip(mk, nchunks):
                if kcv.offsets is not None:
                    bcap = min(kcv.data.shape[0],
                               bucket_capacity(max(opt_cap * nc * 4, 4)))
                    ks_c.append(take_strings(kcv, sel, in_bounds=sl_c,
                                             out_data_capacity=bcap))
                else:
                    ks_c.append(take(kcv, sel, in_bounds=sl_c))
            flat_c = [f[sel] for f in mflat]
            outs = self._finalize_fn(ks_c, flat_c, sl_c)
            return outs, sl_c, count, overflow
        return run

    def _try_whole_input(self, ctx, m):
        """Single-round-trip path: cached child, bounded batch count, no
        retry pressure. Returns a DeviceBatch or None (overflow or
        ineligible)."""
        from ..config import AGG_OPTIMISTIC_GROUPS
        from .nodes import CachedScanExec
        opt_cap = ctx.conf.get(AGG_OPTIMISTIC_GROUPS)
        if (self.mode != "complete" or opt_cap <= 0
                or not self._hash_ok
                or getattr(self, "_whole_disabled", False)
                or not isinstance(self._base, CachedScanExec)):
            return None
        batches = self._base.whole_input(ctx)
        if not batches or len(batches) > 64:
            return None
        if not hasattr(self, "_whole_nchunks"):
            ncs = [self._batch_nchunks(b) for b in batches]
            self._whole_nchunks = tuple(max(t) for t in zip(*ncs))
        from ..config import AGG_STRING_HASH_KEYS
        hash_once = (self._has_string_keys()
                     and bool(ctx.conf.get(AGG_STRING_HASH_KEYS)))
        key = ("whole", self._whole_nchunks, opt_cap, hash_once,
               tuple(b.capacity for b in batches))
        fn = self._update_cache.get(key)
        if fn is None:
            from ..runtime.program_cache import cached_program
            fn = cached_program(
                self._whole_grouped_program(self._whole_nchunks,
                                            opt_cap, hash_once),
                cls="HashAggregateExec", tag="whole",
                key=self._fp + (self._stage_fp, self._whole_nchunks,
                                opt_cap, hash_once))
            self._update_cache[key] = fn
        args = tuple((tuple(b.cvs()), b.row_mask) for b in batches)
        with m.timer("opTime"):
            outs, sl_c, count, overflow = fn(args)
            from ..utils.transfer import fetch
            cnt, ovf = fetch((count, overflow))
        xla_stats.count_dispatch()
        if bool(ovf):
            self._whole_disabled = True
            return None
        tbl = make_table(self.schema, outs, int(cnt))
        m.add("numOutputRows", int(cnt))
        m.add("numOutputBatches", 1)
        return DeviceBatch(tbl, int(cnt), sl_c, sl_c.shape[0])

    def execute_mesh(self, ctx: ExecContext, n: int):
        """The partial aggregate of a mesh plan in lockstep: every
        shard's batch through the sort-and-segment update as ONE program
        over the mesh, each result emitted as a partial batch (the final
        merge behind the exchange takes same-key rows from any number of
        them). Fixed-width keys and traceable reducers only; anything
        else keeps the partition path."""
        if (self.mode != "partial" or self._has_string_keys()
                or any(self._custom(a) for a in self.aggs)):
            return None
        self._resolve_fusion()
        from .lockstep import mesh_batches
        src = mesh_batches(ctx, self._base, n)
        if src is None:
            return None
        from ..parallel.mesh_program import MeshProgram
        nchunks = (0,) * len(self.keys)
        update = self._update_fn(nchunks)
        prog = MeshProgram(
            lambda t: update(*t), n, cls="HashAggregateExec",
            tag="meshupdate", key=self._fp + (self._stage_fp,))
        m = ctx.metrics_for(self._op_id)

        def run():
            for mb in src:
                ctx.check_cancel()
                with m.timer("opTime"):
                    outs = prog(mb.trees())
                xla_stats.count_dispatch()
                self._count_ride(m, nchunks, self._update_payload())
                shards = []
                for b, (ks, st, sl) in zip(mb.shards, outs):
                    cvs = list(ks) + [CV(a, sl) for a in st]
                    shards.append(DeviceBatch(
                        make_table(self.schema, cvs, b.capacity),
                        b.capacity, sl, b.capacity))
                m.add("numOutputBatches", n)
                yield type(mb)(shards)
        return run()

    def execute_partition(self, ctx: ExecContext, pid: int):
        self._resolve_fusion()
        m = ctx.metrics_for(self._op_id)
        child = self._base
        child_pids = ([pid] if self.mode in ("per_partition", "partial",
                                             "final")
                      else range(child.num_partitions(ctx)))

        if self.mode == "final":
            yield from self._execute_final(ctx, pid, m)
            return
        if self.mode == "complete":
            whole = self._try_whole_input(ctx, m)
            if whole is not None:
                yield whole
                return

        from ..config import AGG_STRING_HASH_KEYS
        hash_once = (self._has_string_keys()
                     and bool(ctx.conf.get(AGG_STRING_HASH_KEYS)))

        def update_one(b):
            from .batch import maybe_compact
            b = maybe_compact(b, child.schema)
            nchunks = self._batch_nchunks(b)
            if self._hash_ok and not self._hash_disabled:
                hfn = self._update_cache.get(("hash", nchunks, hash_once))
                if hfn is None:
                    from ..runtime.program_cache import cached_program
                    hfn = cached_program(
                        self._hash_update_fn(nchunks, hash_once),
                        cls="HashAggregateExec", tag="hash_update",
                        key=self._fp + (self._stage_fp, nchunks,
                                        hash_once))
                    self._update_cache[("hash", nchunks, hash_once)] = hfn
                rep_rows, st, sl, leftover, n_live = hfn(b.cvs(),
                                                         b.row_mask)
                xla_stats.count_dispatch()
                from ..utils.transfer import fetch
                lo, nl = (int(v) for v in fetch((leftover, n_live)))
                if lo == 0:
                    return self._materialize_hash_partial(
                        b, rep_rows, st, sl, nl)
                # bucket-collision overflow (high-cardinality batch):
                # fall back to the exact sort path, and stop trying the
                # hash pass for the rest of this query
                self._hash_disabled = True
            fn = self._update_cache.get(nchunks)
            if fn is None:
                from ..runtime.program_cache import cached_program
                fn = cached_program(
                    self._update_fn(nchunks), cls="HashAggregateExec",
                    tag="update",
                    key=self._fp + (self._stage_fp, nchunks))
                self._update_cache[nchunks] = fn
            ks, st, sl = fn(b.cvs(), b.row_mask)
            xla_stats.count_dispatch()
            self._count_ride(m, nchunks, self._update_payload())
            return (ks, st, sl, b.capacity)

        from ..config import AGG_MAX_MERGE_ROWS
        from ..memory.retry import with_retry
        from ..memory.spill import spill_store
        store = spill_store(ctx.conf)
        max_rows = ctx.conf.get(AGG_MAX_MERGE_ROWS)
        handles = []            # (spill handle, capacity)
        buffered = 0
        compactable = True      # do eager merges still shrink the state?
        for cpid in child_pids:
            for batch in child.execute_partition(ctx, cpid):
                ctx.check_cancel()
                with m.timer("opTime"):
                    # split-and-retry: idempotent per-batch first-pass agg
                    # re-executes on halves under memory pressure
                    for part in with_retry(batch, update_one):
                        handles.append((self._park(store, part), part[3]))
                        buffered += part[3]
                if compactable and buffered > max_rows and len(handles) > 1:
                    with m.timer("opTime"):
                        parts = [self._unpark(h) for h, _ in handles]
                        merged = self._merge_partials(parts, m)
                        handles = [(self._park(store, merged), merged[3])]
                        buffered = merged[3]
                        if merged[3] > max_rows // 2:
                            # high cardinality: merging no longer
                            # compacts; buffer spillably and let the
                            # bucket fallback split the final pass
                            compactable = False
        if not handles:
            if self.mode != "partial":
                yield DeviceBatch(make_table(self.schema, [
                    CV(jnp.zeros(128, f.dtype.np_dtype or jnp.int8),
                       jnp.zeros(128, jnp.bool_),
                       jnp.zeros(129, jnp.int32)
                       if f.dtype.is_variable_width else None)
                    for f in self.schema.fields], 0),
                    0, jnp.zeros(128, jnp.bool_), 128)
            return
        yield from self._emit_final(ctx, m, handles)

    # deepest bucket recursion (reference: 10 levels x 16 buckets,
    # GpuAggregateExec.scala:863-894)
    _MAX_BUCKET_DEPTH = 10

    def _emit_final(self, ctx: ExecContext, m, handles,
                    force_merge: bool = False, depth: int = 0):
        """Merge parked partials and emit finalized (or partial-format)
        batches under a bounded merge width: when the buffered group
        state exceeds maxMergeRows, repartition every partial into K
        hash buckets of disjoint keys and merge+emit per bucket,
        RECURSING (fresh hash seed per level) on buckets that still
        exceed the bound — the out-of-core fallback
        (GpuAggregateExec.scala:863-894, 16 buckets x 10 levels).
        Handles are closed on generator exit even when the consumer
        abandons the stream (limit/error)."""
        from ..config import AGG_MAX_MERGE_ROWS
        max_rows = ctx.conf.get(AGG_MAX_MERGE_ROWS)
        total = sum(c for _, c in handles)
        K = 1
        while K < 16 and total > K * max_rows:
            K *= 2
        emit_partial = self.mode == "partial"
        if K == 1:
            with m.timer("opTime"):
                parts = [self._unpark(h) for h, _ in handles]
                if (len(parts) > 1 or force_merge
                        or (not emit_partial and parts[0][3] > 4096)):
                    # the merge pass also sorts live groups first and
                    # compacts the output to the group count
                    part = self._merge_partials(parts, m)
                else:
                    part = parts[0]
                out = self._emit_batch(part, m, emit_partial)
            yield out
            return
        seed = (0x5EED ^ (depth * 0x9E3779B9)) & 0x7FFFFFFF
        fn = self._update_cache.get(("bslice", K, seed))
        if fn is None:
            fn = self._bucket_slice_fn(K, seed)
            self._update_cache[("bslice", K, seed)] = fn
        from ..memory.spill import spill_store
        store = spill_store(ctx.conf)
        open_handles = {h for h, _ in handles}
        try:
            for b in range(K):
                sub = None
                with m.timer("opTime"):
                    parts_b = []
                    for h, _ in handles:
                        close = (b == K - 1) and h in open_handles
                        ks, st, sl, cap = self._unpark(h, close=close)
                        if close:
                            open_handles.discard(h)
                        oks, ost, cnt = fn(ks, st, sl, jnp.int32(b))
                        nlive = fetch_int(cnt)
                        if nlive == 0:
                            continue
                        parts_b.append(self._shrink_to(oks, ost, nlive))
                    if not parts_b:
                        continue
                    bucket_rows = sum(p[3] for p in parts_b)
                    if (bucket_rows > max_rows
                            and depth + 1 < self._MAX_BUCKET_DEPTH
                            and bucket_rows < total):
                        # still oversized: park this bucket's parts and
                        # recurse with a fresh seed. The bucket_rows <
                        # total guard stops degenerate recursion when one
                        # key dominates (re-splitting can't shrink it).
                        sub = [(self._park(store, p), p[3])
                               for p in parts_b]
                        m.add("numBucketRecursions", 1)
                    else:
                        part = self._merge_partials(parts_b, m)
                        out = self._emit_batch(part, m, emit_partial)
                if sub is not None:
                    yield from self._emit_final(
                        ctx, m, sub, force_merge, depth + 1)
                else:
                    yield out
        finally:
            for h in open_handles:
                h.close()

    def _emit_batch(self, part, m, emit_partial: bool) -> DeviceBatch:
        ks, st, sl, cap = part
        if emit_partial:
            cvs = list(ks) + [CV(s, jnp.ones(cap, jnp.bool_)) for s in st]
            tbl = make_table(self.schema, cvs, cap)
            m.add("numOutputBatches", 1)
            return DeviceBatch(tbl, cap, sl, cap)
        outs = self._finalize_jit(ks, st, sl)
        xla_stats.count_dispatch()
        tbl = make_table(self.schema, outs, cap)
        m.add("numOutputBatches", 1)
        return DeviceBatch(tbl, cap, sl, cap)

    def _execute_final(self, ctx: ExecContext, pid: int, m):
        """Merge partial-format batches (keys + state columns) arriving
        from the exchange, then finalize — the final-mode half of the
        partial/final split. Arriving batches buffer spillably; a merge
        pass ALWAYS runs (a single exchanged batch still holds same-key
        partial rows from different map partitions), bucket-split when
        the combined state exceeds the merge bound."""
        from ..memory.spill import spill_store
        store = spill_store(ctx.conf)
        handles = []
        from ..memory.retry import retry_no_split
        for batch in self.children[0].execute_partition(ctx, pid):
            ctx.check_cancel()
            handles.append((retry_no_split(
                lambda b=batch: store.add_batch(b, priority=8)),
                batch.capacity))
        if not handles:
            return
        yield from self._emit_final(ctx, m, handles, force_merge=True)

    def _merge_partials(self, partials, m=None):
        if len(partials) == 1:
            ks, st, sl, cap = partials[0]
        else:
            cap = sum(p[3] for p in partials)
            nkeys = len(self.keys)
            ks = []
            for ki in range(nkeys):
                parts = [p[0][ki] for p in partials]
                ks.append(concat_cvs(parts, self.keys[ki].dtype))
            nst = len(partials[0][1])
            st = [jnp.concatenate([p[1][si] for p in partials])
                  for si in range(nst)]
            sl = concat_masks([p[2] for p in partials])
        nchunks = self._nchunks_for(ks, sl)
        fn = self._merge_cache.get(nchunks)
        if fn is None:
            from ..runtime.program_cache import cached_program
            fn = cached_program(
                lambda ks, st, sl: self._merge_body(ks, st, sl, nchunks),
                cls="HashAggregateExec", tag="merge",
                key=self._fp + (nchunks,))
            self._merge_cache[nchunks] = fn
        ks2, st2, sl2 = fn(ks, st, sl)
        xla_stats.count_dispatch()
        if m is not None:
            self._count_ride(m, nchunks, st)
        return self._compact_partial(ks2, st2, sl2)

    def _compact_partial(self, ks, st, sl):
        """Shrink a merged partial to a capacity sized by live group count.

        Merge output sorts live rows first, so live segments occupy the
        prefix [0, nlive); without this, the buffered partial stays at the
        concatenated input capacity and grows with total input rows even
        when there are few groups (reference shrinks on merge too:
        GpuAggregateExec.scala:863-894 repartition buckets)."""
        cap = sl.shape[0]
        nlive = fetch_int(jnp.sum(sl.astype(jnp.int32)))
        new_cap = bucket_capacity(max(nlive, 1))
        if new_cap >= cap:
            return (ks, st, sl, cap)
        idx = jnp.arange(new_cap)
        in_bounds = idx < nlive
        ks2 = []
        for kcv in ks:
            if kcv.offsets is not None:
                nbytes = fetch_int(kcv.offsets[nlive])
                byte_cap = bucket_capacity(max(nbytes, 1))
                byte_cap = min(byte_cap, kcv.data.shape[0])
                ks2.append(take_strings(kcv, idx, in_bounds=in_bounds,
                                        out_data_capacity=byte_cap))
            else:
                ks2.append(CV(kcv.data[:new_cap], kcv.validity[:new_cap]))
        st2 = [s[:new_cap] for s in st]
        return (ks2, st2, sl[:new_cap], new_cap)


class CollectAggExec(TpuExec):
    """Grouped aggregation when any aggregate is collect_list/collect_set.

    One stable sort of the partition's rows by (keys [, value for sets])
    makes each group's values contiguous: the sorted value column IS the
    concatenated list child, group-count cumsums are the offsets. Plain
    aggregates in the same GROUP BY ride the identical segmentation.
    (reference: GpuCollectList/GpuCollectSet in aggregateFunctions.scala,
    executed via cudf groupby collect; here the sort-segmented design means
    collect costs one value gather beyond the regular agg sort.)

    Distributed: the planner hash-exchanges input rows on the grouping keys
    first, so per_partition collects are final (disjoint keys).
    """

    def __init__(self, child: TpuExec, key_names, bound_keys, agg_names,
                 bound_aggs, schema: Schema, per_partition: bool = False):
        super().__init__([child], schema)
        self.key_names = list(key_names)
        self.keys = list(bound_keys)
        self.agg_names = list(agg_names)
        self.aggs = list(bound_aggs)
        self.per_partition = per_partition
        from ..runtime.program_cache import exprs_fp
        self._fp = (exprs_fp(self.keys), exprs_fp(self.aggs))
        self._run_cache = {}  # local memo over CachedProgram wrappers

    def num_partitions(self, ctx):
        if self.per_partition:
            return self.children[0].num_partitions(ctx)
        return 1

    def describe(self):
        return (f"CollectAggExec[keys={self.key_names}, "
                f"aggs={self.agg_names}]")

    def _value_nchunks(self, cvs, mask):
        """Static order-key chunk counts for string-typed collect_set
        values (dedup needs full-width comparisons)."""
        cap = mask.shape[0]
        ctx = EmitCtx(cvs, cap)
        ncs = []
        for a in self.aggs:
            if getattr(a, "is_set", False) and isinstance(
                    a.child.dtype, (dt.StringType, dt.BinaryType)):
                ncs.append(sk.string_nchunks(a.child.emit(ctx), mask))
            else:
                ncs.append(0)
        return tuple(ncs)

    def _key_nchunks(self, cvs, mask):
        cap = mask.shape[0]
        ctx = EmitCtx(cvs, cap)
        ncs = []
        for k in self.keys:
            if isinstance(k.dtype, (dt.StringType, dt.BinaryType)):
                ncs.append(sk.string_nchunks(k.emit(ctx), mask))
            else:
                ncs.append(0)
        return tuple(ncs)

    def _run_fn(self, nchunks, vnchunks):
        def fn(cvs, mask):
            cap = mask.shape[0]
            ctx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ctx) for k in self.keys]
            arrays = [jnp.logical_not(mask).astype(jnp.uint8)]  # dead last
            key_arrays = []
            for kcv, kexpr, nc in zip(key_cvs, self.keys, nchunks):
                ka = [jnp.logical_not(kcv.validity).astype(jnp.uint8)]
                ka += sk.order_keys(kcv, kexpr.dtype, nc)
                key_arrays.extend(ka)
                arrays.extend(ka)
            perm = sk.lexsort(arrays)
            keys_sorted = [a_[perm] for a_ in key_arrays]
            dead_sorted = arrays[0][perm]
            boundary = sk.group_boundaries([dead_sorted] + keys_sorted)
            seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            live = mask[perm]
            seg_live = jax.ops.segment_max(live.astype(jnp.int32),
                                           seg_ids, cap) > 0
            if not self.keys:
                # ungrouped sort-path aggregates (count(DISTINCT x),
                # median, ...): every live row is one segment; an
                # all-dead batch still emits row 0 (count 0 / null /
                # empty list, matching Spark's ungrouped semantics)
                seg_live = seg_live.at[0].set(True)
            seg_start = jax.ops.segment_min(jnp.arange(cap), seg_ids, cap)
            src_rows = perm[jnp.clip(seg_start, 0, cap - 1)]
            outs = [take(kcv, src_rows, in_bounds=seg_live)
                    for kcv in key_cvs]
            for a, vnc in zip(self.aggs, vnchunks):
                if not getattr(a, "is_collect", False):
                    cv = (a.child.emit(ctx) if a.child is not None
                          else CV(jnp.zeros(cap, jnp.int8),
                                  jnp.ones(cap, jnp.bool_)))
                    if cv.offsets is not None:
                        scv = CV(jnp.zeros(cap, jnp.int8),
                                 cv.validity[perm])
                    else:
                        scv = CV(cv.data[perm], cv.validity[perm])
                    st = a.g_update(scv, live,
                                    ScatterGroups(seg_ids, cap))
                    v, okv = a.finalize(st)
                    if isinstance(v, CV):
                        outs.append(CV(v.data, v.validity & okv & seg_live,
                                       v.offsets, v.children))
                    else:
                        outs.append(CV(v, okv & seg_live))
                    continue
                vcv = a.child.emit(ctx)
                vs = take(vcv, perm)          # values in main (group) order
                valid = live & vs.validity    # collect family skips nulls
                from ..expr.aggregates import (_FirstLast,
                                              _seg_extreme_pos)
                if isinstance(a, _FirstLast):
                    # var-width first/last: per-segment positional select
                    # in input order (stable key sort preserves it)
                    elig = valid if a.ignore_nulls else live
                    sel, has = _seg_extreme_pos(elig, seg_ids, cap,
                                                a.take_first)
                    outs.append(take(vs, sel.astype(jnp.int32),
                                     in_bounds=has & seg_live))
                    continue
                if not getattr(a, "is_set", False):
                    # collect_list: stable main order == input order
                    outs.append(self._list_output(vs, valid, seg_ids, cap,
                                                  seg_live))
                    continue
                # per-agg SECONDARY sort: (segment, dead, null, value) —
                # each agg gets its own value ordering, so multiple
                # sorted aggs on different columns stay independent
                varrs = [jnp.logical_not(vs.validity).astype(jnp.uint8)]
                varrs += sk.order_keys(vs, a.child.dtype, vnc)
                order2 = sk.lexsort(
                    [seg_ids, jnp.logical_not(live).astype(jnp.uint8)]
                    + varrs)
                seg2 = seg_ids[order2]
                firsts2 = sk.group_boundaries(
                    [seg2] + [x[order2] for x in varrs])
                first_flag = jnp.zeros(cap, jnp.bool_).at[order2].set(
                    firsts2)
                kind = type(a).__name__
                if kind == "CountDistinct":
                    keep = valid & first_flag
                    cnt = jax.ops.segment_sum(keep.astype(jnp.int64),
                                              seg_ids, cap)
                    outs.append(CV(cnt, seg_live))
                elif kind in ("Percentile", "Median"):
                    outs.append(self._percentile_output(
                        a, vs, valid, seg_ids, order2, cap))
                else:                          # CollectSet
                    keep = valid & first_flag
                    outs.append(self._list_output(vs, keep, seg_ids, cap,
                                                  seg_live))
            return outs, seg_live
        return fn

    @staticmethod
    def _list_output(vs, keep, seg_ids, cap, seg_live):
        """Array column from kept rows: per-group counts -> offsets,
        global stable compaction preserves (group, position) order."""
        cnt = jax.ops.segment_sum(keep.astype(jnp.int32), seg_ids, cap)
        off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(cnt).astype(jnp.int32)])
        perm2 = jnp.argsort(jnp.logical_not(keep), stable=True)
        inb = jnp.arange(cap) < off[cap]
        child_cv = take(vs, perm2, inb)
        return CV(jnp.zeros(0, jnp.int8), seg_live, off, (child_cv,))

    def _percentile_output(self, a, vs, valid, seg_ids, order2, cap):
        """Rank-select percentiles from the per-agg value ordering:
        valid live values of segment g occupy order2 positions
        [start2[g], start2[g] + nvalid[g]) (dead/null rows sort last
        within the segment)."""
        rowpos = jnp.arange(cap, dtype=jnp.int32)
        seg2 = seg_ids[order2]
        start2 = jax.ops.segment_min(rowpos, seg2, cap)
        nvalid = jax.ops.segment_sum(valid.astype(jnp.int32),
                                     seg_ids, cap)
        ok_g = nvalid > 0
        sorted_vals = vs.data[order2]
        ps = a.percentages
        k = len(ps)

        def value_at(frac_idx):
            # frac_idx float per group; interpolate between floor/ceil
            lo = jnp.floor(frac_idx).astype(jnp.int32)
            hi = jnp.ceil(frac_idx).astype(jnp.int32)
            pos_lo = jnp.clip(start2 + lo, 0, cap - 1)
            pos_hi = jnp.clip(start2 + hi, 0, cap - 1)
            vlo = sorted_vals[pos_lo]
            vhi = sorted_vals[pos_hi]
            if a.interpolate:
                frac = frac_idx - lo.astype(jnp.float64)
                return (vlo.astype(jnp.float64) * (1 - frac)
                        + vhi.astype(jnp.float64) * frac)
            return vlo

        cols = []
        for p in ps:
            if a.interpolate:
                fi = p * jnp.maximum(nvalid - 1, 0).astype(jnp.float64)
            else:
                # Spark discrete: element at ceil(p*n)-1 (1-based rank)
                fi = jnp.maximum(
                    jnp.ceil(p * nvalid.astype(jnp.float64)) - 1,
                    0).astype(jnp.float64)
            cols.append(value_at(fi))
        if a.scalar_out:
            return CV(cols[0], ok_g)
        data = jnp.stack(cols, axis=1).reshape(-1)   # [cap*k] row-major
        child = CV(data, jnp.repeat(ok_g, k))
        off = jnp.arange(cap + 1, dtype=jnp.int32) * k
        return CV(jnp.zeros(0, jnp.int8), ok_g, off, (child,))

    def execute_partition(self, ctx: ExecContext, pid: int):
        m = ctx.metrics_for(self._op_id)
        child = self.children[0]
        child_pids = ([pid] if self.per_partition
                      else range(child.num_partitions(ctx)))
        batches = []
        for cpid in child_pids:
            batches.extend(child.execute_partition(ctx, cpid))
        if not batches:
            return
        ncols = len(child.schema.fields)
        with m.timer("opTime"):
            if len(batches) == 1:
                cvs, mask = batches[0].cvs(), batches[0].row_mask
            else:
                cvs = [concat_cvs([b.cvs()[i] for b in batches],
                                  child.schema.fields[i].dtype)
                       for i in range(ncols)]
                mask = concat_masks([b.row_mask for b in batches])
            nchunks = self._key_nchunks(cvs, mask)
            vnchunks = self._value_nchunks(cvs, mask)
            fn = self._run_cache.get((nchunks, vnchunks))
            if fn is None:
                from ..runtime.program_cache import cached_program
                fn = cached_program(
                    self._run_fn(nchunks, vnchunks),
                    cls="CollectAggExec", tag="run",
                    key=self._fp + (nchunks, vnchunks))
                self._run_cache[(nchunks, vnchunks)] = fn
            outs, seg_live = fn(cvs, mask)
            cap = mask.shape[0]
        tbl = make_table(self.schema, outs, cap)
        m.add("numOutputBatches", 1)
        yield DeviceBatch(tbl, cap, seg_live, cap)
