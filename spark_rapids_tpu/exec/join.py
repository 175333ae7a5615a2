"""Hash-join execution (inner/left/right/full/semi/anti/cross).

Reference: GpuShuffledHashJoinExec.scala:167 + GpuHashJoin.scala (gather-map
join over cudf hash tables) and GpuBroadcastNestedLoopJoinExecBase for
cross. TPU-first redesign under the static-shape regime:

  1. BUILD: concat the right side into one device table.
  2. Per stream batch, COUNT phase (one XLA program): sort the combined
     (build + stream) keys — radix-normalized, NaN/null aware — derive
     equality segments, count joinable build rows per segment, and for
     every stream row its match count. Matching rows of a segment are
     contiguous in combined-sorted space, so a (segment start, j) pair
     addresses the j-th match directly.
  3. Host-sync ONLY the total match count -> bucket the output capacity
     (the cudf analog returns gather-map sizes the same way).
  4. EXPAND phase (second XLA program, shape keyed by output bucket):
     searchsorted over the per-row offsets builds the left/right gather
     maps; gather payload columns from both sides.

A single fixed-width key, or several fixed-width integral keys packed
into one monotone uint64 word over the build side's ranges, skips the
combined sort: the build side sorts once per join (or is addressed
directly) and every stream batch probes it by binary search.

Semi/anti joins skip phases 3-4 entirely — they are a mask update on the
stream batch. Right/full outer track per-build-row matched flags across
stream batches and emit unmatched build rows in a final batch.

Null join keys never match (SQL equi-join); NaN keys match NaN per Spark.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.column import bucket_capacity
from ..columnar.table import Schema
from ..expr.expressions import EmitCtx, Expression
from ..ops import sortkeys as sk
from ..ops.concat import concat_cvs, concat_masks
from ..ops.kernel_utils import CV
from ..ops.partition import word_count
from ..utils.transfer import fetch_int
from ..profiler import xla_stats
from .base import ExecContext, TpuExec
from .batch import DeviceBatch
from .nodes import make_table

__all__ = ["HashJoinExec", "NestedLoopJoinExec"]



def _null_cvs(fields, cap):
    """All-null columns for outer-join extension rows (flat dtypes;
    nested children TODO alongside nested outer-join payload support)."""
    from ..columnar.column import alloc_shape
    out = []
    for f in fields:
        np_dt = f.dtype.np_dtype or jnp.int8
        out.append(CV(jnp.zeros(alloc_shape(f.dtype, cap), np_dt),
                      jnp.zeros(cap, jnp.bool_),
                      jnp.zeros(cap + 1, jnp.int32)
                      if f.dtype.is_variable_width else None))
    return out


class HashJoinExec(TpuExec):
    # the stream side collapses into the probe pre-stage program
    # (_stream_batches); the fusion pass leaves that prefix alone
    fuses_child_chain = True

    def __init__(self, left: TpuExec, right: TpuExec,
                 bound_left_keys: Sequence[Expression],
                 bound_right_keys: Sequence[Expression], how: str,
                 schema: Schema, per_partition: bool = False,
                 condition: Optional[Expression] = None):
        """per_partition: both children are hash-partitioned on the join
        keys (exchanges below us), so each partition joins independently —
        the distributed shuffled-join topology (reference:
        GpuShuffledHashJoinExec.scala:167).

        condition: extra non-equi predicate bound over the COMBINED
        (left ++ right) schema, evaluated on candidate pairs after the
        equi-key expansion (the reference compiles these to cudf AST
        expressions, AstUtil.scala; here the expression fuses into the
        pair-evaluation program)."""
        super().__init__([left, right], schema)
        self.lkeys = list(bound_left_keys)
        self.rkeys = list(bound_right_keys)
        self.how = how
        self.per_partition = per_partition
        self.condition = condition
        self._count_cache = {}
        self._expand_cache = {}
        from ..runtime.program_cache import expr_fp, exprs_fp
        # shared program-cache key material: same keys/type/condition
        # from a different DataFrame reuse every join program
        self._fp = (exprs_fp(self.lkeys), exprs_fp(self.rkeys), how,
                    expr_fp(condition) if condition is not None
                    else None)
        # probe-side pre-projection: the fusable stream-side chain
        # collapses into one pre-stage program per stream batch
        # (resolved lazily at first execute, see UngroupedAggExec)
        self._base_left = None
        self._lstages = None
        self._n_fused = 0
        self._pre_jit = None
        # the lockstep result, kept only for parents that pull partitions
        from ..runtime import lockdep
        self._mesh_lock = lockdep.rlock("HashJoinExec._mesh_lock")
        self._mesh_out = None

    def num_partitions(self, ctx):
        if self.per_partition:
            return self.children[0].num_partitions(ctx)
        return 1

    def describe(self):
        mode = "distributed" if self.per_partition else "single"
        fused = f", fused_stages={self._n_fused}" if self._n_fused else ""
        return f"HashJoinExec[{self.how}, {mode}{fused}]"

    def _resolve_fusion(self, ctx):
        if self._base_left is None:
            from ..config import STAGE_FUSION_ENABLED
            from .base import collapse_fusable
            if ctx.conf.get(STAGE_FUSION_ENABLED):
                self._base_left, self._lstages, self._n_fused = \
                    collapse_fusable(self.children[0])
            else:
                self._base_left, self._n_fused = self.children[0], 0
            if self._n_fused:
                from ..runtime.program_cache import cached_program
                # tpulint: allow[fp-unstable-attr,unstable-program-key] id(self) is the documented per-instance fallback key: unshared, never falsely shared, excluded from warm packs
                self._pre_jit = cached_program(
                    self._lstages, cls=type(self).__name__, tag="pre",
                    key=getattr(self._lstages, "_stage_fp",
                                ("inst", id(self))))

    def _stream_batches(self, ctx, pid):
        """Probe-side input with the fusable left chain applied as one
        pre-stage program per batch (the probe-side pre-projection)."""
        self._resolve_fusion(ctx)
        base = self._base_left
        for lpid in ([pid] if self.per_partition
                     else range(base.num_partitions(ctx))):
            for b in base.execute_partition(ctx, lpid):
                ctx.check_cancel()
                if self._n_fused:
                    cvs2, mask2 = self._pre_jit(b.cvs(), b.row_mask)
                    xla_stats.count_dispatch()
                    b = DeviceBatch(
                        make_table(self.children[0].schema, cvs2,
                                   b.num_rows),
                        b.num_rows, mask2, b.capacity)
                yield b

    # ------------------------------------------------------------------
    @staticmethod
    def _concat_batches(batches, schema: Schema):
        if not batches:
            cvs = [CV(jnp.zeros(128, f.dtype.np_dtype or jnp.int8),
                      jnp.zeros(128, jnp.bool_),
                      jnp.zeros(129, jnp.int32)
                      if f.dtype.is_variable_width else None)
                   for f in schema.fields]
            return cvs, jnp.zeros(128, jnp.bool_)
        ncols = len(batches[0].table.columns)
        if len(batches) == 1:
            return batches[0].cvs(), batches[0].row_mask
        cvs = [concat_cvs([b.cvs()[i] for b in batches],
                          schema.fields[i].dtype)
               for i in range(ncols)]
        mask = concat_masks([b.row_mask for b in batches])
        return cvs, mask

    def _collect_side(self, ctx, child, key_exprs, pids=None):
        batches = []
        for pid in (pids if pids is not None
                    else range(child.num_partitions(ctx))):
            batches.extend(child.execute_partition(ctx, pid))
        return self._concat_batches(batches, child.schema)

    def _report_key_words(self, m, bkey_cvs, nchunks=None):
        """`joinKeyWords`: the 32-bit words of key a row as this join's
        build side is sorted (or addressed) by it: an int64 key reads 2,
        two of them 4, a decimal128 4, a string its chunk words
        (`nchunks`; unknown before the generic path measures them)."""
        words = 0
        for i, kcv in enumerate(bkey_cvs):
            if kcv.offsets is None:
                words += word_count([kcv.data])
            elif nchunks is None:
                return
            else:
                words += nchunks[i]
        m.set("joinKeyWords", words)

    def _key_nchunks(self, bkey_cvs, bmask, skey_cvs, smask):
        ncs = []
        for i, ke in enumerate(self.lkeys):
            if isinstance(ke.dtype, (dt.StringType, dt.BinaryType)):
                ncs.append(max(sk.string_nchunks(bkey_cvs[i], bmask),
                               sk.string_nchunks(skey_cvs[i], smask)))
            else:
                ncs.append(0)
        return tuple(ncs)

    # ---- single-key fast path: sorted build + searchsorted probe -------
    # The build side sorts ONCE per join (not once per stream batch): keys
    # normalize to a monotone uint64 radix word, invalid keys pin to
    # UINT64_MAX (sorted last, excluded by clipping ranges to n_valid), and
    # each stream batch probes with two binary searches — O(S log B) per
    # batch instead of a combined (B+S) sort (reference contrast:
    # GpuHashJoin.scala builds a hash table once; this is the TPU-sortable
    # equivalent).
    @staticmethod
    def _single_key_u64(kcv: CV, dtype: dt.DataType):
        """Monotone uint64 key, or None when the dtype needs >1 array."""
        arrs = sk.order_keys(kcv, dtype)
        if len(arrs) != 1:
            return None
        a = arrs[0]
        if a.dtype == jnp.uint8 or a.dtype == jnp.uint32:
            return a.astype(jnp.uint64)
        if a.dtype == jnp.int64:
            return a.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
        if a.dtype == jnp.int32:
            return (a.astype(jnp.int64).astype(jnp.uint64)
                    ^ jnp.uint64(1 << 63))
        if a.dtype == jnp.int8 or a.dtype == jnp.int16:
            return (a.astype(jnp.int64).astype(jnp.uint64)
                    ^ jnp.uint64(1 << 63))
        return None

    @staticmethod
    def _key_word(kcv, dtype, pack=()):
        """(monotone uint64 word, key can match) of each row. One key
        (`pack` empty): its radix word and its validity. Packed keys
        (`kcv` the list of key columns, `pack` = (lo, hi): each key's
        least and greatest value over the build side, int64 device
        arrays): the mixed-radix word sum (k_i - lo_i) * prod_{j>i} span_j.
        A key outside its [lo_i, hi_i] cannot match, and must not: its
        word would alias another tuple's."""
        if not pack:
            return HashJoinExec._single_key_u64(kcv, dtype), kcv.validity
        lo, hi = pack
        span = (hi - lo + 1).astype(jnp.uint64)
        word = ok = None
        for i, c in enumerate(kcv):
            v = c.data.astype(jnp.int64)
            off = v.astype(jnp.uint64) - lo[i].astype(jnp.uint64)
            inr = c.validity & (v >= lo[i]) & (v <= hi[i])
            word = off if word is None else word * span[i] + off
            ok = inr if ok is None else ok & inr
        return word, ok

    def _fast_path_ok(self):
        if len(self.rkeys) != 1:
            return False
        d = self.rkeys[0].dtype
        if isinstance(d, dt.DecimalType) and d.is_decimal128:
            return False   # two-limb keys need the generic path
        return not (d.is_variable_width or d.is_nested
                    or isinstance(d, dt.DoubleType))

    def _pack_ok(self):
        """Two keys or more, each a value of one int64 (an integral,
        date, timestamp, boolean or decimal64 column): they may pack into
        one word, if the build side's ranges let them (_pack_ranges).
        The planner gives both sides of a key the same type."""
        def packable(d):
            if isinstance(d, dt.DecimalType):
                return not d.is_decimal128
            return d.is_integral or isinstance(
                d, (dt.DateType, dt.TimestampType, dt.BooleanType))
        return len(self.rkeys) >= 2 and all(packable(k.dtype)
                                            for k in self.rkeys)

    _WORD_MAX = (1 << 63) - 1     # packed words stay clear of the pin

    def _pack_ranges(self, bkey_cvs, bmask, cap_b):
        """((lo, hi) device arrays, product of the spans) when the keys
        of the build side's matchable rows pack into one word, else None
        (also for a build side with no such row). One launch, one
        fetch."""
        from ..utils.transfer import fetch
        key = ("keyranges", cap_b)
        rfn = self._count_cache.get(key)
        if rfn is None:
            def rfn_(kcvs, mask):
                valid = mask
                for c in kcvs:
                    valid = valid & c.validity
                vs = [c.data.astype(jnp.int64) for c in kcvs]
                big = jnp.iinfo(jnp.int64)
                lo = jnp.stack([jnp.min(jnp.where(valid, v, big.max))
                                for v in vs])
                hi = jnp.stack([jnp.max(jnp.where(valid, v, big.min))
                                for v in vs])
                return lo, hi, jnp.sum(valid.astype(jnp.int32))
            from ..runtime.program_cache import cached_program
            rfn = cached_program(rfn_, cls=type(self).__name__,
                                 tag="keyranges", key=self._fp)
            self._count_cache[key] = rfn
        lo_d, hi_d, nv_d = rfn(bkey_cvs, bmask)
        lo, hi, nv = fetch((lo_d, hi_d, nv_d))
        if int(nv) == 0:
            return None
        span = 1
        for a, b in zip(lo.tolist(), hi.tolist()):
            span *= b - a + 1
        if span > self._WORD_MAX:
            return None
        return (lo_d, hi_d), span

    @staticmethod
    def _build_sort_fn(dtype):
        def fn_(kcv, mask, *pack):
            ukey, kvalid = HashJoinExec._key_word(kcv, dtype, pack)
            valid = mask & kvalid
            pinned = jnp.where(valid, ukey,
                               jnp.uint64(0xFFFFFFFFFFFFFFFF))
            inv = jnp.logical_not(valid).astype(jnp.uint8)
            perm = sk.lexsort([inv, pinned])
            return pinned[perm], perm.astype(jnp.int32), \
                jnp.sum(valid.astype(jnp.int32))
        return fn_

    def _build_sorted(self, bkey_cvs, bmask, pack=()):
        """jitted once per build capacity (cached in _count_cache):
        returns (sorted ukeys with invalids pinned MAX, perm sorted->orig,
        n_valid)."""
        key = ("buildsort", bmask.shape[0])
        fn = self._count_cache.get(key)
        if fn is None:
            from ..runtime.program_cache import cached_program
            fn = cached_program(self._build_sort_fn(self.rkeys[0].dtype),
                                cls=type(self).__name__,
                                tag="buildsort", key=self._fp)
            self._count_cache[key] = fn
        return fn(bkey_cvs if pack else bkey_cvs[0], bmask, *pack)

    # ---- direct-address (perfect-hash) build: no sort at all -----------
    # When the single int key's value span fits a bounded table (TPC-H
    # surrogate keys are dense), build = two scatters, probe = two
    # gathers: O(n) linear passes instead of XLA's single-threaded
    # O(n log n) sort (~0.5s at 1M rows on CPU). Falls back to the sorted
    # path per-batch only when a stream row has >1 match AND the join
    # needs pair enumeration.
    _DIRECT_SPAN_FACTOR = 8
    _DIRECT_SPAN_MIN = 1 << 22

    def _try_build_direct(self, bkey_cvs, bmask, cap_b, packed=None):
        """Returns {'R', 'kmin', 'kmax', 'cnt_t', 'idx_t'} or None.
        `packed` (_pack_ranges' answer): the words lie in [0, span)."""
        if packed is not None:
            pack, span = packed
            kmin_d, kmax_d = jnp.uint64(0), jnp.uint64(span - 1)
            return self._build_direct(bkey_cvs, bmask, cap_b, span, kmin_d,
                                      kmax_d, pack)
        from ..utils.transfer import fetch
        key = ("keyrange", cap_b)
        rfn = self._count_cache.get(key)
        if rfn is None:
            def rfn_(kcv, mask):
                ukey = self._single_key_u64(kcv, self.rkeys[0].dtype)
                valid = mask & kcv.validity
                kmin = jnp.min(jnp.where(valid, ukey,
                                         jnp.uint64(0xFFFFFFFFFFFFFFFF)))
                kmax = jnp.max(jnp.where(valid, ukey, jnp.uint64(0)))
                return kmin, kmax, jnp.sum(valid.astype(jnp.int32))
            from ..runtime.program_cache import cached_program
            rfn = cached_program(rfn_, cls=type(self).__name__,
                                 tag="keyrange", key=self._fp)
            self._count_cache[key] = rfn
        kmin_d, kmax_d, nv_d = rfn(bkey_cvs[0], bmask)
        kmin, kmax, nv = (int(v) for v in fetch((kmin_d, kmax_d, nv_d)))
        if nv == 0:
            return None
        return self._build_direct(bkey_cvs, bmask, cap_b, kmax - kmin + 1,
                                  kmin_d, kmax_d)

    def _build_direct(self, bkey_cvs, bmask, cap_b, span, kmin_d, kmax_d,
                      pack=()):
        if span > max(self._DIRECT_SPAN_FACTOR * cap_b,
                      self._DIRECT_SPAN_MIN):
            return None
        R = bucket_capacity(span)
        bkey = ("directbuild", R, cap_b)
        bfn = self._count_cache.get(bkey)
        if bfn is None:
            dtype = self.rkeys[0].dtype

            def bfn_(kcv, mask, kmin_dev, *pack):
                ukey, kvalid = HashJoinExec._key_word(kcv, dtype, pack)
                valid = mask & kvalid
                d = (ukey - kmin_dev).astype(jnp.int64)
                off = jnp.where(valid, jnp.clip(d, 0, R), R)
                cnt_t = jnp.zeros(R + 1, jnp.int32).at[off].add(
                    valid.astype(jnp.int32))
                idx_t = jnp.zeros(R + 1, jnp.int32).at[off].max(
                    jnp.arange(cap_b, dtype=jnp.int32))
                return cnt_t, idx_t
            from ..runtime.program_cache import cached_program
            bfn = cached_program(bfn_, cls=type(self).__name__,
                                 tag="directbuild",
                                 key=self._fp + (R,))
            self._count_cache[bkey] = bfn
        cnt_t, idx_t = bfn(bkey_cvs if pack else bkey_cvs[0], bmask, kmin_d,
                           *pack)
        return {"R": R, "kmin": kmin_d, "kmax": kmax_d,
                "cnt_t": cnt_t, "idx_t": idx_t}

    def _direct_probe(self, direct, skcv, smask, cap_s, pack=()):
        R = direct["R"]
        key = ("directprobe", R, cap_s)
        fn = self._count_cache.get(key)
        if fn is None:
            dtype = self.lkeys[0].dtype

            def fn_(cnt_t, idx_t, kmin, kmax, skcv, smask, *pack):
                ukey_s, kvalid = HashJoinExec._key_word(skcv, dtype, pack)
                joinable = smask & kvalid
                in_r = joinable & (ukey_s >= kmin) & (ukey_s <= kmax)
                d = (ukey_s - kmin).astype(jnp.int64)
                poff = jnp.where(in_r, jnp.clip(d, 0, R), R)
                cnt = cnt_t[poff].astype(jnp.int64)
                bidx = idx_t[poff]
                return cnt, bidx
            from ..runtime.program_cache import cached_program
            fn = cached_program(fn_, cls=type(self).__name__,
                                tag="directprobe",
                                key=self._fp + (R,))
            self._count_cache[key] = fn
        return fn(direct["cnt_t"], direct["idx_t"], direct["kmin"],
                  direct["kmax"], skcv, smask, *pack)

    def _probe_fn(self, cap_b, cap_s):
        """Per-stream-batch count phase against the sorted build keys."""
        # not `self`: a cached program pinning its builder must not pin
        # the operator tree (and what it parked) with it
        word, ldtype = self._key_word, self.lkeys[0].dtype

        def fn(sorted_ukey, n_valid, skcv, smask, *pack):
            ukey_s, kvalid = word(skcv, ldtype, pack)
            joinable = smask & kvalid
            lo = jnp.searchsorted(sorted_ukey, ukey_s, side="left")
            hi = jnp.searchsorted(sorted_ukey, ukey_s, side="right")
            lo = jnp.minimum(lo, n_valid)
            hi = jnp.minimum(hi, n_valid)
            cnt = jnp.where(joinable, (hi - lo).astype(jnp.int64), 0)
            offsets = jnp.cumsum(cnt) - cnt
            total = jnp.sum(cnt)
            # matched build positions (right/full outer): range-mark via
            # +1/-1 diff then prefix sum over sorted build space
            diff = jnp.zeros(cap_b + 1, jnp.int32)
            add_lo = jnp.where(joinable, lo, cap_b)
            add_hi = jnp.where(joinable, hi, cap_b)
            diff = diff.at[add_lo].add(1).at[add_hi].add(-1)
            touched = jnp.cumsum(diff[:-1]) > 0
            return (cnt, offsets, total, lo.astype(jnp.int64), touched)
        return fn

    @staticmethod
    @jax.jit
    def _matched_from_touched(bperm, touched, n_valid, acc):
        pos_ok = jnp.arange(touched.shape[0]) < n_valid
        upd = jnp.zeros_like(acc).at[bperm].max(touched & pos_ok)
        return acc | upd

    # ---- single-match (FK-join) output stats + gather index -----------
    # When no stream row has more than one match — every build-unique
    # dimension join (TPC-H's dominant shape) — the expand phase is a
    # no-op permutation: the stream side passes through UNTOUCHED (zero
    # copy, mask update only) and the build payload gathers at stream
    # capacity. One probe-stat fetch decides the path per batch.
    @staticmethod
    @jax.jit
    def _probe_stats(cnt, smask):
        matched = (cnt > 0) & smask
        eff = jnp.where(smask & (cnt == 0), 1, cnt)
        return (jnp.sum(cnt), jnp.sum(eff),
                jnp.sum(matched.astype(jnp.int64)), jnp.max(cnt))

    @staticmethod
    @jax.jit
    def _fk_gather_idx(cnt, bstart, perm, smask, n_build):
        matched = (cnt > 0) & smask
        pos = jnp.clip(bstart, 0, perm.shape[0] - 1).astype(jnp.int32)
        rg = jnp.clip(perm[pos], 0, n_build - 1).astype(jnp.int32)
        return rg, matched

    def _fk_output(self, m, batch, scvs, bcvs, rg, matched, smask,
                   n_matched, n_eff, cap_s):
        """Single-match join output: stream columns pass through IN
        PLACE (holey mask — num_rows stays the positional upper bound),
        build payload gathered by the per-row match index."""
        new_mask = matched if self.how == "inner" else smask
        out_cvs = list(scvs) + self._gather_cols(bcvs, rg, matched)
        tbl = make_table(self.schema, out_cvs, batch.num_rows)
        m.add("numOutputRows",
              n_matched if self.how == "inner" else n_eff)
        m.add("numOutputBatches", 1)
        return ("batch", DeviceBatch(tbl, batch.num_rows, new_mask,
                                     cap_s))

    # ---- phase 1+2: combined sort & count (jitted) --------------------
    def _count_fn(self, nchunks, cap_b, cap_s):
        def fn(bkeys, bmask, skeys, smask):
            nk = len(self.rkeys)
            joinable_b = bmask
            joinable_s = smask
            comb_keys: List = []
            for i in range(nk):
                kb, ks_ = bkeys[i], skeys[i]
                joinable_b = joinable_b & kb.validity
                joinable_s = joinable_s & ks_.validity
                comb_keys.append(concat_cvs([kb, ks_], self.rkeys[i].dtype))
            joinable = jnp.concatenate([joinable_b, joinable_s])
            is_build = jnp.concatenate([
                jnp.ones(cap_b, jnp.bool_), jnp.zeros(cap_s, jnp.bool_)])
            arrays = [jnp.logical_not(joinable).astype(jnp.uint8)]
            for i, kcv in enumerate(comb_keys):
                arrays.extend(sk.order_keys(kcv, self.rkeys[i].dtype,
                                            nchunks[i]))
            perm = sk.lexsort(arrays)
            sorted_arrays = [a[perm] for a in arrays]
            boundary = sk.group_boundaries(sorted_arrays)
            seg_ids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            n = cap_b + cap_s
            jb_sorted = (is_build & joinable)[perm]
            js_sorted = (joinable & ~is_build)[perm]
            seg_bcnt = jax.ops.segment_sum(jb_sorted.astype(jnp.int64),
                                           seg_ids, n)
            seg_scnt = jax.ops.segment_sum(js_sorted.astype(jnp.int64),
                                           seg_ids, n)
            # combined-sorted position of the first joinable build row of
            # each segment (build rows sort before stream rows? not
            # guaranteed -> take min over build rows only)
            pos = jnp.arange(n)
            seg_bstart = jax.ops.segment_min(
                jnp.where(jb_sorted, pos, n), seg_ids, n)
            # per ORIGINAL stream row: its segment & match count
            seg_of_comb = jnp.zeros(n, jnp.int32).at[perm].set(seg_ids)
            seg_of_stream = seg_of_comb[cap_b:]
            cnt = jnp.where(joinable_s, seg_bcnt[seg_of_stream], 0)
            bstart_of_stream = seg_bstart[seg_of_stream]
            # matched flags for build rows (right/full outer)
            matched_comb = jb_sorted & (seg_scnt[seg_ids] > 0)
            matched_orig = jnp.zeros(n, jnp.bool_).at[perm].set(matched_comb)
            matched_b = matched_orig[:cap_b]
            offsets = jnp.cumsum(cnt) - cnt
            total = jnp.sum(cnt)
            return (cnt, offsets, total, bstart_of_stream, perm, matched_b)
        return fn

    # ---- phase 3: expansion (jitted, keyed by out capacity) ------------
    def _expand_fn(self, out_cap, cap_b, with_left_nulls):
        from ..ops.gather import row_of_unit

        def fn(cnt, offsets, bstart_of_stream, perm, smask):
            t = jnp.arange(out_cap, dtype=jnp.int64)
            cap_s = cnt.shape[0]
            # stream row for each output slot (scatter+cummax, not
            # searchsorted — see ops.gather.row_of_unit)
            i = row_of_unit(offsets, cap_s, out_cap).astype(jnp.int64)
            if with_left_nulls:
                # left/full: unmatched live stream rows produce one row
                eff_cnt = jnp.where(smask & (cnt == 0), 1, cnt)
                offs = jnp.cumsum(eff_cnt) - eff_cnt
                i = row_of_unit(offs, cap_s, out_cap).astype(jnp.int64)
                i = jnp.clip(i, 0, cap_s - 1)
                j = t - offs[i]
                matched = cnt[i] > 0
                total = jnp.sum(eff_cnt)
            else:
                i = jnp.clip(i, 0, cap_s - 1)
                j = t - offsets[i]
                matched = cnt[i] > 0
                total = jnp.sum(cnt)
            in_bounds = t < total
            comb_pos = bstart_of_stream[i] + j
            comb_pos = jnp.clip(comb_pos, 0, perm.shape[0] - 1)
            b_orig = perm[comb_pos]           # original combined index
            b_orig = jnp.clip(b_orig, 0, cap_b - 1)
            lgather = i.astype(jnp.int32)
            rgather = b_orig.astype(jnp.int32)
            rvalid = matched & in_bounds
            lvalid = in_bounds
            return lgather, rgather, lvalid, rvalid, total
        return fn

    def _gather_cols(self, cvs, idx, inb):
        """Gather payload columns by idx — join expansion duplicates rows,
        so var-width capacities are re-measured (ops.gather.gather_cols)."""
        from ..ops.gather import gather_cols
        return gather_cols(cvs, idx, inb)

    # ---- the co-partitioned join in lockstep over a mesh ---------------
    # Both children are hash-partitioned over n devices (exchanges below
    # us) and hand over every shard's batch at once (execute_mesh). Each
    # step then runs as ONE program over the mesh instead of one a
    # device: sorted build + probe, ONE fetch of every shard's match
    # statistics, and the expansion at one output capacity (the largest
    # shard's, bucketed). A single fixed-width key and no residual
    # condition, which is the fast path above; anything else keeps the
    # per-partition path.
    _MESH_HOWS = ("inner", "left", "left_semi", "left_anti")

    def _mesh_sides(self, ctx, n):
        """(stream, build) lockstep sources, or None."""
        if (not self.per_partition or self.how not in self._MESH_HOWS
                or self.condition is not None
                or not self._fast_path_ok()
                or any(f.dtype.is_nested for f in self.schema.fields)):
            return None
        from .lockstep import mesh_batches
        build = mesh_batches(ctx, self.children[1], n)
        if build is None:
            return None
        stream = mesh_batches(ctx, self.children[0], n)
        return None if stream is None else (stream, build)

    def _mesh_memo(self, ctx):
        """The lockstep result kept for parents that pull partitions;
        None where the join has no lockstep form here. The first caller
        computes it and the others wait for it; `_mesh_lock` guards only
        the hand-over, never the children's own locks."""
        import threading
        with self._mesh_lock:
            once = self._mesh_out
            lead = once is None
            if lead:
                once = self._mesh_out = {"done": threading.Event(),
                                         "out": None, "err": None}
        if lead:
            try:
                n = self.children[0].num_partitions(ctx)
                it = self.execute_mesh(ctx, n) if n > 1 else None
                once["out"] = None if it is None else list(it)
            except BaseException as e:
                once["err"] = e
                with self._mesh_lock:
                    self._mesh_out = None     # a retried action recomputes
                raise
            finally:
                once["done"].set()
        else:
            while not once["done"].wait(0.05):
                ctx.check_cancel()
            if once["err"] is not None:
                raise once["err"]
        return once["out"]

    def execute_mesh(self, ctx: ExecContext, n: int):
        sides = self._mesh_sides(ctx, n)
        if sides is None:
            return None
        stream, build = sides
        builds = list(build)
        if not builds:
            return None     # an empty build side: the partition path
        return self._mesh_join(ctx, n, stream, builds)

    def _mesh_join(self, ctx, n, stream, builds):
        from ..ops.gather import repeat_measures, take
        from ..parallel.mesh_program import MeshProgram
        from ..utils.transfer import fetch
        m = ctx.metrics_for(self._op_id)
        left, right = self.children
        rkey, lkey = self.rkeys[0], self.lkeys[0]
        rdtypes = [f.dtype for f in right.schema.fields]
        how = self.how
        u64 = self._single_key_u64
        key = self._fp

        def build_fn(tree):
            if len(tree) == 1:
                bcvs, bmask = tree[0]
            else:
                bcvs = [concat_cvs([t[0][ci] for t in tree], d)
                        for ci, d in enumerate(rdtypes)]
                bmask = concat_masks([t[1] for t in tree])
            kcv = rkey.emit(EmitCtx(bcvs, bmask.shape[0]))
            valid = bmask & kcv.validity
            pinned = jnp.where(valid, u64(kcv, rkey.dtype),
                               jnp.uint64(0xFFFFFFFFFFFFFFFF))
            perm = sk.lexsort([jnp.logical_not(valid).astype(jnp.uint8),
                               pinned])
            return (list(bcvs), pinned[perm], perm.astype(jnp.int32),
                    jnp.sum(valid.astype(jnp.int32)))

        with m.timer("buildTime"):
            built = MeshProgram(build_fn, n, cls="HashJoinExec",
                                 tag="meshbuild", key=key)(
                [tuple(shard)
                 for shard in zip(*(mb.trees() for mb in builds))])
            xla_stats.count_dispatch()
        cap_b = built[0][2].shape[0]
        bvar = [ci for ci, d in enumerate(rdtypes) if d.is_variable_width]
        svar = [ci for ci, f in enumerate(left.schema.fields)
                if f.dtype.is_variable_width]
        left_nulls = how == "left"

        probe_body = self._probe_fn(cap_b, 0)

        def probe_fn(tree):
            (bcvs, sorted_ukey, perm, n_valid), (scvs, smask) = tree
            skcv = lkey.emit(EmitCtx(scvs, smask.shape[0]))
            cnt, offsets, total, lo, _ = probe_body(
                sorted_ukey, n_valid[0], skcv, smask)
            eff = jnp.where(smask & (cnt == 0), 1, cnt) if left_nulls \
                else cnt
            stats = [jnp.sum(eff), jnp.max(cnt)]
            # var-width bytes the expansion will hold: a stream row's
            # length times its copies; a build row's through the prefix
            # sum of lengths in sorted-build order over [lo, lo + cnt)
            for ci in svar:
                stats.extend(repeat_measures(scvs[ci], eff))
            for ci in bvar:
                off = bcvs[ci].offsets
                lens = jnp.where(bcvs[ci].validity,
                                 (off[1:] - off[:-1]), 0)[perm]
                pre = jnp.concatenate([jnp.zeros(1, jnp.int64),
                                       jnp.cumsum(lens.astype(jnp.int64))])
                stats.append(jnp.sum(pre[lo + cnt] - pre[lo]))
            keep = smask & ((cnt == 0) if how == "left_anti" else (cnt > 0))
            return cnt, offsets, lo, jnp.stack(
                [jnp.asarray(v, jnp.int64) for v in stats]), keep

        probe = MeshProgram(probe_fn, n, cls="HashJoinExec",
                             tag="meshprobe", key=key + (cap_b,))
        for mb in stream:
            ctx.check_cancel()
            streamed = mb.trees()
            with m.timer("opTime"):
                probed = probe(list(zip(built, streamed)))
                xla_stats.count_dispatch()
            if how in ("left_semi", "left_anti"):
                # a mask update on the stream batch: no fetch, no copy
                yield type(mb)([
                    DeviceBatch(b.table, b.num_rows, p[4], b.capacity)
                    for b, p in zip(mb.shards, probed)])
                continue
            with m.timer("opTime"):
                st = [[int(v) for v in row]
                      for row in fetch([p[3] for p in probed])]
                rows = [row[0] for row in st]
                if max(rows) == 0:
                    continue
                out_cap = bucket_capacity(max(rows))
                caps = tuple(bucket_capacity(max(max(row[2 + j]
                                                     for row in st), 1))
                             for j in range(len(svar) + len(bvar)))

                # built here and not in the closure: a bound method
                # there would pin the operator tree in the program cache
                expand_body = self._expand_fn(out_cap, cap_b, left_nulls)

                def expand_fn(tree, out_cap=out_cap, caps=caps,
                              expand_body=expand_body):
                    (bcvs, _, perm, _), (scvs, smask), (cnt, offs, lo, _,
                                                        _) = tree
                    lg, rg, lvalid, rvalid, total = expand_body(
                        cnt, offs, lo, perm, smask)
                    it = iter(caps)
                    out = [take(cv, lg, lvalid,
                                iter((next(it),)) if ci in svar else None)
                           for ci, cv in enumerate(scvs)]
                    out += [take(cv, rg, rvalid,
                                 iter((next(it),)) if ci in bvar else None)
                            for ci, cv in enumerate(bcvs)]
                    return out, jnp.arange(out_cap) < total

                outs = MeshProgram(
                    expand_fn, n, cls="HashJoinExec", tag="meshexpand",
                    key=key + (out_cap, cap_b, left_nulls, caps))(
                    list(zip(built, streamed, probed)))
                xla_stats.count_dispatch()
            m.add("numOutputRows", sum(rows))
            m.add("numOutputBatches", n)
            yield type(mb)([
                DeviceBatch(make_table(self.schema, cvs, rows[s]), rows[s],
                            mask, out_cap)
                for s, (cvs, mask) in enumerate(outs)])

    def release(self):
        with self._mesh_lock:
            self._mesh_out = None
        super().release()

    # ------------------------------------------------------------------
    def execute_partition(self, ctx: ExecContext, pid: int):
        if self.how == "cross":
            yield from self._execute_cross(ctx)
            return
        if self.per_partition:
            out = self._mesh_memo(ctx)
            if out is not None:
                for mb in out:
                    if mb.shards[pid].num_rows:
                        yield mb.shards[pid]
                return
        m = ctx.metrics_for(self._op_id)
        right = self.children[1]
        stream_batches = self._stream_batches(ctx, pid)
        from ..config import (EXCHANGE_ASYNC_BROADCAST,
                              EXCHANGE_BROADCAST_TIMEOUT)
        from .broadcast import BroadcastExchangeExec, on_build_pool
        if (not self.per_partition
                and isinstance(right, BroadcastExchangeExec)
                and ctx.conf.get(EXCHANGE_ASYNC_BROADCAST)
                and not on_build_pool()):
            # async broadcast build (GpuBroadcastExchangeExec model):
            # the build materializes on a background thread while this
            # thread advances the stream side's scan/decode/pre-stage;
            # bounded prefetch so waiting batches don't pin HBM
            right.submit_build(ctx)
            prefetched = []
            while not right.build_done() and len(prefetched) < 2:
                b = next(stream_batches, None)
                if b is None:
                    break
                prefetched.append(b)
            with m.timer("buildTime"):
                bbatches = right.await_build(
                    ctx, ctx.conf.get(EXCHANGE_BROADCAST_TIMEOUT))
            if prefetched:
                import itertools
                stream_batches = itertools.chain(prefetched,
                                                 stream_batches)
        else:
            build_pids = ([pid] if self.per_partition
                          else range(right.num_partitions(ctx)))
            with m.timer("buildTime"):
                bbatches = []
                for bpid in build_pids:
                    bbatches.extend(right.execute_partition(ctx, bpid))

        from ..config import JOIN_BUILD_BUDGET
        budget = ctx.conf.get(JOIN_BUILD_BUDGET)
        total_bytes = sum(b.nbytes for b in bbatches)
        if budget > 0 and total_bytes > budget and self.lkeys:
            yield from self._execute_subpartitioned(
                ctx, m, pid, bbatches, total_bytes, budget,
                stream_batches=stream_batches)
            return

        yield from self._join_pass(ctx, m, bbatches, stream_batches)

    def _join_pass(self, ctx: ExecContext, m, bbatches, stream_batches):
        """One complete hash-join pass: concat the given build batches,
        probe every stream batch, emit unmatched build rows for
        right/full. Called once normally; once per disjoint-key
        sub-partition in the out-of-core path."""
        from .batch import maybe_compact
        left, right = self.children
        with m.timer("buildTime"):
            bbatches = [maybe_compact(b, right.schema) for b in bbatches]
            bcvs, bmask = self._concat_batches(bbatches, right.schema)
            cap_b = bmask.shape[0]
            bctx = EmitCtx(bcvs, cap_b)
            bkey_cvs = [k.emit(bctx) for k in self.rkeys]
        self._report_key_words(m, bkey_cvs)
        matched_b_acc = jnp.zeros(cap_b, jnp.bool_)
        fast = self._fast_path_ok()
        packed, pack = None, ()
        if len(self.rkeys) > 1:
            # several fixed-width keys: one word, then the single-key path
            if self._pack_ok():
                with m.timer("buildTime"):
                    packed = self._pack_ranges(bkey_cvs, bmask, cap_b)
            m.set("joinPackedKeys", len(self.rkeys) if packed else 0)
            if packed is not None:
                fast, pack = True, packed[0]
                m.set("joinKeyWords", 2)
        direct = None
        if fast and self.condition is None and self.how in (
                "inner", "left", "left_semi", "left_anti"):
            with m.timer("buildTime"):
                direct = self._try_build_direct(bkey_cvs, bmask, cap_b,
                                                packed)
        if fast and direct is None:
            with m.timer("buildTime"):
                sorted_ukey, bperm, n_valid_b = self._build_sorted(
                    bkey_cvs, bmask, pack)
        elif direct is not None:
            # sorted structures built lazily only if a stream batch needs
            # pair enumeration (duplicate build keys)
            sorted_ukey = bperm = n_valid_b = None

        from ..memory.retry import with_retry

        def probe_one(batch):
            """Idempotent per-stream-batch probe: returns (kind, payload)
            for the caller to yield/accumulate. Split-safe: all join
            semantics here are stream-row-local; matched-build marks
            OR-accumulate."""
            out = list(self._probe_batch(ctx, m, batch, bcvs, bmask,
                                         bkey_cvs, cap_b, fast,
                                         sorted_ukey if fast else None,
                                         bperm if fast else None,
                                         n_valid_b if fast else None,
                                         direct, pack))
            return out

        for batch in stream_batches:
            batch = maybe_compact(batch, left.schema, factor=8)
            for results in with_retry(batch, probe_one):
                for kind, payload in results:
                    if kind == "matched_b":
                        matched_b_acc = matched_b_acc | payload
                    else:
                        yield payload

        if self.how in ("right", "full"):
            unmatched = bmask & ~matched_b_acc
            n_un = fetch_int((jnp.sum(unmatched)))
            if n_un > 0:
                # emit unmatched build rows with null left columns
                out_cvs = _null_cvs(left.schema.fields, cap_b)
                out_cvs += [CV(cv.data, cv.validity & unmatched, cv.offsets)
                            for cv in bcvs]
                tbl = make_table(self.schema, out_cvs, cap_b)
                yield DeviceBatch(tbl, cap_b, unmatched, cap_b)

    # ---- out-of-core: disjoint-key sub-partition loop ------------------
    def _subpartition_fn(self, key_exprs, S: int, seed: int = 0xAB5):
        """Device program extracting hash sub-partition `b` of a batch:
        rows whose join-key hash lands in bucket b compact to the front
        (GpuSubPartitionHashJoin.scala:617 rehash, TPU-style). `seed`
        varies per recursion level — re-splitting with the same seed
        would put every row back into one bucket."""
        from ..ops.gather import compact
        from ..ops.hash import partition_ids
        key_dtypes = [k.dtype for k in key_exprs]

        def fn(cvs, mask, b):
            cap = mask.shape[0]
            ectx = EmitCtx(cvs, cap)
            key_cvs = [k.emit(ectx) for k in key_exprs]
            pids = partition_ids(key_cvs, key_dtypes, S, seed=seed)
            mask_b = mask & (pids == b)
            out_cvs, count = compact(cvs, mask_b)
            return out_cvs, count
        from ..runtime.program_cache import cached_program, exprs_fp
        return cached_program(fn, cls=type(self).__name__, tag="subpart",
                              key=(exprs_fp(key_exprs), S, seed))

    def _subpart_fns(self, S: int, seed: int):
        """Cached (build-side, stream-side) sub-partition programs."""
        kb = ("subpart", "b", S, seed)
        ks = ("subpart", "s", S, seed)
        if kb not in self._count_cache:
            self._count_cache[kb] = self._subpartition_fn(
                self.rkeys, S, seed)
            self._count_cache[ks] = self._subpartition_fn(
                self.lkeys, S, seed)
        return self._count_cache[kb], self._count_cache[ks]

    def _shrink_batch(self, schema: Schema, out_cvs, nlive: int):
        """Slice a compacted (live-prefix) batch down to a bucketed
        capacity; nested columns keep their capacity (offset/child
        re-slicing is not worth the complexity here)."""
        from ..ops.gather import take_strings as _ts
        cap = out_cvs[0].validity.shape[0] if out_cvs else 128
        if any(cv.children for cv in out_cvs):
            tbl = make_table(schema, out_cvs, nlive)
            return DeviceBatch(tbl, nlive, jnp.arange(cap) < nlive, cap)
        new_cap = min(bucket_capacity(max(nlive, 1)), cap)
        cvs2 = []
        idx = jnp.arange(new_cap)
        inb = idx < nlive
        for cv in out_cvs:
            if cv.offsets is not None:
                nbytes = fetch_int(cv.offsets[nlive]) if nlive else 0
                bcap = min(bucket_capacity(max(nbytes, 1)),
                           cv.data.shape[0])
                cvs2.append(_ts(cv, idx, in_bounds=inb,
                                out_data_capacity=bcap))
            else:
                cvs2.append(CV(cv.data[:new_cap], cv.validity[:new_cap]))
        tbl = make_table(schema, cvs2, nlive)
        return DeviceBatch(tbl, nlive, inb, new_cap)

    # deepest sub-partition recursion (reference allows repeated
    # repartition, GpuSubPartitionHashJoin.scala:617)
    _MAX_SUBPART_DEPTH = 10

    def _split_both(self, ctx, m, S: int, seed: int, build_batches,
                    stream_batches):
        """Split build + stream batch iterators into S disjoint-key
        spillable piles. On error, closes everything parked so far (the
        OOC path must not leak under the very memory pressure it exists
        to handle). Returns (piles_b, bytes_b, piles_s)."""
        from ..memory.spill import spill_store
        store = spill_store(ctx.conf)
        left, right = self.children
        bfn, sfn = self._subpart_fns(S, seed)
        piles_b: List[List] = [[] for _ in range(S)]
        bytes_b = [0] * S
        piles_s: List[List] = [[] for _ in range(S)]
        try:
            with m.timer("buildTime"):
                for b in build_batches:
                    for s in range(S):
                        out_cvs, cnt = bfn(b.cvs(), b.row_mask,
                                           jnp.int32(s))
                        nlive = fetch_int(cnt)
                        if nlive == 0:
                            continue
                        sb = self._shrink_batch(right.schema, out_cvs,
                                                nlive)
                        bytes_b[s] += sb.nbytes
                        piles_b[s].append(store.add_batch(sb, priority=7))
            for batch in stream_batches:
                with m.timer("opTime"):
                    for s in range(S):
                        out_cvs, cnt = sfn(batch.cvs(), batch.row_mask,
                                           jnp.int32(s))
                        nlive = fetch_int(cnt)
                        if nlive == 0:
                            continue
                        sb = self._shrink_batch(left.schema, out_cvs,
                                                nlive)
                        piles_s[s].append(store.add_batch(sb, priority=7))
        except BaseException:
            for pile in piles_b + piles_s:
                for h in pile:
                    h.close()
            raise
        return piles_b, bytes_b, piles_s

    def _run_buckets(self, ctx, m, piles_b, bytes_b, piles_s,
                     budget: int, depth: int):
        """Dispatch each disjoint-key bucket through _join_bucket,
        closing every pile handle on generator exit (including consumer
        abandonment — close() is idempotent with the per-bucket
        finally)."""
        try:
            for s in range(len(piles_b)):
                yield from self._join_bucket(ctx, m, piles_b[s],
                                             piles_s[s], bytes_b[s],
                                             budget, depth)
        finally:
            for pile in piles_b + piles_s:
                for h in pile:
                    h.close()

    @staticmethod
    def _drain(handles):
        for h in handles:
            b = h.materialize()
            h.close()
            yield b

    def _execute_subpartitioned(self, ctx: ExecContext, m, pid, bbatches,
                                total_bytes: int, budget: int,
                                stream_batches=None):
        """Build side exceeds its budget: rehash BOTH sides into S
        disjoint-key sub-partitions parked as spillable piles, then run
        an independent join pass per sub-partition, RECURSIVELY
        re-splitting any sub-partition whose build still exceeds the
        budget (fresh hash seed per level). Keys are disjoint across
        buckets, so every join type decomposes exactly (reference:
        GpuSubPartitionHashJoin.scala:617 — 16-bucket
        repartition-and-loop)."""
        S = 2
        while S < 16 and total_bytes > S * budget:
            S *= 2
        m.add("numSubPartitions", S)

        piles_b, bytes_b, piles_s = self._split_both(
            ctx, m, S, 0xAB5, bbatches,
            stream_batches if stream_batches is not None
            else self._stream_batches(ctx, pid))
        del bbatches
        yield from self._run_buckets(ctx, m, piles_b, bytes_b, piles_s,
                                     budget, depth=1)

    def _join_bucket(self, ctx, m, bhandles, shandles, bbytes: int,
                     budget: int, depth: int):
        """Join one disjoint-key sub-partition held as spillable piles.
        Re-splits recursively while the build exceeds the budget; build
        handles stay OPEN (reservation counted) for the whole pass and
        close in a finally, so accounting reflects resident memory and
        abandoned generators leak nothing."""
        if bbytes > budget and depth < self._MAX_SUBPART_DEPTH:
            S = 2
            while S < 16 and bbytes > S * budget:
                S *= 2
            seed = (0xAB5 ^ (depth * 0x9E3779B9)) & 0x7FFFFFFF
            piles_b, bytes_b, piles_s = self._split_both(
                ctx, m, S, seed, self._drain(bhandles),
                self._drain(shandles))
            if max(bytes_b) >= bbytes:
                # degenerate (one dominant key): the split didn't shrink
                # the biggest bucket — stop recursing below, join as-is
                depth = self._MAX_SUBPART_DEPTH
            m.add("numSubPartRecursions", 1)
            yield from self._run_buckets(ctx, m, piles_b, bytes_b,
                                         piles_s, budget, depth + 1)
            return

        # terminal: one join pass. Handles stay open while their batches
        # are live (ADVICE r3: closing early releases the DeviceManager
        # reservation during the most memory-intensive phase).
        try:
            builds = [h.materialize() for h in bhandles]

            def stream_s():
                for h in shandles:
                    yield h.materialize()

            yield from self._join_pass(ctx, m, builds, stream_s())
        finally:
            for h in bhandles:
                h.close()
            for h in shandles:
                h.close()

    def _probe_batch(self, ctx, m, batch, bcvs, bmask, bkey_cvs, cap_b,
                     fast, sorted_ukey, bperm, n_valid_b, direct=None,
                     pack=()):
        """One stream batch through count/probe + expand. Yields
        ("matched_b", mask) and ("batch", DeviceBatch) items. Idempotent
        (retry/split safe): all semantics are stream-row-local and
        matched-build marks OR-accumulate in the caller."""
        with m.timer("opTime"):
            scvs, smask = batch.cvs(), batch.row_mask
            cap_s = batch.capacity
            sctx = EmitCtx(scvs, cap_s)
            skey_cvs = [k.emit(sctx) for k in self.lkeys]
            skey = skey_cvs if pack else skey_cvs[0]
            if direct is not None:
                from ..utils.transfer import fetch
                cnt, bidx = self._direct_probe(direct, skey, smask, cap_s,
                                               pack)
                if self.how == "left_semi":
                    yield ("batch", DeviceBatch(
                        batch.table, batch.num_rows,
                        smask & (cnt > 0), cap_s))
                    return
                if self.how == "left_anti":
                    yield ("batch", DeviceBatch(
                        batch.table, batch.num_rows,
                        smask & (cnt == 0), cap_s))
                    return
                n_total, n_eff, n_matched, max_cnt = (
                    int(v) for v in fetch(self._probe_stats(cnt, smask)))
                if max_cnt <= 1:
                    if self.how == "inner" and n_matched == 0:
                        return
                    matched = (cnt > 0) & smask
                    rg = jnp.clip(bidx, 0, cap_b - 1)
                    yield self._fk_output(m, batch, scvs, bcvs, rg,
                                          matched, smask, n_matched,
                                          n_eff, cap_s)
                    return
                # duplicate build keys in this batch's match set: promote
                # to the sorted fast path (built once, reused)
                if "sorted" not in direct:
                    direct["sorted"] = self._build_sorted(bkey_cvs, bmask,
                                                          pack)
                sorted_ukey, bperm, n_valid_b = direct["sorted"]
            if fast:
                pkey = ("probe", cap_b, cap_s)
                pfn = self._count_cache.get(pkey)
                if pfn is None:
                    from ..runtime.program_cache import cached_program
                    pfn = cached_program(
                        self._probe_fn(cap_b, cap_s),
                        cls=type(self).__name__, tag="probe",
                        key=self._fp + (cap_b, cap_s))
                    self._count_cache[pkey] = pfn
                (cnt, offsets, total, bstart,
                 touched) = pfn(sorted_ukey, n_valid_b, skey, smask, *pack)
                xla_stats.count_dispatch()
                perm = bperm
                if self.how in ("right", "full") and \
                        self.condition is None:
                    yield ("matched_b", self._matched_from_touched(
                        bperm, touched, n_valid_b,
                        jnp.zeros(cap_b, jnp.bool_)))
            else:
                nchunks = self._key_nchunks(bkey_cvs, bmask,
                                            skey_cvs, smask)
                if any(nchunks):
                    self._report_key_words(m, bkey_cvs, nchunks)
                ckey = (nchunks, cap_b, cap_s)
                cfn = self._count_cache.get(ckey)
                if cfn is None:
                    from ..runtime.program_cache import cached_program
                    cfn = cached_program(
                        self._count_fn(nchunks, cap_b, cap_s),
                        cls=type(self).__name__, tag="count",
                        key=self._fp + (nchunks, cap_b, cap_s))
                    self._count_cache[ckey] = cfn
                (cnt, offsets, total, bstart, perm,
                 matched_b) = cfn(bkey_cvs, bmask, skey_cvs, smask)
                xla_stats.count_dispatch()
                if self.how in ("right", "full") and \
                        self.condition is None:
                    yield ("matched_b", matched_b)
            if self.condition is not None:
                yield from self._probe_cond(m, batch, scvs, smask, cap_s,
                                            bcvs, cap_b, cnt, offsets,
                                            total, bstart, perm)
                return
            if self.how == "left_semi":
                yield ("batch", DeviceBatch(batch.table, batch.num_rows,
                                            smask & (cnt > 0), cap_s))
                return
            if self.how == "left_anti":
                yield ("batch", DeviceBatch(batch.table, batch.num_rows,
                                            smask & (cnt == 0), cap_s))
                return
            from ..utils.transfer import fetch
            n_total, n_eff, n_matched, max_cnt = (
                int(v) for v in fetch(self._probe_stats(cnt, smask)))
            with_left_nulls = self.how in ("left", "full")
            if max_cnt <= 1 and self.how in ("inner", "left"):
                # FK fast path: stream columns pass through unchanged
                if self.how == "inner" and n_matched == 0:
                    return
                rg, matched = self._fk_gather_idx(cnt, bstart, perm,
                                                  smask, cap_b)
                yield self._fk_output(m, batch, scvs, bcvs, rg, matched,
                                      smask, n_matched, n_eff, cap_s)
                return
            n_out = n_eff if with_left_nulls else n_total
            if n_out == 0:
                return
            out_cap = bucket_capacity(n_out)
            ekey = (out_cap, cap_b, cap_s, with_left_nulls)
            efn = self._expand_cache.get(ekey)
            if efn is None:
                from ..runtime.program_cache import cached_program
                efn = cached_program(
                    self._expand_fn(out_cap, cap_b, with_left_nulls),
                    cls=type(self).__name__, tag="expand",
                    key=self._fp + (out_cap, cap_b, with_left_nulls))
                self._expand_cache[ekey] = efn
            lg, rg, lvalid, rvalid, _ = efn(cnt, offsets, bstart, perm,
                                            smask)
            xla_stats.count_dispatch()
            out_cvs = self._gather_cols(scvs, lg, lvalid)
            out_cvs += self._gather_cols(bcvs, rg, rvalid)
            tbl = make_table(self.schema, out_cvs, n_out)
        m.add("numOutputRows", n_out)
        m.add("numOutputBatches", 1)
        yield ("batch", DeviceBatch(tbl, n_out,
                                    jnp.arange(out_cap) < n_out, out_cap))

    # ------------------------------------------------------------------
    def _probe_cond(self, m, batch, scvs, smask, cap_s, bcvs, cap_b,
                    cnt, offsets, total, bstart, perm):
        """Conditional-join path: expand pure candidate pairs from the
        equi keys, evaluate the bound non-equi condition on the gathered
        pair columns, then derive per-stream-row and per-build-row match
        state from the PASSING pairs only. Outer-side null extension uses
        seg_matched, not the raw candidate counts."""
        n_out = fetch_int(total)
        seg_matched = jnp.zeros(cap_s, jnp.bool_)
        if n_out > 0:
            out_cap = bucket_capacity(n_out)
            ekey = (out_cap, cap_b, cap_s, False)
            efn = self._expand_cache.get(ekey)
            if efn is None:
                from ..runtime.program_cache import cached_program
                efn = cached_program(
                    self._expand_fn(out_cap, cap_b, False),
                    cls=type(self).__name__, tag="expand",
                    key=self._fp + (out_cap, cap_b, False))
                self._expand_cache[ekey] = efn
            lg, rg, lvalid, rvalid, _ = efn(cnt, offsets, bstart, perm,
                                            smask)
            lcols = self._gather_cols(scvs, lg, lvalid)
            rcols = self._gather_cols(bcvs, rg, rvalid)
            cctx = EmitCtx(lcols + rcols, out_cap)
            ccv = self.condition.emit(cctx)
            pass_ = (lvalid & rvalid & ccv.validity
                     & ccv.data.astype(jnp.bool_))
            seg_matched = seg_matched.at[lg].max(pass_)
            if self.how in ("right", "full"):
                mb = jnp.zeros(cap_b, jnp.bool_).at[rg].max(pass_)
                yield ("matched_b", mb)
            if self.how not in ("left_semi", "left_anti"):
                tbl = make_table(self.schema, lcols + rcols, n_out)
                m.add("numOutputRows", n_out)
                m.add("numOutputBatches", 1)
                yield ("batch", DeviceBatch(tbl, n_out, pass_, out_cap))
        if self.how == "left_semi":
            yield ("batch", DeviceBatch(batch.table, batch.num_rows,
                                        smask & seg_matched, cap_s))
        elif self.how == "left_anti":
            yield ("batch", DeviceBatch(batch.table, batch.num_rows,
                                        smask & ~seg_matched, cap_s))
        elif self.how in ("left", "full"):
            # stream rows with no PASSING pair -> one null-extended row
            null_mask = smask & ~seg_matched
            out_cvs = list(batch.cvs()) + _null_cvs(
                self.children[1].schema.fields, cap_s)
            tbl = make_table(self.schema, out_cvs, batch.num_rows)
            yield ("batch", DeviceBatch(tbl, batch.num_rows, null_mask,
                                        cap_s))

    # ------------------------------------------------------------------
    def _execute_cross(self, ctx: ExecContext):
        m = ctx.metrics_for(self._op_id)
        left, right = self.children
        bcvs, bmask = self._collect_side(ctx, right, [])
        cap_b = bmask.shape[0]
        # densify build side row ids on host once
        bidx = jnp.nonzero(bmask, size=cap_b, fill_value=0)[0]
        n_b = fetch_int((jnp.sum(bmask)))
        for lpid in range(left.num_partitions(ctx)):
            for batch in left.execute_partition(ctx, lpid):
                ctx.check_cancel()
                scvs, smask = batch.cvs(), batch.row_mask
                cap_s = batch.capacity
                sidx = jnp.nonzero(smask, size=cap_s, fill_value=0)[0]
                n_s = fetch_int((jnp.sum(smask)))
                n_out = n_s * n_b
                if n_out == 0:
                    continue
                out_cap = bucket_capacity(n_out)
                t = jnp.arange(out_cap)
                li = sidx[jnp.clip(t // max(n_b, 1), 0, cap_s - 1)]
                ri = bidx[jnp.clip(t % max(n_b, 1), 0, cap_b - 1)]
                inb = t < n_out
                out_cvs = self._gather_cols(scvs, li.astype(jnp.int32), inb)
                out_cvs += self._gather_cols(bcvs, ri.astype(jnp.int32), inb)
                tbl = make_table(self.schema, out_cvs, n_out)
                m.add("numOutputRows", n_out)
                yield DeviceBatch(tbl, n_out, inb, out_cap)


class NestedLoopJoinExec(HashJoinExec):
    """Broadcast nested-loop join: no equi keys, arbitrary condition
    (reference: GpuBroadcastNestedLoopJoinExecBase.scala). The build side
    is collected once; each stream batch crosses against it in bounded
    chunks (stream-slice x full build), the condition evaluates on the
    gathered pair columns, and outer/semi/anti semantics derive from the
    passing pairs exactly as in the conditional hash join."""

    _CHUNK_TARGET = 1 << 20

    def __init__(self, left: TpuExec, right: TpuExec, how: str,
                 schema: Schema, condition: Expression):
        super().__init__(left, right, [], [], how, schema,
                         condition=condition)

    def describe(self):
        return f"NestedLoopJoinExec[{self.how}]"

    def num_partitions(self, ctx):
        return 1

    def execute_partition(self, ctx: ExecContext, pid: int):
        m = ctx.metrics_for(self._op_id)
        left, right = self.children
        with m.timer("buildTime"):
            bcvs, bmask = self._collect_side(ctx, right, [])
            cap_b = bmask.shape[0]
            bidx = jnp.nonzero(bmask, size=cap_b, fill_value=0)[0]
            n_b = fetch_int(jnp.sum(bmask))
        matched_b_acc = jnp.zeros(cap_b, jnp.bool_)
        right_fields = right.schema.fields
        for lpid in range(left.num_partitions(ctx)):
            for batch in left.execute_partition(ctx, lpid):
                ctx.check_cancel()
                scvs, smask = batch.cvs(), batch.row_mask
                cap_s = batch.capacity
                sidx = jnp.nonzero(smask, size=cap_s, fill_value=0)[0]
                n_s = fetch_int(jnp.sum(smask))
                seg_matched = jnp.zeros(cap_s, jnp.bool_)
                if n_b > 0 and n_s > 0:
                    chunk = max(1, self._CHUNK_TARGET // max(n_b, 1))
                    for s0 in range(0, n_s, chunk):
                        k = min(chunk, n_s - s0)
                        n_out = k * n_b
                        out_cap = bucket_capacity(n_out)
                        with m.timer("opTime"):
                            t = jnp.arange(out_cap)
                            li = sidx[jnp.clip(s0 + t // n_b, 0,
                                               cap_s - 1)].astype(
                                jnp.int32)
                            ri = bidx[jnp.clip(t % n_b, 0,
                                               cap_b - 1)].astype(
                                jnp.int32)
                            inb = t < n_out
                            lcols = self._gather_cols(scvs, li, inb)
                            rcols = self._gather_cols(bcvs, ri, inb)
                            cctx = EmitCtx(lcols + rcols, out_cap)
                            ccv = self.condition.emit(cctx)
                            pass_ = (inb & ccv.validity
                                     & ccv.data.astype(jnp.bool_))
                            seg_matched = seg_matched.at[li].max(pass_)
                            if self.how in ("right", "full"):
                                matched_b_acc = \
                                    matched_b_acc.at[ri].max(pass_)
                        if self.how not in ("left_semi", "left_anti"):
                            tbl = make_table(self.schema, lcols + rcols,
                                             n_out)
                            m.add("numOutputBatches", 1)
                            yield DeviceBatch(tbl, n_out, pass_, out_cap)
                if self.how == "left_semi":
                    yield DeviceBatch(batch.table, batch.num_rows,
                                      smask & seg_matched, cap_s)
                elif self.how == "left_anti":
                    yield DeviceBatch(batch.table, batch.num_rows,
                                      smask & ~seg_matched, cap_s)
                elif self.how in ("left", "full"):
                    null_mask = smask & ~seg_matched
                    out_cvs = list(batch.cvs()) + _null_cvs(
                        right_fields, cap_s)
                    tbl = make_table(self.schema, out_cvs,
                                     batch.num_rows)
                    yield DeviceBatch(tbl, batch.num_rows, null_mask,
                                      cap_s)
        if self.how in ("right", "full"):
            unmatched = bmask & ~matched_b_acc
            n_un = fetch_int(jnp.sum(unmatched))
            if n_un > 0:
                out_cvs = _null_cvs(left.schema.fields, cap_b)
                out_cvs += [CV(cv.data, cv.validity & unmatched,
                               cv.offsets) for cv in bcvs]
                tbl = make_table(self.schema, out_cvs, cap_b)
                yield DeviceBatch(tbl, cap_b, unmatched, cap_b)
