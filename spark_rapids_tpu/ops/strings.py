"""String kernels over Arrow-layout (offsets + bytes) device columns.

Replaces the cudf string kernel surface (reference: stringFunctions.scala
over cudf strings; JNI CastStrings). The deep TPU problem (SURVEY.md §7.3
item 1): cuDF launches warp-per-row kernels with dynamic outputs; XLA wants
static shapes and regular parallelism. The design here works in the BYTE
DOMAIN: a byte->row map (searchsorted over offsets) turns every per-row
variable-length loop into a dense vectorized pass over the data buffer,
and per-row results come back via segment reductions. A literal pattern
(contains, LIKE, instr) needs no byte->row map: shifted compares of the
buffer mark match starts, and a prefix count of them read at each row's
bounds answers per row; startswith/endswith read only each row's ends.
Output buffers are sized by exact computed byte totals (cumsum of per-row
output lengths) — capacity equals the input's byte capacity for
non-growing ops.

ASCII-only case mapping round-1 (documented in docs/compatibility.md).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .groups import _segmented_scan
from .kernel_utils import CV

__all__ = ["byte_row_map", "str_len_bytes", "str_len_chars", "upper",
           "lower", "substring", "concat_strings", "compare", "contains",
           "startswith", "endswith", "rebuild_strings", "trim", "reverse",
           "find_first", "pad", "repeat_str", "literal_column"]


def byte_row_map(offsets, dcap: int):
    """row index for every byte position of the data buffer (garbage for
    positions beyond the last offset)."""
    pos = jnp.arange(dcap, dtype=jnp.int32)
    n = offsets.shape[0] - 1
    row = jnp.searchsorted(offsets[1:], pos, side="right").astype(jnp.int32)
    return jnp.clip(row, 0, n - 1)


def str_len_bytes(cv: CV):
    return cv.offsets[1:] - cv.offsets[:-1]


def str_len_chars(cv: CV):
    """UTF-8 aware char count: bytes minus continuation bytes."""
    n = cv.offsets.shape[0] - 1
    dcap = cv.data.shape[0]
    row = byte_row_map(cv.offsets, dcap)
    pos = jnp.arange(dcap)
    in_range = (pos >= cv.offsets[row]) & (pos < cv.offsets[row + 1])
    is_cont = (cv.data & 0xC0) == 0x80
    cont = jax.ops.segment_sum((in_range & is_cont).astype(jnp.int32),
                               row, n)
    return str_len_bytes(cv) - cont


def _map_case(cv: CV, to_upper: bool) -> CV:
    d = cv.data
    if to_upper:
        is_lower = (d >= 97) & (d <= 122)
        out = jnp.where(is_lower, d - 32, d)
    else:
        is_upper = (d >= 65) & (d <= 90)
        out = jnp.where(is_upper, d + 32, d)
    return CV(out.astype(jnp.uint8), cv.validity, cv.offsets)


def upper(cv: CV) -> CV:
    return _map_case(cv, True)


def lower(cv: CV) -> CV:
    return _map_case(cv, False)


def rebuild_strings(cv: CV, new_starts, new_lens,
                    out_data_capacity: Optional[int] = None,
                    wrap=None) -> CV:
    """Build a new string column where row i is the byte range
    [new_starts[i], new_starts[i]+new_lens[i]) of cv.data. With `wrap`
    (per-row period), source bytes repeat cyclically every wrap[i] bytes
    (the repeat() kernel)."""
    n = new_lens.shape[0]
    new_lens = jnp.maximum(new_lens, 0)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(new_lens).astype(jnp.int32)])
    out_cap = out_data_capacity or cv.data.shape[0]
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off[1:], pos, side="right"),
                   0, n - 1).astype(jnp.int32)
    rel = pos - new_off[row]
    if wrap is not None:
        rel = rel % jnp.maximum(wrap[row], 1)
    src = new_starts[row] + rel
    src = jnp.clip(src, 0, cv.data.shape[0] - 1)
    data = cv.data[src]
    total = new_off[n]
    data = jnp.where(pos < total, data, 0).astype(jnp.uint8)
    return CV(data, cv.validity, new_off)


def substring(cv: CV, start: int, length: Optional[int]) -> CV:
    """Spark substring: 1-based start; negative counts from the end;
    byte-based round-1 (exact for ASCII; documented deviation)."""
    lens = str_len_bytes(cv)
    if start > 0:
        s = jnp.minimum(start - 1, lens)
    elif start == 0:
        s = jnp.zeros_like(lens)
    else:
        s = jnp.maximum(lens + start, 0)
    if length is None:
        ln = lens - s
    else:
        ln = jnp.minimum(jnp.maximum(length, 0), lens - s)
    return rebuild_strings(cv, cv.offsets[:-1] + s.astype(jnp.int32),
                           ln.astype(jnp.int32))


def concat_strings(cvs: List[CV], out_data_capacity: int) -> CV:
    """Row-wise concatenation of string columns (null if any input null,
    Spark concat semantics)."""
    n = cvs[0].offsets.shape[0] - 1
    lens = [str_len_bytes(c) for c in cvs]
    tot = sum(lens)
    valid = cvs[0].validity
    for c in cvs[1:]:
        valid = valid & c.validity
    tot = jnp.where(valid, tot, 0)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(tot).astype(jnp.int32)])
    pos = jnp.arange(out_data_capacity, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off[1:], pos, side="right"),
                   0, n - 1).astype(jnp.int32)
    rel = pos - new_off[row]
    # which source column does each output byte come from?
    out = jnp.zeros(out_data_capacity, jnp.uint8)
    acc = jnp.zeros(n, jnp.int32)
    for c, ln in zip(cvs, lens):
        ln = ln.astype(jnp.int32)
        in_this = (rel >= acc[row]) & (rel < acc[row] + ln[row])
        src = c.offsets[row] + (rel - acc[row])
        src = jnp.clip(src, 0, c.data.shape[0] - 1)
        out = jnp.where(in_this, c.data[src], out)
        acc = acc + ln
    total = new_off[n]
    out = jnp.where(pos < total, out, 0).astype(jnp.uint8)
    return CV(out, valid, new_off)


def equals_literal(cv: CV, raw: bytes):
    """Row == constant-string: length check + big-endian 4-byte chunk
    compares — O(rows * len/4) gathers. The general `compare` walks the
    column's whole BYTE domain with a segment_min (O(bytes)), which is
    ~30x more work for a short literal against a long column; XLA also
    CSEs the chunk extraction across many literal compares on the same
    column (q19's 12 container compares cost one extraction). Exact for
    any byte content: equal length + equal zero-padded chunks <=> equal
    bytes."""
    from .sortkeys import string_chunk_keys
    n = cv.offsets.shape[0] - 1
    lens = cv.offsets[1:] - cv.offsets[:-1]
    L = len(raw)
    ok = lens == L
    nch = (L + 3) // 4
    if nch:
        ks = string_chunk_keys(cv, nch)
        for i in range(nch):
            word = int.from_bytes(raw[i * 4:(i + 1) * 4].ljust(4, b"\0"),
                                  "big")
            ok = ok & (ks[i] == jnp.uint32(word))
    return ok


def compare(a: CV, b: CV):
    """Per-row byte-lexicographic compare: returns int8 in {-1,0,1}.
    Works over a's byte domain + a length tiebreak."""
    n = a.offsets.shape[0] - 1
    la = str_len_bytes(a)
    lb = str_len_bytes(b)
    dcap = a.data.shape[0]
    row = byte_row_map(a.offsets, dcap)
    pos = jnp.arange(dcap, dtype=jnp.int32)
    rel = pos - a.offsets[row]
    within = (rel >= 0) & (rel < jnp.minimum(la, lb)[row])
    bsrc = jnp.clip(b.offsets[row] + rel, 0, b.data.shape[0] - 1)
    abyte = a.data
    bbyte = b.data[bsrc]
    differs = within & (abyte != bbyte)
    first_diff = jax.ops.segment_min(
        jnp.where(differs, rel, jnp.int32(2**30)), row, n)
    has_diff = first_diff < 2**30
    # byte values at the first differing position
    asrc = jnp.clip(a.offsets[:-1] + first_diff, 0, dcap - 1)
    bsrc2 = jnp.clip(b.offsets[:-1] + first_diff, 0, b.data.shape[0] - 1)
    av = a.data[asrc].astype(jnp.int32)
    bv = b.data[bsrc2].astype(jnp.int32)
    cmp_diff = jnp.sign(av - bv)
    cmp_len = jnp.sign(la - lb)
    return jnp.where(has_diff, cmp_diff, cmp_len).astype(jnp.int8)


def _match_starts(data, pattern: bytes, wildcard=None):
    """bool per byte position: the pattern's bytes start here. One
    compare of the byte buffer a pattern byte, against the buffer
    shifted by that byte's place (padded so each shift keeps its
    length); bytes equal to `wildcard` (e.g. ord('_')) match anything.
    A start whose match runs past its row is the caller's to drop."""
    dcap = data.shape[0]
    m = len(pattern)
    padded = jnp.concatenate([data, jnp.zeros((m,), data.dtype)])
    hit = jnp.ones((dcap,), jnp.bool_)
    for j, pb in enumerate(pattern):
        if wildcard is not None and pb == wildcard:
            continue
        hit = hit & (padded[j:j + dcap] == pb)
    return hit


def _starts_in(cv: CV, pattern: bytes, wildcard, skip_prefix: int,
               skip_suffix: int):
    """Per row: the prefix count of match starts, the count before the
    row's first admissible start, and whether a start lies in
    [offset + skip_prefix, end - skip_suffix - m]. A match that would run
    into the next row, or into the bytes past the last offset, starts
    after that range and is never counted. Reads the count at 2n
    positions: nothing per byte is gathered, scattered or searched. The
    count is the doubling scan of `ops/groups.py`: `jnp.cumsum` and
    `lax.associative_scan` take 50-70 s to compile for a v5e at 1 Mi
    elements."""
    m = len(pattern)
    hit = _match_starts(cv.data, pattern, wildcard)
    count = _segmented_scan(jnp.add, jnp.arange(hit.shape[0]) == 0,
                            hit.astype(jnp.int32))
    lo = cv.offsets[:-1] + skip_prefix
    hi = cv.offsets[1:] - skip_suffix - m
    last = count.shape[0] - 1
    before = jnp.where(lo > 0, count[jnp.clip(lo - 1, 0, last)], 0)
    found = (hi >= lo) & (count[jnp.clip(hi, 0, last)] > before)
    return count, before, found


def contains(cv: CV, pattern: bytes, wildcard=None,
             skip_prefix: int = 0, skip_suffix: int = 0):
    """True per row when pattern occurs within
    [skip_prefix, len-skip_suffix) — the bounds let LIKE exclude the
    bytes already consumed by its prefix/suffix runs."""
    n = cv.offsets.shape[0] - 1
    if len(pattern) == 0:
        return jnp.ones(n, jnp.bool_)
    if cv.data.shape[0] < len(pattern):
        return jnp.zeros(n, jnp.bool_)
    return _starts_in(cv, pattern, wildcard, skip_prefix, skip_suffix)[2]


def _bytes_at(cv: CV, start, pattern: bytes, wildcard=None):
    """Per row: the row is long enough and its bytes from `start` on
    equal the pattern's; one gather of n elements a pattern byte."""
    lens = str_len_bytes(cv)
    dcap = cv.data.shape[0]
    ok = lens >= len(pattern)
    if dcap < len(pattern):
        return jnp.zeros_like(ok)
    for j, pb in enumerate(pattern):
        if wildcard is not None and pb == wildcard:
            continue
        ok = ok & (cv.data[jnp.clip(start + j, 0, dcap - 1)] == pb)
    return ok


def startswith(cv: CV, pattern: bytes, wildcard=None):
    return _bytes_at(cv, cv.offsets[:-1], pattern, wildcard)


def endswith(cv: CV, pattern: bytes, wildcard=None):
    return _bytes_at(cv, cv.offsets[1:] - len(pattern), pattern, wildcard)


def trim(cv: CV, left: bool = True, right: bool = True) -> CV:
    """Strip ASCII spaces (Spark trim/ltrim/rtrim trim ' ' by default).
    Unbounded: one byte-domain pass finds each row's first/last non-space
    via segment reductions."""
    lens = str_len_bytes(cv)
    n = lens.shape[0]
    dcap = cv.data.shape[0]
    starts = cv.offsets[:-1]
    row = byte_row_map(cv.offsets, dcap)
    pos = jnp.arange(dcap, dtype=jnp.int32)
    rel = pos - starts[row]
    in_range = (rel >= 0) & (rel < lens[row])
    non_space = in_range & (cv.data != 32)
    first_rel = jax.ops.segment_min(
        jnp.where(non_space, rel, jnp.int32(2**30)), row, n)
    last_rel = jax.ops.segment_max(
        jnp.where(non_space, rel, jnp.int32(-1)), row, n)
    all_space = first_rel >= 2**30
    lead = jnp.where(left, jnp.where(all_space, lens, first_rel), 0)
    end = jnp.where(right, last_rel + 1, lens)
    new_len = jnp.maximum(end - lead, 0)
    new_len = jnp.where(all_space, 0, new_len)
    return rebuild_strings(cv, (starts + lead).astype(jnp.int32),
                           new_len.astype(jnp.int32))


def reverse(cv: CV) -> CV:
    """Byte-reverse each row (exact for ASCII; documented deviation)."""
    n = cv.offsets.shape[0] - 1
    dcap = cv.data.shape[0]
    row = byte_row_map(cv.offsets, dcap)
    pos = jnp.arange(dcap, dtype=jnp.int32)
    rel = pos - cv.offsets[row]
    lens = str_len_bytes(cv)
    src = cv.offsets[row] + (lens[row] - 1 - rel)
    src = jnp.clip(src, 0, dcap - 1)
    in_range = (rel >= 0) & (rel < lens[row])
    data = jnp.where(in_range, cv.data[src], 0).astype(jnp.uint8)
    return CV(data, cv.validity, cv.offsets)


def find_first(cv: CV, pattern: bytes):
    """1-based position of the first occurrence per row; 0 if absent
    (Spark instr/locate semantics), counted in bytes. The first start
    of a row is the first position whose prefix count passes the count
    before the row: a binary search of n queries over the count."""
    n = cv.offsets.shape[0] - 1
    if len(pattern) == 0:
        return jnp.ones(n, jnp.int32)
    if cv.data.shape[0] < len(pattern):
        return jnp.zeros(n, jnp.int32)
    count, before, found = _starts_in(cv, pattern, None, 0, 0)
    first = jnp.searchsorted(count, before, side="right").astype(jnp.int32)
    return jnp.where(found, first - cv.offsets[:-1] + 1, 0).astype(
        jnp.int32)




def pad(cv: CV, target_len: int, pad_bytes: bytes, left: bool) -> CV:
    """lpad/rpad to target_len BYTES with a cyclic literal pad; rows
    longer than target are truncated to it. Byte-based (exact for ASCII;
    documented deviation in docs/compatibility.md — Spark counts chars).
    Spark edge semantics honored: negative target -> empty strings; empty
    pad -> truncate only, never extend."""
    import numpy as np
    target_len = max(int(target_len), 0)
    lens = str_len_bytes(cv)
    n = lens.shape[0]
    if len(pad_bytes) == 0:
        # Spark: empty pad never extends; rows only truncate to target
        return rebuild_strings(cv, cv.offsets[:-1],
                               jnp.minimum(lens, target_len)
                               .astype(jnp.int32))
    new_off = jnp.arange(n + 1, dtype=jnp.int32) * target_len
    out_cap = max(int(n * target_len), 1)
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(pos // max(target_len, 1), 0, n - 1)
    rel = pos - row * target_len
    cur = jnp.minimum(lens, target_len)
    padlen = max(len(pad_bytes), 1)
    pad_arr = jnp.asarray(np.frombuffer(
        pad_bytes if pad_bytes else b"\0", np.uint8))
    if left:
        npad = target_len - cur
        from_pad = rel < npad[row]
        src_data = cv.offsets[row] + (rel - npad[row])
        pad_idx = rel % padlen
    else:
        from_pad = rel >= cur[row]
        src_data = cv.offsets[row] + rel
        pad_idx = (rel - cur[row]) % padlen
    src_data = jnp.clip(src_data, 0, cv.data.shape[0] - 1)
    out = jnp.where(from_pad, pad_arr[jnp.clip(pad_idx, 0, padlen - 1)],
                    cv.data[src_data]).astype(jnp.uint8)
    return CV(out, cv.validity, new_off)


def repeat_str(cv: CV, times: int, out_data_capacity: int) -> CV:
    """Repeat each row `times` times (Spark repeat; times<=0 -> empty)."""
    times = max(times, 0)
    lens = str_len_bytes(cv)
    return rebuild_strings(cv, cv.offsets[:-1],
                           (lens * times).astype(jnp.int32),
                           out_data_capacity, wrap=lens)


def literal_column(raw: bytes, present, capacity: int) -> CV:
    """String CV holding `raw` where `present` is True, '' elsewhere
    (always valid) — the concat_ws separator builder."""
    import numpy as np
    n = present.shape[0]
    nb = max(len(raw), 1)
    lens = jnp.where(present, len(raw), 0).astype(jnp.int32)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    out_cap = max(capacity, 1)
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off[1:], pos, side="right"),
                   0, n - 1).astype(jnp.int32)
    rel = pos - new_off[row]
    src = jnp.asarray(np.frombuffer(raw.ljust(nb, b"\0"), np.uint8))
    data = src[jnp.clip(rel, 0, nb - 1)]
    total = new_off[n]
    data = jnp.where(pos < total, data, 0).astype(jnp.uint8)
    return CV(data, jnp.ones(n, jnp.bool_), new_off)


def str_equal_rowmap(ecv: CV, vcv: CV, rows, live):
    """bool[ecap]: element string e equals the per-row string
    vcv[rows[e]]. Compares in the element byte domain with a row-mapped
    source index — no replication gather, so no output-capacity sizing is
    needed (used by array_contains / map element_at over strings)."""
    n = ecv.offsets.shape[0] - 1
    le = str_len_bytes(ecv)
    lv = str_len_bytes(vcv)
    lv_e = lv[rows]
    len_ok = le == lv_e
    dcap = ecv.data.shape[0]
    rowb = byte_row_map(ecv.offsets, dcap)       # element index per byte
    pos = jnp.arange(dcap, dtype=jnp.int32)
    rel = pos - ecv.offsets[rowb]
    lim = jnp.minimum(le, lv_e)
    within = (rel >= 0) & (rel < lim[rowb])
    vsrc = jnp.clip(vcv.offsets[rows[rowb]] + rel, 0,
                    vcv.data.shape[0] - 1)
    differs = within & (ecv.data != vcv.data[vsrc])
    any_diff = jax.ops.segment_max(differs.astype(jnp.int32), rowb, n) > 0
    return (len_ok & ~any_diff & ecv.validity & vcv.validity[rows] & live)
