"""Order-key normalization: map any column to TPU-sortable key arrays.

TPU-native replacement for cudf's comparator-based sort/groupby
(reference: SortUtils.scala, cudf OrderByArg). Design constraint: TPU has no
native 64-bit lanes — XLA emulates s64/f64 — and the x64 rewrite cannot
implement f64<->s64 bitcasts. So keys avoid 64-bit bitcasts entirely:

  - bool/ints/decimal/date/timestamp: the value itself (signed order);
    descending = bitwise NOT (exact order reversal, no overflow)
  - float32: IEEE bitcast trick on 32-bit (supported): uint32 radix key;
    NaN canonicalized and ordered greatest (Spark), -0.0 == +0.0
  - float64: TWO keys (isnan, canonical value). NaN rows get canonical 0.0
    so equality/boundary checks are NaN-safe, and the isnan key orders NaN
    greatest per Spark; -0.0 canonicalized to +0.0
  - strings/binary: big-endian 4-byte chunks as uint32 (nchunks static
    per trace); padding 0x00 sorts first = byte-lexicographic order

Ascending argsort over the returned key list (most-significant first)
yields Spark's ordering; `group_boundaries` on the same arrays is exact
(no NaNs survive canonicalization).
"""
from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp

from ..columnar import dtypes as dt
from .kernel_utils import CV
from .partition import sorted_by_target

__all__ = ["order_keys", "string_chunk_keys", "lexsort", "lexsort_riding",
           "group_boundaries", "nchunks_for_len"]


def nchunks_for_len(maxlen: int) -> int:
    """Chunk count for string keys of max byte length `maxlen`, rounded
    onto the shape-bucket grid (columnar/column.py set_bucket_policy) so
    chunk-count program signatures canonicalize the same way capacities
    do. The default grid keeps the historical next-power-of-two."""
    from ..columnar.column import bucket_chunks
    return bucket_chunks(max(1, -(-maxlen // 4)))


def _f32_key(x, descending):
    x = jnp.where(x == 0, jnp.zeros_like(x), x)          # -0.0 -> +0.0
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
    b = x.view(jnp.int32).view(jnp.uint32)
    sign = jnp.uint32(0x80000000)
    k = jnp.where((b & sign) != 0, ~b, b | sign)
    return [~k if descending else k]


def _f64_keys(x, descending):
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    nan = jnp.isnan(x)
    canon = jnp.where(nan, jnp.zeros_like(x), x)
    nankey = nan.astype(jnp.uint8)                        # NaN greatest
    if descending:
        return [~nankey, -canon]
    return [nankey, canon]


def order_keys(cv: CV, dtype: dt.DataType, nchunks: int = 0,
               descending: bool = False) -> List[jnp.ndarray]:
    """Key arrays for one column (excluding the null key), most-significant
    first. Ascending unsigned/signed order of the keys == requested order."""
    if isinstance(dtype, (dt.StringType, dt.BinaryType)):
        ks = string_chunk_keys(cv, nchunks)
        return [~k for k in ks] if descending else ks
    x = cv.data
    if isinstance(dtype, dt.BooleanType):
        k = x.astype(jnp.uint8)
        return [~k if descending else k]
    if isinstance(dtype, dt.FloatType):
        return _f32_key(x, descending)
    if isinstance(dtype, dt.DoubleType):
        return _f64_keys(x, descending)
    if isinstance(dtype, dt.NullType):
        return [jnp.zeros(cv.capacity, jnp.uint8)]
    if isinstance(dtype, dt.DecimalType) and dtype.is_decimal128:
        # two keys: signed hi limb, then lo limb mapped to signed-
        # comparable order (bias flip of the top bit)
        hi = x[:, 1]
        lo = x[:, 0] ^ jnp.int64(-(1 << 63))   # flip the sign bit
        if descending:
            return [~hi, ~lo]
        return [hi, lo]
    # integral / decimal / date / timestamp: natural signed order
    return [~x if descending else x]


def string_chunk_keys(cv: CV, nchunks: int) -> List[jnp.ndarray]:
    """Big-endian uint32 4-byte chunk keys (32-bit native on TPU)."""
    n = cv.offsets.shape[0] - 1
    starts = cv.offsets[:-1]
    lens = cv.offsets[1:] - starts
    keys = []
    data = cv.data
    dcap = data.shape[0]
    for c in range(nchunks):
        base = starts + 4 * c
        key = jnp.zeros(n, jnp.uint32)
        for b in range(4):
            pos = base + b
            inb = (4 * c + b) < lens
            idx = jnp.clip(pos, 0, dcap - 1)
            byte = jnp.where(inb, data[idx], 0).astype(jnp.uint32)
            key = (key << 8) | byte
        keys.append(key)
    return keys


def lexsort(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stable permutation ordering rows by keys[0], then keys[1], ...

    On the CPU backend: ONE variadic `lax.sort` over all key arrays
    (lexicographic, stable) with an iota payload operand that becomes
    the permutation. Off the CPU: chained stable two-operand sorts, one
    per 32-bit word of the keys, least significant first
    (`_lexsort_lsd32`), because the TPU compiler's time grows steeply
    with the operands of a variadic sort.
    """
    import jax
    n = keys[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if jax.default_backend() != "cpu":
        return _lexsort_lsd32(keys, iota)
    ops = list(keys) + [iota]
    out = jax.lax.sort(ops, num_keys=len(keys), is_stable=True)
    return out[-1]


def _words32(keys: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """`keys` as 32-bit words, most-significant first, whose lexicographic
    order is the keys' own: runs of up to four 8-bit flag keys pack into
    one uint32, a 64-bit integer splits into (high word keeping its sign,
    low word unsigned). float64 keys stay one 64-bit word (no f64<->s64
    bitcast exists under the x64 rewrite)."""
    words, flags = [], []

    def flush():
        if flags:
            w = flags[0].astype(jnp.uint32)
            for f in flags[1:]:
                w = (w << 8) | f.astype(jnp.uint32)
            words.append(w)
            flags.clear()

    for k in keys:
        if k.dtype in (jnp.uint8, jnp.bool_):
            flags.append(k)
            if len(flags) == 4:
                flush()
            continue
        flush()
        if k.dtype == jnp.int64:
            words += [(k >> 32).astype(jnp.int32), k.astype(jnp.uint32)]
        elif k.dtype == jnp.uint64:
            words += [(k >> 32).astype(jnp.uint32), k.astype(jnp.uint32)]
        elif k.dtype in (jnp.int8, jnp.int16):
            words.append(k.astype(jnp.int32))
        else:
            words.append(k)
    flush()
    return words


def _lexsort_lsd32(keys: Sequence[jnp.ndarray], iota) -> jnp.ndarray:
    """The same stable permutation as the variadic sort, as one stable
    single-key sort per 32-bit word, least-significant word first.

    The TPU compiler's time for a sort grows steeply with the operand
    count and with 8-bit and emulated 64-bit operands (q3's top-k sort,
    six keys of which two s64, took 408 s to compile for a v5e at 128 Ki
    rows; seven chained (uint32 key, int32 perm) sorts of the same rows
    took 22 s), so off the CPU every pass sorts exactly two 32-bit
    operands."""
    import jax
    perm = iota
    for i, w in enumerate(reversed(_words32(keys))):
        _, perm = jax.lax.sort([w if i == 0 else w[perm], perm],
                               num_keys=1, is_stable=True)
    return perm


def lexsort_riding(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """`lexsort`'s permutation with no row fetched by index, the same
    program on every backend: one stable sort per 32-bit word of the
    keys, least significant first, and the words not yet used ride
    each pass beside the permutation (`ops/partition.py`) instead of
    being gathered by it. K words cost K(K+1)/2 word-sorts and K sorts
    to compile. On a v5e, 1 Mi rows and three words: 10.6 ms against
    20.2 ms for `lexsort`'s chain and its two gathers (PERF.md, PR 36)."""
    words = list(_words32(keys))
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    while words:
        key = words.pop()
        *words, perm = sorted_by_target(key, words + [perm])
    return perm


def group_boundaries(sorted_keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """bool[n]: True where row starts a new group (row 0 is True)."""
    n = sorted_keys[0].shape[0]
    new = jnp.zeros(n, jnp.bool_).at[0].set(True)
    for k in sorted_keys:
        prev = jnp.roll(k, 1)
        new = new | (k != prev).at[0].set(True)
    return new


def string_nchunks(cv: CV, mask) -> int:
    """Static order-key chunk count covering the longest live+valid
    string (shared by aggregate/join/collect key sizing: dead and padding
    rows must not inflate the count)."""
    from ..utils.transfer import fetch_int
    lens = cv.offsets[1:] - cv.offsets[:-1]
    lens = jnp.where(mask & cv.validity, lens, 0)
    mx = fetch_int(jnp.max(lens)) if lens.shape[0] else 0
    return nchunks_for_len(max(mx, 1))
