"""Sort-by-target: bring a batch's rows into stable target order.

The one routine behind every hash/range/round-robin partitioning in the
tree: the one-chip exchange map (`exec/exchange.py:_finish_map`) and the
mesh exchange (`parallel/collectives.py:exchange_cvs`) both call it. The
payload rides the sort, it is not gathered after it: on a v5e a gather
by a sorted index costs 20 ns an element and a scatter the same, a
two-operand stable sort about 2 ns an element a 32-bit word (PERF.md,
PR 30 and PR 32). Nothing here names a mesh axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["runs_by_target", "sorted_by_target", "to_words", "from_words",
           "word_count"]


def runs_by_target(mask, pids, n_shards: int):
    """(eff_pid, starts): a row's effective target (dead rows go to
    bucket n, past every peer's) and, for rows brought into stable
    target order, where each peer's run begins: starts [n+1], starts[n]
    the live row count. Counted, not searched: n reductions."""
    eff_pid = jnp.where(mask, pids, n_shards).astype(jnp.int32)
    per_target = jnp.sum(
        eff_pid[None, :] == jnp.arange(n_shards, dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(per_target)])
    return eff_pid, starts


def _carrier(dtype):
    """The type an array travels as: itself, or int32 where narrower."""
    return dtype if dtype.itemsize >= 4 else jnp.dtype(jnp.int32)


def word_count(arrays) -> int:
    """Rows of `to_words(arrays)`: the 32-bit words a row of the payload
    takes through the sort. Reads shapes and dtypes only."""
    flags = sum(a.dtype == jnp.bool_ for a in arrays)
    return -(-flags // 32) + sum(
        _carrier(a.dtype).itemsize // 4 * (a.size // a.shape[0])
        for a in arrays if a.dtype != jnp.bool_)   # a float64 counts two


def to_words(arrays):
    """Every array [cap, ...] as rows of ONE uint32 [W, cap]: a 64-bit
    value is two rows, a narrow integer widens to one, trailing dims
    (decimal128 limb pairs) are rows of their own, and the bool arrays
    share rows a bit each."""
    cap = arrays[0].shape[0]
    rows, flags = [], []
    for a in arrays:
        if a.dtype == jnp.bool_:
            flags.append(a)
            continue
        w = jax.lax.bitcast_convert_type(a.astype(_carrier(a.dtype)),
                                         jnp.uint32).reshape(cap, -1)
        rows += [w[:, j] for j in range(w.shape[1])]
    for i in range(0, len(flags), 32):
        word = jnp.zeros(cap, jnp.uint32)
        for bit, f in enumerate(flags[i:i + 32]):
            word = word | (f.astype(jnp.uint32) << bit)
        rows.append(word)
    return jnp.stack(rows)


def from_words(words, like):
    """Inverse of `to_words`: arrays shaped and typed as `like`."""
    out, r, flags = [], 0, []
    for a in like:
        if a.dtype == jnp.bool_:
            flags.append(len(out))
            out.append(None)
            continue
        carrier = _carrier(a.dtype)
        per = carrier.itemsize // 4
        k = per * (a.size // a.shape[0])
        w = jnp.stack(list(words[r:r + k]), axis=1)
        r += k
        w = w.reshape(a.shape + ((2,) if per == 2 else ()))
        out.append(jax.lax.bitcast_convert_type(w, carrier).astype(a.dtype))
    for k, at in enumerate(flags):
        out[at] = ((words[r + k // 32] >> (k % 32)) & 1).astype(jnp.bool_)
    return out


def _wordless(a) -> bool:
    """A float64 array cannot become words: the TPU's x64 rewrite has no
    f64 bitcast (as `ops/hash.py` and `ops/sortkeys.py` found)."""
    return a.dtype == jnp.float64


def sorted_by_target(eff_pid, arrays):
    """`arrays` in stable target order. The payload rides the sort: each
    32-bit word of it is the second operand of the SAME two-operand
    stable sort by target, one word a turn of a loop, so the program
    holds one sort to compile however wide the rows are (two where a
    float64 array is among them: those ride a sort of their own type, a
    column a turn). On a v5e a gather by a sorted index costs 20 ns an
    element (192 ms for six arrays of 1.5 M rows), these sorts 3.4 ms a
    word (25 ms); all words as operands of one variadic sort run in 11 ms
    but take 17 s more to compile for every word (PERF.md, PR 30). A
    float64 column of 1 Mi rows: 2.3 ms by its sort, 18.1 ms by a row
    index that rode the words (PERF.md, PR 32)."""
    def ride(rows):
        return jax.lax.map(
            lambda w: jax.lax.sort((eff_pid, w), num_keys=1,
                                   is_stable=True)[1], rows)

    words = [a for a in arrays if not _wordless(a)]
    doubles = [a for a in arrays if _wordless(a)]
    by_word = iter(from_words(ride(to_words(words)), words) if words else ())
    by_double = iter(ride(jnp.stack(doubles)) if doubles else ())
    return [next(by_double if _wordless(a) else by_word) for a in arrays]
