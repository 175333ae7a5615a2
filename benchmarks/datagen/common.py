"""What the table generators share: the specification's constants (TPC-H
rev. 3.0.1 clause 4.2.3), decimals from unscaled integers, and text.

Dates are int32 days since 1970-01-01, as the program's TPC-H queries take
them. Every stream is `numpy.random.default_rng([seed, stream])`, so the
same --seed gives the same tables and no column's draw moves another's."""
import concurrent.futures
import functools

import numpy as np
import pyarrow as pa

# 1992-01-01, 1995-06-17, 1998-12-31
STARTDATE, CURRENTDATE, ENDDATE = 8035, 9298, 10591
ROWS_PER_SF = {"lineitem": 6_001_215, "orders": 1_500_000,
               "customer": 150_000, "part": 200_000, "supplier": 10_000}

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
# clause 4.2.2.13: the grammar's word classes (nouns, verbs, adjectives,
# adverbs, prepositions, auxiliaries), as dbgen's dists.dss lists them
WORDS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers "
         "sauternes warthogs frets dinos attainments somas Tiresias' "
         "patterns forges braids hockey players frays warhorses dugouts "
         "notornis epitaphs pearls tithes waters orbits gifts sheaves "
         "depths sentiments decoys realms pains grouches escapades "
         "packages requests accounts deposits "
         "sleep wake are cajole haggle nag use boost affix detect "
         "integrate maintain nod was lose sublate solve thrash promise "
         "engage hinder print x-ray breach eat grow impress mold poach "
         "serve run dazzle snooze doze unwind kindle play hang believe "
         "doubt "
         "furious sly careful blithe quick fluffy slow quiet ruthless thin "
         "close dogged daring brave stealthy permanent enticing idle busy "
         "regular final ironic even bold silent special pending unusual "
         "express "
         "sometimes always never furiously slyly carefully blithely quickly "
         "fluffily slowly quietly ruthlessly thinly closely doggedly "
         "daringly bravely stealthily permanently enticingly idly busily "
         "regularly finally ironically evenly boldly silently "
         "about above according to across after against along alongside of "
         "among around at atop before behind beneath beside besides "
         "between beyond by despite during except for from in place of "
         "inside instead of into near of on outside over past since "
         "through throughout to toward under until up upon without with "
         "within "
         "do may might shall will would can could should ought to must "
         "will have to shall have to could have to should have to must "
         "have to need to try to").split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]
POOL_BYTES = 8 << 20
THREADS = 12
ALPHANUMERIC = ("0123456789abcdefghijklmnopqrstuvwxyz"
                "ABCDEFGHIJKLMNOPQRSTUVWXYZ,. ")


def stream(seed, n):
    return np.random.default_rng([int(seed), n])


def parallel(thunks):
    """The thunks' results in order, each run on a thread of its own (numpy
    and arrow let go of the interpreter in their loops): 60 M rows take a
    while on one."""
    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        return [f.result() for f in [pool.submit(t) for t in thunks]]


def rows(table, sf):
    return max(int(ROWS_PER_SF[table] * sf), 1)


def decimal_from_unscaled(vals, precision, scale):
    """A decimal128 array whose UNSCALED value is `vals` (int64)."""
    lo = np.ascontiguousarray(vals, np.int64)
    words = np.empty(2 * len(lo), np.int64)
    words[0::2] = lo
    np.right_shift(lo, 63, out=words[1::2])   # the sign's 64 bits
    return pa.Array.from_buffers(pa.decimal128(precision, scale), len(lo),
                                 [None, pa.py_buffer(words)])


def unscaled(column):
    """The unscaled int64 values of a decimal128 column (no nulls)."""
    arr = column.combine_chunks() if hasattr(column, "combine_chunks") \
        else column
    words = np.frombuffer(arr.buffers()[1], np.int64)
    return words[2 * arr.offset:2 * (arr.offset + len(arr)):2]


def pick(words, codes):
    """The string column `words[codes]`: a dictionary cast, a slice of the
    rows to a thread (6 M rows take a second in one)."""
    words = pa.array(words, pa.string())
    codes = pa.array(codes.astype(np.int32))
    step = max(-(-len(codes) // 4), 1)
    parts = parallel([
        lambda at=at: pa.DictionaryArray.from_arrays(
            codes.slice(at, step), words).cast(pa.string())
        for at in range(0, len(codes), step)])
    return pa.concat_arrays(parts) if parts else words.slice(0, 0)


def flag(letters, codes):
    """The one-character column `letters[codes]`."""
    return _strings(np.frombuffer(letters.encode(), np.uint8)[codes],
                    np.ones(len(codes), np.int64))


def numbered(prefix, numbers):
    """`prefix` + the number in 9 digits (Customer#000000001, Clerk#...)."""
    digits = (numbers[:, None] // 10 ** np.arange(8, -1, -1)) % 10 + ord("0")
    head = np.frombuffer(prefix.encode(), np.uint8)
    fixed = np.concatenate(
        [np.broadcast_to(head, (len(numbers), len(head))),
         digits.astype(np.uint8)], axis=1)
    return _strings(fixed.reshape(-1),
                    np.full(len(numbers), fixed.shape[1], np.int64))


def _strings(data, lengths):
    offsets = np.zeros(len(lengths) + 1, np.int32)
    np.cumsum(lengths, out=offsets[1:])
    return pa.Array.from_buffers(
        pa.string(), len(lengths),
        [None, pa.py_buffer(offsets),
         pa.py_buffer(np.ascontiguousarray(data))])


def _substrings(pool, rng, n, lo, hi):
    """n strings with lengths uniform in [lo, hi], cut from `pool` one after
    another from a random place on, the pool taken as a ring. (dbgen cuts
    each comment at a place of its own; a row's text is spread alike, and
    laying them end to end makes 60 M of them in a second.)"""
    lengths = rng.integers(lo, hi + 1, n)
    total = int(lengths.sum())
    turned = np.roll(pool, -int(rng.integers(0, len(pool))))
    return _strings(np.tile(turned, -(-total // len(pool)))[:total], lengths)


@functools.lru_cache(maxsize=1)
def _text_pool(seed):
    """POOL_BYTES of the grammar's words; one terminator about every 8."""
    r = stream(seed, 90)
    words = np.array(WORDS + TERMINATORS, object)
    p = np.r_[np.full(len(WORDS), 7 / len(WORDS)),
              np.full(len(TERMINATORS), 1 / len(TERMINATORS))] / 8
    picked = words[r.choice(len(words), POOL_BYTES // 5, p=p)]
    pool = np.frombuffer(" ".join(picked).encode(), np.uint8)
    assert len(pool) >= POOL_BYTES
    return pool[:POOL_BYTES]


def text(rng, seed, n, lo, hi):
    """A TEXT column (clause 4.2.2.10): lengths uniform in [lo, hi], cut
    from one pool of the grammar's words (made once per seed), as dbgen cuts
    its comments from one pool of generated sentences."""
    return _substrings(_text_pool(int(seed)), rng, n, lo, hi)


def v_string(rng, n, lo, hi):
    """A random v-string (clause 4.2.2.7): lengths uniform in [lo, hi],
    characters drawn from an alphabet of 64."""
    alphabet = np.frombuffer(ALPHANUMERIC.encode(), np.uint8)[:64]
    pool = alphabet[rng.integers(0, 64, 1 << 20)]
    return _substrings(pool, rng, n, lo, hi)
