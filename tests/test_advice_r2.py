"""Regressions for the round-1 advisor findings."""
import io

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops.concat import concat_cvs
from spark_rapids_tpu.ops.hash import murmur3_cv
from spark_rapids_tpu.ops.kernel_utils import CV


def _string_cv(strs, byte_cap=None):
    """Build a string CV whose data buffer is exactly full (or padded to
    byte_cap) to reproduce the full-capacity concat corruption."""
    bs = [s.encode() for s in strs]
    data = b"".join(bs)
    offs = np.zeros(len(bs) + 1, np.int32)
    np.cumsum([len(b) for b in bs], out=offs[1:])
    buf = np.frombuffer(data, np.uint8)
    if byte_cap is not None and byte_cap > buf.shape[0]:
        buf = np.concatenate([buf, np.zeros(byte_cap - buf.shape[0],
                                            np.uint8)])
    return CV(jnp.asarray(buf), jnp.ones(len(bs), jnp.bool_),
              jnp.asarray(offs))


def _cv_strings(cv):
    data = np.asarray(cv.data)
    offs = np.asarray(cv.offsets)
    return [bytes(data[offs[i]:offs[i + 1]]).decode()
            for i in range(offs.shape[0] - 1)]


def test_concat_full_capacity_string_batch_no_trailing_nuls():
    # part 1's data buffer is exactly full: its last row must NOT extend
    # into part 2's region after concat (a high finding of that review)
    a = _string_cv(["row0", "row127"])            # 10 bytes, exactly full
    b = _string_cv(["xx", "yy"], byte_cap=16)     # padded buffer
    out = concat_cvs([a, b], dt.STRING)
    assert _cv_strings(out) == ["row0", "row127", "xx", "yy"]


def test_concat_padded_parts_preserve_rows():
    a = _string_cv(["alpha", "b"], byte_cap=32)
    b = _string_cv(["", "gamma"], byte_cap=8)
    c = _string_cv(["zz"])
    out = concat_cvs([a, b, c], dt.STRING)
    assert _cv_strings(out) == ["alpha", "b", "", "gamma", "zz"]


# -- murmur3 oracle: Spark's Murmur3_x86_32.hashUnsafeBytes ---------------
def _i32(x):
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x >= (1 << 31) else x


def _mix_k1(k1):
    k1 = _i32(k1 * -862048943)
    k1 = _i32(((k1 & 0xFFFFFFFF) << 15) | ((k1 & 0xFFFFFFFF) >> 17))
    return _i32(k1 * 461845907)


def _mix_h1(h1, k1):
    h1 = _i32(h1 ^ k1)
    h1 = _i32(((h1 & 0xFFFFFFFF) << 13) | ((h1 & 0xFFFFFFFF) >> 19))
    return _i32(h1 * 5 + -430675100)


def _fmix(h1, length):
    h1 = _i32(h1 ^ length)
    u = h1 & 0xFFFFFFFF
    u ^= u >> 16
    u = (u * 0x85EBCA6B) & 0xFFFFFFFF
    u ^= u >> 13
    u = (u * 0xC2B2AE35) & 0xFFFFFFFF
    u ^= u >> 16
    return _i32(u)


def spark_hash_bytes(b: bytes, seed=42) -> int:
    aligned = len(b) - len(b) % 4
    h1 = seed
    for i in range(0, aligned, 4):
        word = int.from_bytes(b[i:i + 4], "little", signed=False)
        h1 = _mix_h1(h1, _mix_k1(_i32(word)))
    for i in range(aligned, len(b)):
        byte = b[i] - 256 if b[i] >= 128 else b[i]  # sign-extended
        h1 = _mix_h1(h1, _mix_k1(byte))
    return _fmix(h1, len(b))


@pytest.mark.parametrize("strs", [
    ["ab", "abc", "café", "a", "", "abcd", "abcde", "abcdef", "abcdefg"],
    ["x" * 63, "y" * 64, "ünïcödé-tail", "\x80\xff tail"],
])
def test_murmur3_string_matches_spark_oracle(strs):
    cv = _string_cv(strs)
    seed = jnp.full(len(strs), 42, jnp.int32)
    got = np.asarray(murmur3_cv(cv, dt.STRING, seed))
    want = [spark_hash_bytes(s.encode()) for s in strs]
    assert got.tolist() == want


def test_murmur3_random_lengths_vs_oracle():
    rng = np.random.default_rng(7)
    strs = ["".join(chr(rng.integers(32, 127)) for _ in range(l))
            for l in list(range(0, 25)) + [31, 33, 62, 63, 64]]
    cv = _string_cv(strs)
    got = np.asarray(murmur3_cv(cv, dt.STRING,
                                jnp.full(len(strs), 42, jnp.int32)))
    want = [spark_hash_bytes(s.encode()) for s in strs]
    assert got.tolist() == want


def test_serializer_rejects_corrupt_magic():
    from spark_rapids_tpu.shuffle.serializer import read_subbatch
    import struct
    bad = struct.pack("<IIQ", 0xDEAD, 1, 4)
    blob = struct.pack("<Q", len(bad)) + bad
    with pytest.raises(IOError):
        read_subbatch(io.BytesIO(blob), [np.dtype(np.int64)])


def test_serializer_rejects_truncated_block():
    from spark_rapids_tpu.shuffle.serializer import read_subbatch
    import struct
    blob = struct.pack("<Q", 100) + b"\x00" * 10
    with pytest.raises(IOError):
        read_subbatch(io.BytesIO(blob), [np.dtype(np.int64)])


def test_merge_partials_compacts_capacity(session):
    """Few groups over many rows: buffered partial must shrink back to a
    group-count-sized capacity after an eager merge (ADVICE.md low)."""
    import spark_rapids_tpu as st
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.expr.expressions import col

    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 256})
    n = 2048
    df = s.create_dataframe({
        "k": pa.array([i % 4 for i in range(n)], pa.int32()),
        "v": pa.array(list(range(n)), pa.int64())})
    plan = df.group_by("k").agg(F.sum("v").alias("s"))
    out = plan.to_arrow()
    got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
    want = {}
    for i in range(n):
        want[i % 4] = want.get(i % 4, 0) + i
    assert got == want

    # white-box: merging two merged partials lands at MIN_CAPACITY (128),
    # not the 2x concatenated capacity
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    root, _ = plan._execute()
    agg_nodes = [op for op in _walk_plan(root)
                 if isinstance(op, HashAggregateExec)]
    assert agg_nodes, "plan has no HashAggregateExec"
    node = agg_nodes[0]
    ks, st_, sl = _make_partial(node, 512)
    merged = node._merge_partials([(ks, st_, sl, 512), (ks, st_, sl, 512)])
    assert merged[3] == 128


def _walk_plan(node):
    yield node
    for c in node.children:
        yield from _walk_plan(c)


def _make_partial(node, cap):
    keys = CV(jnp.arange(cap, dtype=jnp.int32) % 4,
              jnp.ones(cap, jnp.bool_))
    st_ = [jnp.zeros(cap, jnp.int64), jnp.zeros(cap, jnp.int64)]
    sl = jnp.arange(cap) < 4
    return [keys], st_, sl
