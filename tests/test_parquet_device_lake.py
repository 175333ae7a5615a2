"""The device Parquet decode of the chunk shapes a pyarrow- or Hive-written
lake holds, each against pyarrow's decode of the same file (the plain
reference): FIXED_LEN_BYTE_ARRAY decimals of 1-8 bytes, a dictionary
chunk whose index bit width grows page by page, and dictionary chunks
that give way to PLAIN pages once the dictionary outgrows its page limit.
Then TPC-H q3 over the benchmark's three generated tables as its Parquet
storage writes them, decoded on the device, against the plain reference."""
import decimal
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from spark_rapids_tpu.columnar.column import bucket_capacity  # noqa: E402
from spark_rapids_tpu.io import parquet_device as pdv  # noqa: E402

DEVICE_DECODE = "spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled"


def _expected(col):
    """(values, validity) of a pyarrow column: a decimal as its unscaled
    int64, a string as bytes."""
    arr = col.combine_chunks()
    valid = ~np.asarray(arr.is_null().to_numpy(zero_copy_only=False))
    if pa.types.is_decimal(arr.type):
        words = np.frombuffer(arr.buffers()[1], np.int64)
        return words[2 * arr.offset:2 * (arr.offset + len(arr)):2], valid
    if pa.types.is_string(arr.type):
        return [None if v is None else v.encode()
                for v in arr.to_pylist()], valid
    return np.asarray(arr.fill_null(0).to_numpy(zero_copy_only=False)), \
        valid


def _decode_all(path, names, dtype=None):
    """{name: [(got, nrows, layout)]} over the file's row groups: every
    chunk must take the device path."""
    pf = pq.ParquetFile(path)
    out = {}
    for rg in range(pf.metadata.num_row_groups):
        nrows = pf.metadata.row_group(rg).num_rows
        elig = pdv.eligible_chunks(pf, rg, names)
        assert set(elig) == set(names), pdv.fallback_reasons(pf, rg, names)
        for name in names:
            c = pdv.chunk_device_plan(pf, path, rg, elig[name], name,
                                      pf.schema_arrow.field(name).nullable)
            lay = pdv._layout(c, np.frombuffer(c.raw, np.uint8),
                              bucket_capacity(nrows))
            got = pdv.decode_chunk_device(c, bucket_capacity(nrows),
                                          dtype=dtype)
            c.close(sync=True)
            out.setdefault(name, []).append((got, nrows, lay))
    return out


def _assert_same(path, table, names, dtype=None):
    decoded = _decode_all(path, names, dtype)
    for name in names:
        want, want_valid = _expected(table.column(name))
        at = 0
        for got, nrows, _lay in decoded[name]:
            w, wv = want[at:at + nrows], want_valid[at:at + nrows]
            if len(got) == 3:                              # strings
                data, valid, offsets = (np.asarray(x) for x in got)
                valid = valid[:nrows]
                np.testing.assert_array_equal(valid, wv, err_msg=name)
                vals = [bytes(data[offsets[r]:offsets[r + 1]])
                        if valid[r] else None for r in range(nrows)]
                assert vals == list(w), name
            else:
                vals, valid = (np.asarray(x)[:nrows] for x in got)
                np.testing.assert_array_equal(valid, wv, err_msg=name)
                np.testing.assert_array_equal(vals[valid],
                                              np.asarray(w)[wv],
                                              err_msg=name)
            at += nrows
    return decoded


# precision -> FIXED_LEN_BYTE_ARRAY width: 1, 2, ..., 8 bytes
PRECISION_OF_WIDTH = {1: 2, 2: 4, 3: 6, 4: 9, 5: 11, 6: 14, 7: 16, 8: 18}


def _decimals(n, precision, seed, nulls, distinct=None):
    rng = np.random.default_rng(seed)
    top = 10 ** precision - 1
    if distinct is None:
        unscaled = rng.integers(-top, top + 1, n, dtype=np.int64)
    else:
        pool = rng.integers(-top, top + 1, distinct, dtype=np.int64)
        unscaled = pool[rng.integers(0, distinct, n)]
    unscaled[:4] = [top, -top, 0, -1]      # the extremes and the sign
    mask = rng.random(n) < 0.15 if nulls else np.zeros(n, bool)
    vals = [None if m else decimal.Decimal(int(u)).scaleb(-2)
            for u, m in zip(unscaled, mask)]
    return pa.array(vals, pa.decimal128(precision, 2))


@pytest.mark.parametrize("width", range(1, 9))
@pytest.mark.parametrize("use_dict,pagever,nulls", [
    (False, "1.0", True), (True, "1.0", True), (False, "2.0", False),
    (True, "2.0", True)])
def test_flba_decimals_of_every_width(tmp_path, width, use_dict, pagever,
                                      nulls):
    precision = PRECISION_OF_WIDTH[width]
    t = pa.table({"d": _decimals(3000, precision, width, nulls,
                                 distinct=40 if use_dict else None)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression="snappy", use_dictionary=use_dict,
                   data_page_version=pagever, data_page_size=2048)
    assert pq.ParquetFile(path).schema.column(0).length == width
    decoded = _assert_same(path, t, ["d"], dtype="int64")
    lay = decoded["d"][0][2]
    assert lay.has_dict == use_dict
    assert lay.nulls == nulls


def test_flba_past_eight_bytes_stays_on_the_host(tmp_path):
    t = pa.table({"d": pa.array([decimal.Decimal("1.25")] * 100,
                                pa.decimal128(20, 2))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    pf = pq.ParquetFile(path)
    assert pdv.eligible_chunks(pf, 0, ["d"]) == {}
    assert pdv.fallback_reasons(pf, 0, ["d"])["d"][0] == "type"


@pytest.mark.parametrize("kind", ["int64", "decimal", "string"])
def test_bit_width_grows_page_by_page(tmp_path, kind):
    """Every value new: the dictionary grows through every page, and each
    page's indices take the width its dictionary needs so far."""
    n = 90_000
    rng = np.random.default_rng(7)
    keys = rng.permutation(n).astype(np.int64) * 3 + 1
    col = {"int64": pa.array(keys),
           "decimal": pa.array([decimal.Decimal(int(k)).scaleb(-2)
                                for k in keys], pa.decimal128(12, 2)),
           "string": pa.array([f"k{k}" for k in keys])}[kind]
    t = pa.table({"k": col})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression="snappy",
                   dictionary_pagesize_limit=64 << 20,
                   data_page_size=16 << 10)
    decoded = _assert_same(path, t, ["k"], dtype="int64"
                           if kind != "string" else None)
    lay = decoded["k"][0][2]
    assert lay.has_dict and not lay.plain
    assert len(lay.idx.groups) > 1, "one bit width: not the shape asked"


def test_pages_of_odd_counts_that_fill_the_capacity(tmp_path):
    """Rows exactly a capacity, in pages of odd value counts: every
    page's last bit-packed group is padded, so the unpacked values
    outnumber the rows."""
    n = 4096
    assert bucket_capacity(n) == n
    keys = np.random.default_rng(3).integers(0, 3000, n).astype(np.int64)
    t = pa.table({"k": pa.array(keys)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression="NONE", write_batch_size=13,
                   data_page_size=64)
    decoded = _assert_same(path, t, ["k"])
    lay = decoded["k"][0][2]
    padded = sum(nv for _spans, nv in lay.idx.groups.values())
    assert lay.has_dict and padded > n


@pytest.mark.parametrize("kind", ["int64", "decimal", "string"])
@pytest.mark.parametrize("nulls", [False, True])
def test_dictionary_that_falls_back_to_plain(tmp_path, kind, nulls):
    """A dictionary past its page limit: the writer's first pages index
    it, the rest are PLAIN."""
    n = 30_000
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 10 ** 9, n).astype(np.int64)
    mask = rng.random(n) < 0.1 if nulls else None
    col = {"int64": pa.array(keys, mask=mask),
           "decimal": pa.array(
               [None if mask is not None and m else
                decimal.Decimal(int(k)).scaleb(-2)
                for k, m in zip(keys, mask if nulls else [False] * n)],
               pa.decimal128(12, 2)),
           "string": pa.array([f"s{k}" for k in keys], mask=mask)}[kind]
    t = pa.table({"k": col})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression="snappy",
                   dictionary_pagesize_limit=8 << 10,
                   data_page_size=4 << 10)
    decoded = _assert_same(path, t, ["k"], dtype="int64"
                           if kind != "string" else None)
    lay = decoded["k"][0][2]
    assert lay.has_dict and lay.plain, "not dictionary then PLAIN"
    assert lay.nulls == nulls


@pytest.mark.parametrize("bw", range(1, 33))
def test_unpack_every_bit_width(bw):
    """pq_unpack against a plain bit-packer: values LSB first, groups of
    eight, 16 groups a row of ROW_BYTES; the width is data."""
    from spark_rapids_tpu.ops import parquet_decode as pd
    rng = np.random.default_rng(bw)
    rows = 3
    vals = rng.integers(0, 1 << bw, rows * 128, dtype=np.uint64)
    vals[:2] = [(1 << bw) - 1, 0]
    bits = ((vals[:, None] >> np.arange(bw, dtype=np.uint64)) & 1)
    packed = np.packbits(bits.astype(np.uint8).reshape(-1),
                         bitorder="little")
    payload = np.zeros((rows, pd.ROW_BYTES), np.uint8)
    payload[:, :16 * bw] = packed.reshape(rows, 16 * bw)
    buf = np.full(1024, -7, np.int32)
    got = np.asarray(pd.pq_unpack(buf, payload, np.int32(5),
                                  np.int32(bw)))
    np.testing.assert_array_equal(
        got[5:5 + rows * 128].view(np.uint32), vals.astype(np.uint32))
    assert (got[:5] == -7).all() and (got[5 + rows * 128:] == -7).all()


def test_malformed_chunk_is_a_counted_fallback(tmp_path):
    """Bytes that do not parse raise DecodeFallback('malformed'), which
    the scan counts; a bug in the decode raises through."""
    t = pa.table({"k": pa.array(np.arange(5000, dtype=np.int64))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression="NONE")
    pf = pq.ParquetFile(path)
    c = pdv.chunk_device_plan(pf, path, 0, 0, "k", True)
    c.raw = bytes(c.raw[:len(c.raw) // 2])          # a cut chunk
    with pytest.raises(pdv.DecodeFallback) as e:
        pdv.decode_chunk_device(c, bucket_capacity(5000))
    assert e.value.cause == "malformed"


@pytest.mark.parametrize("codec,rc", [("snappy", 0), ("gzip", 1)])
def test_check_tool_compares_every_chunk(tmp_path, capsys, codec, rc):
    """tools/pq_device_check.py: every chunk of the named columns against
    pyarrow; a chunk left to the host fails the check."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import pq_device_check
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    n = 6000
    t = pa.table({"k": pa.array(np.arange(n, dtype=np.int64) * 7 - 3),
                  "d": _decimals(n, 12, 5, nulls=True, distinct=50),
                  "s": pa.array([f"v{i % 17}" for i in range(n)]),
                  "x": pa.array(np.zeros(n))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression=codec, row_group_size=4000)
    assert pq_device_check.main([path, "--columns", "k,d,s"]) == rc
    total = json.loads(capsys.readouterr().out.splitlines()[-1])
    if rc:
        assert total == {"chunks": 0, "fallback": 6, "wrong": 0}
    else:
        assert total == {"chunks": 6, "fallback": 0, "wrong": 0}


@pytest.mark.parametrize("codecs,host_chunks,span", [
    ({"a": "gzip", "b": "snappy"}, 2, True),     # a left to the host
    ("gzip", 4, False)])                         # the whole file
def test_host_decode_is_a_span_and_a_counter(tmp_path, monkeypatch, codecs,
                                             host_chunks, span):
    import spark_rapids_tpu as st
    from spark_rapids_tpu.profiler import tracing
    t = pa.table({"a": pa.array(np.arange(4000, dtype=np.int64)),
                  "b": pa.array([f"v{i % 13}" for i in range(4000)])})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, compression=codecs, row_group_size=2000)
    opened = []
    real = tracing.span

    def spy(name, kind, *a, **k):
        opened.append((name, kind))
        return real(name, kind, *a, **k)
    monkeypatch.setattr(tracing, "span", spy)
    df = st.TpuSession({DEVICE_DECODE: True}).read.parquet(path)
    assert df.to_arrow().equals(t)
    counted = {}
    for m in df.last_metrics().values():
        for k, v in m.items():
            counted[k] = counted.get(k, 0) + v
    assert counted["hostDecodedChunks"] == host_chunks
    assert counted["deviceDecodeFallback.codec"] >= 1
    assert (("io.host_decode", "io") in opened) == span
    assert f"hostDecoded={host_chunks}" in df.explain("ANALYZE")


# ----------------------------------------------------------------------
# TPC-H q3 off the benchmark's Parquet storage
# ----------------------------------------------------------------------
SF = 0.01
TABLES = ("customer", "orders", "lineitem")


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    from benchmarks import datagen
    return datagen.generate(list(TABLES), SF, 3000410007), \
        str(tmp_path_factory.mktemp("lake"))


def test_q3_off_parquet_decoded_on_the_device(lake):
    import spark_rapids_tpu as st
    from benchmarks.harness import compare
    from benchmarks.reference import q3 as reference_q3
    from benchmarks.storage.parquet import Storage
    from spark_rapids_tpu.workloads import tpch

    tables, work_dir = lake
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch_sf1_parquet3.json")) as f:
        config = json.load(f)
    conf = dict(config["conf"])
    conf[DEVICE_DECODE] = True
    session = st.TpuSession(conf)
    storage = Storage(session, config["storage"], work_dir)
    try:
        storage.load(tables)
        df = tpch.queries()[3](storage.scans())
        got = df.to_arrow()
        counted = {}
        for m in df.last_metrics().values():
            for k, v in m.items():
                counted[k] = counted.get(k, 0) + v
    finally:
        storage.close()
        session.stop()
    assert compare.mismatches(got, reference_q3.reference(tables)) == 0
    assert not [k for k in counted if k.startswith("deviceDecodeFallback")]
    assert counted.get("hostDecodedChunks", 0) == 0
    # 2 + 4 + 4 columns, one row group each at this scale
    assert counted["deviceDecodedChunks"] == 10
