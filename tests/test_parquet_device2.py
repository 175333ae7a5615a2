"""Device Parquet decode slice 2: compressed pages + strings + v2 data
pages + pinned staging pool (reference: GpuParquetScan.scala:3364 +
nvcomp device decompression; ISSUE 4).

Round-trip fuzz vs the pyarrow oracle across
{snappy, uncompressed} x {v1, v2} x {PLAIN, dict} x
{int64, double, string} with nulls, empty strings and multi-page
chunks; staging-pool reuse/budget tests; device snappy kernel parity.
"""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.io import parquet_thrift as pt
from spark_rapids_tpu.io.parquet_device import (chunk_device_plan,
                                                decode_chunk_device,
                                                eligible_chunks,
                                                fallback_reasons)


# ----------------------------------------------------------------------
# chunk-level round-trip helpers
# ----------------------------------------------------------------------
def _decode_file(table, p, device_snappy=False, pool=None):
    """Device-decode every eligible chunk of file `p`; returns
    {name: [per-row-group python list]} (None for nulls)."""
    pf = pq.ParquetFile(p)
    out = {}
    for rg in range(pf.metadata.num_row_groups):
        elig = eligible_chunks(pf, rg, table.column_names)
        nrows = pf.metadata.row_group(rg).num_rows
        cap = bucket_capacity(nrows)
        for name, ci in elig.items():
            nullable = pf.schema_arrow.field(name).nullable
            c = chunk_device_plan(pf, p, rg, ci, name, nullable,
                                  pool=pool,
                                  device_snappy=device_snappy)
            assert c is not None, f"plan failed for {name}"
            got = decode_chunk_device(c, cap)
            assert got is not None, f"decode fell back for {name}"
            if len(got) == 3:                      # strings
                data, valid, offsets = got
                data = np.asarray(data)
                valid = np.asarray(valid)[:nrows]
                off = np.asarray(offsets)[:nrows + 1]
                vals = [bytes(data[off[i]:off[i + 1]]).decode()
                        if valid[i] else None for i in range(nrows)]
            else:
                v, valid = got
                v = np.asarray(v)[:nrows]
                valid = np.asarray(valid)[:nrows]
                vals = [v[i].item() if valid[i] else None
                        for i in range(nrows)]
            c.close()
            out.setdefault(name, []).extend(vals)
    return out


def _expect(table, name):
    return table.column(name).to_pylist()


def _fuzz_table(n, seed, with_nulls, dict_friendly):
    rng = np.random.default_rng(seed)
    mask = (rng.random(n) < 0.2) if with_nulls else None
    if dict_friendly:
        i64 = rng.integers(0, 12, n).astype(np.int64) * 31
        f64 = rng.choice(np.asarray([0.0, -1.5, 2.25, 1e9]), n)
        words = np.asarray(["", "a", "bb", "ccc", "x" * 17,
                            "snap", "py"], dtype=object)
        s = words[rng.integers(0, len(words), n)]
    else:
        i64 = rng.integers(-2**62, 2**62, n).astype(np.int64)
        f64 = rng.standard_normal(n)
        lens = rng.integers(0, 23, n)       # includes empty strings
        alphabet = np.frombuffer(b"abcdefghijklmnop0123", np.uint8)
        s = np.asarray(
            ["".join(chr(c) for c in
                     rng.choice(alphabet, ln)) for ln in lens],
            dtype=object)
    return pa.table({
        "i64": pa.array(i64, type=pa.int64(), mask=mask),
        "f64": pa.array(f64, type=pa.float64(), mask=mask),
        "s": pa.array(s, type=pa.string(), mask=mask),
    })


# full {codec} x {pagever} x {dict} grid with nulls; the no-null
# variants exercise the separate no-def-level path on two
# representative corners in tier-1 and the rest under -m slow (suite
# wall-time budget)
_FUZZ_GRID = [
    pytest.param(codec, pagever, use_dict, True,
                 id=f"{codec}-{pagever}-dict{use_dict}-nulls")
    for codec in ("NONE", "snappy")
    for pagever in ("1.0", "2.0")
    for use_dict in (False, True)
] + [
    pytest.param("NONE", "1.0", False, False,
                 id="NONE-1.0-plain-nonull"),
    pytest.param("snappy", "2.0", False, False,
                 id="snappy-2.0-plain-nonull"),
    pytest.param("snappy", "1.0", True, False,
                 id="snappy-1.0-dict-nonull"),
] + [
    pytest.param(codec, pagever, use_dict, False, marks=pytest.mark.slow,
                 id=f"{codec}-{pagever}-dict{use_dict}-nonull-slow")
    for (codec, pagever, use_dict) in (
        ("NONE", "1.0", True), ("NONE", "2.0", False),
        ("NONE", "2.0", True), ("snappy", "1.0", False),
        ("snappy", "2.0", True))
]


@pytest.mark.parametrize("codec,pagever,use_dict,with_nulls",
                         _FUZZ_GRID)
def test_roundtrip_fuzz(tmp_path, codec, pagever, use_dict, with_nulls):
    t = _fuzz_table(3000, seed=hash((codec, pagever, use_dict)) % 977,
                    with_nulls=with_nulls, dict_friendly=use_dict)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression=codec, use_dictionary=use_dict,
                   data_page_version=pagever)
    pf = pq.ParquetFile(p)
    assert set(eligible_chunks(pf, 0, t.column_names)) \
        == set(t.column_names)
    got = _decode_file(t, p)
    for name in t.column_names:
        assert got[name] == _expect(t, name), \
            f"{name} @ {codec}/{pagever}/dict={use_dict}"


@pytest.mark.parametrize("codec,pagever", [
    ("snappy", "1.0"), ("NONE", "2.0"),
    pytest.param("NONE", "1.0", marks=pytest.mark.slow),
    pytest.param("snappy", "2.0", marks=pytest.mark.slow)])
def test_multi_page_chunks(tmp_path, codec, pagever):
    """Small data pages force several pages per chunk (and several
    def-level sections / packed-stream rebases)."""
    t = _fuzz_table(8000, seed=3, with_nulls=True,
                    dict_friendly=False)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression=codec, use_dictionary=False,
                   data_page_version=pagever, data_page_size=1024,
                   row_group_size=3500)
    pf = pq.ParquetFile(p)
    assert pf.metadata.num_row_groups > 1
    got = _decode_file(t, p)
    for name in t.column_names:
        assert got[name] == _expect(t, name), f"{name}"


def test_dict_strings_many_pages(tmp_path):
    t = _fuzz_table(8000, seed=11, with_nulls=True,
                    dict_friendly=True)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", use_dictionary=True,
                   data_page_size=512)
    got = _decode_file(t, p)
    for name in t.column_names:
        assert got[name] == _expect(t, name), f"{name}"


def test_all_null_and_all_empty_strings(tmp_path):
    t = pa.table({
        "s_null": pa.array([None] * 300, type=pa.string()),
        "s_empty": pa.array([""] * 300, type=pa.string()),
        "i_null": pa.array([None] * 300, type=pa.int64()),
    })
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", use_dictionary=False)
    got = _decode_file(t, p)
    for name in t.column_names:
        assert got[name] == _expect(t, name), f"{name}"


# ----------------------------------------------------------------------
# device snappy kernel (conf sql.parquet.deviceSnappy)
# ----------------------------------------------------------------------
def _snappy_device_roundtrip(payload: bytes):
    import jax.numpy as jnp

    from spark_rapids_tpu.io.parquet_device import \
        _parse_snappy_elements
    from spark_rapids_tpu.ops import parquet_decode as pd

    comp = pa.Codec("snappy").compress(payload).to_pybytes()
    out_len, dl, ll, sl = _parse_snappy_elements(comp, 0, len(comp))
    assert out_len == len(payload)
    E = pd.bucket_len(max(len(dl), 1))
    dst = np.full(E, out_len, np.int32)
    lit = np.zeros(E, np.int32)
    src = np.zeros(E, np.int32)
    dst[:len(dl)], lit[:len(dl)], src[:len(dl)] = dl, ll, sl
    cap = pd.bucket_len(max(out_len, 1), floor=128)
    kbits = max(1, (cap - 1).bit_length())
    got = pd.pq_snappy_expand(
        jnp.asarray(np.frombuffer(comp, np.uint8)), jnp.asarray(dst),
        jnp.asarray(lit), jnp.asarray(src), len(dl), out_len, kbits,
        cap)
    return bytes(np.asarray(got)[:out_len])


@pytest.mark.parametrize("payload", [
    b"",
    b"abc",
    b"hello hello hello hello hello hello",      # overlapping copies
    bytes(range(256)) * 40,                      # literal-heavy
    b"\x00" * 5000,                              # RLE-ish (offset 1)
    b"ab" * 4000,                                # short-period copies
])
def test_snappy_expand_parity(payload):
    assert _snappy_device_roundtrip(payload) == payload


def test_snappy_expand_fuzz():
    rng = np.random.default_rng(17)
    for trial in range(6):
        # mix of compressible runs and incompressible noise
        parts = []
        for _ in range(rng.integers(1, 9)):
            if rng.random() < 0.5:
                parts.append(bytes(rng.integers(0, 256, 200,
                                                dtype=np.uint8)))
            else:
                parts.append(bytes(rng.integers(0, 4, 1,
                                                dtype=np.uint8)) *
                             int(rng.integers(1, 800)))
        payload = b"".join(parts)
        assert _snappy_device_roundtrip(payload) == payload


def test_device_snappy_chunk_path(tmp_path):
    """device_snappy=True routes qualifying (non-null PLAIN v1) pages
    through the device kernel — byte-identical to the host result."""
    rng = np.random.default_rng(5)
    n = 6000
    t = pa.table({
        "i64": pa.array(rng.integers(0, 50, n).astype(np.int64)),
        "f64": pa.array(np.repeat(rng.standard_normal(60), 100)),
    })
    p = str(tmp_path / "t.parquet")
    # nullable=False requires an explicit non-nullable schema
    schema = pa.schema([pa.field("i64", pa.int64(), nullable=False),
                        pa.field("f64", pa.float64(), nullable=False)])
    pq.write_table(t.cast(schema), p, compression="snappy",
                   use_dictionary=False)
    pf = pq.ParquetFile(p)
    for name, ci in eligible_chunks(pf, 0, t.column_names).items():
        c = chunk_device_plan(pf, p, 0, ci, name, False,
                              device_snappy=True)
        assert c is not None
        assert c.dev_pages, f"device-snappy did not engage for {name}"
        got = decode_chunk_device(c, bucket_capacity(n))
        vals = np.asarray(got[0])[:n]
        np.testing.assert_array_equal(vals,
                                      np.asarray(t.column(name)))


# ----------------------------------------------------------------------
# pinned staging pool
# ----------------------------------------------------------------------
def test_staging_pool_reuse_and_buckets():
    from spark_rapids_tpu.memory.host import (HostMemoryManager,
                                              PinnedStagingPool)
    mgr = HostMemoryManager(0)          # unlimited
    pool = PinnedStagingPool(1 << 20, mgr)
    a = pool.acquire(100_000)           # -> 128KiB bucket
    assert a.capacity == 128 * 1024
    assert a.view().nbytes == 100_000
    a.release()
    b = pool.acquire(90_000)            # same bucket: reuse
    assert b.capacity == 128 * 1024
    assert pool.metrics["stagingPoolHits"] == 1
    assert pool.metrics["stagingPoolMisses"] == 1
    b.release()
    # different bucket: fresh allocation
    c = pool.acquire(1000)
    assert c.capacity == 64 * 1024      # floor bucket
    assert pool.metrics["stagingPoolMisses"] == 2
    c.release()


def test_staging_pool_budget_accounting():
    from spark_rapids_tpu.memory.host import (HostMemoryManager,
                                              PinnedStagingPool)
    mgr = HostMemoryManager(10 << 20)
    pool = PinnedStagingPool(8 << 20, mgr)
    a = pool.acquire(1 << 20)
    assert mgr.reserved == a.capacity
    a.release()
    assert pool.held_bytes == a.capacity     # cached, still reserved
    freed = pool.clear()
    assert freed == a.capacity
    assert mgr.reserved == 0
    assert pool.held_bytes == 0


def test_staging_pool_transient_over_cap():
    from spark_rapids_tpu.memory.host import PinnedStagingPool
    pool = PinnedStagingPool(128 * 1024)     # tiny pool
    a = pool.acquire(100 * 1024)             # fills the pool
    b = pool.acquire(100 * 1024)             # over cap: transient
    assert pool.metrics["stagingPoolTransient"] == 1
    b.release()
    assert pool.held_bytes == a.capacity     # transient not cached
    a.release()
    c = pool.acquire(100 * 1024)
    assert pool.metrics["stagingPoolHits"] == 1
    c.release()


def test_chunk_plan_uses_pool(tmp_path):
    from spark_rapids_tpu.memory.host import PinnedStagingPool
    t = _fuzz_table(4000, seed=1, with_nulls=True, dict_friendly=False)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", use_dictionary=False)
    pool = PinnedStagingPool(64 << 20)
    got = _decode_file(t, p, pool=pool)
    for name in t.column_names:
        assert got[name] == _expect(t, name)
    # chunks were read through the pool and the leases came back
    assert pool.metrics["stagingPoolMisses"] > 0
    assert pool.metrics["stagingPoolHits"] > 0   # reuse across chunks
    free = sum(len(v) for v in pool._free.values())
    assert free > 0


# ----------------------------------------------------------------------
# scan integration: metrics, fallback reasons, prefetch
# ----------------------------------------------------------------------
def _scan_session(extra=None):
    import spark_rapids_tpu as st
    conf = {"spark.rapids.tpu.sql.format.parquet.deviceDecode.enabled":
            True}
    conf.update(extra or {})
    return st.TpuSession(conf)


def test_scan_snappy_strings_end_to_end(tmp_path):
    t = _fuzz_table(6_000, seed=23, with_nulls=True,
                    dict_friendly=False)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", use_dictionary=False)
    s = _scan_session()
    df = s.read.parquet(p)
    out = df.to_arrow()
    assert out.num_rows == t.num_rows
    assert out.column("s").to_pylist() == t.column("s").to_pylist()
    assert out.column("i64").to_pylist() == t.column("i64").to_pylist()
    mets = {k: v for _op, ms in df.last_metrics().items()
            for k, v in ms.items()}
    assert mets.get("deviceDecodedChunks", 0) >= 3
    assert mets.get("decompressBusySecs", 0) > 0
    assert "prefetchWaitSecs" in mets


def test_scan_fallback_reason_counters(tmp_path):
    """gzip columns fall back with a 'codec' reason; the counters ride
    the scan's MetricSet into EXPLAIN ANALYZE."""
    t = _fuzz_table(2000, seed=7, with_nulls=False,
                    dict_friendly=False)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression={"i64": "gzip", "f64": "snappy",
                                      "s": "snappy"},
                   use_dictionary=False)
    pf = pq.ParquetFile(p)
    reasons = fallback_reasons(pf, 0, t.column_names)
    assert set(reasons) == {"i64"}
    assert reasons["i64"][0] == "codec"
    s = _scan_session()
    df = s.read.parquet(p)
    df.to_arrow()
    mets = {k: v for _op, ms in df.last_metrics().items()
            for k, v in ms.items()}
    assert mets.get("deviceDecodeFallback.codec", 0) >= 1
    assert mets.get("deviceDecodedChunks", 0) >= 2
    txt = df.explain("ANALYZE")
    assert "fallback" in txt and "codec" in txt


def test_plan_audit_reports_scan_fallbacks(tmp_path):
    """The static auditor answers 'why would this scan fall back'
    BEFORE execution, from the footer of the first file."""
    t = _fuzz_table(2000, seed=7, with_nulls=False,
                    dict_friendly=False)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="gzip", use_dictionary=False)
    s = _scan_session()
    df = s.read.parquet(p)
    txt = df.explain("VALIDATE")
    assert "device-decode" in txt and "codec" in txt


def test_v2_thrift_header_fields(tmp_path):
    """The thrift reader surfaces the v2 level-section lengths the
    decoder needs."""
    t = pa.table({"a": pa.array([1, None, 3] * 100, type=pa.int64())})
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="NONE", use_dictionary=False,
                   data_page_version="2.0")
    pf = pq.ParquetFile(p)
    col = pf.metadata.row_group(0).column(0)
    start = col.data_page_offset
    if col.has_dictionary_page:
        start = min(start, col.dictionary_page_offset)
    with open(p, "rb") as f:
        f.seek(start)
        raw = f.read(col.total_compressed_size)
    pages = pt.parse_page_headers(raw, col.num_values)
    v2 = [pg for pg in pages if pg.page_type == pt.DATA_PAGE_V2]
    assert v2, "writer did not produce v2 pages"
    assert v2[0].def_levels_byte_length > 0
    assert v2[0].rep_levels_byte_length == 0
