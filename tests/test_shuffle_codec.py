"""The local file shuffle's codec: raw blocks by default, the named
codec or an error, never a silent stand-in."""
import decimal
import sys
import types
import zlib

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.profiler import tracing
from spark_rapids_tpu.shuffle.local import get_codec

CODEC = "spark.rapids.tpu.shuffle.compression.codec"


def _session(tmp_path, **extra):
    return st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 256,
        "spark.rapids.tpu.sql.shuffle.partitions": 4,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.shuffle.dir": str(tmp_path / "shuffle"),
        **extra})


def _table(n=1000):
    return pa.table({
        "k": pa.array([None if i % 17 == 0 else i % 13 for i in range(n)],
                      type=pa.int64()),
        "s": pa.array([None if i % 11 == 0 else f"s{i % 37}" * (i % 4)
                       for i in range(n)]),
        "d": pa.array([None if i % 7 == 0 else
                       decimal.Decimal(f"{(i - 500) * 10**25 + i}.{i % 100:02d}")
                       for i in range(n)], type=pa.decimal128(38, 2))})


def _shuffled(s):
    q = s.create_dataframe(_table()).repartition(4, F.col("k"))
    out = q.to_arrow()
    (m,) = [m for m in q.last_metrics().values() if "shuffleRawBytes" in m]
    return out, m


def _default_writes_raw(tmp_path, monkeypatch):
    opened = []
    span = tracing.span

    def recording(name, kind, *a, **kw):
        opened.append(name)
        return span(name, kind, *a, **kw)

    monkeypatch.setattr(tracing, "span", recording)
    _, m = _shuffled(_session(tmp_path))
    assert m["shuffleBlocksWritten"] > 0
    assert m["shuffleBytesWritten"] == (
        m["shuffleRawBytes"] + 8 * m["shuffleBlocksWritten"])
    assert "shuffle.serialize" in opened
    assert "shuffle.compress" not in opened
    assert "shuffle.decompress" not in opened


def _missing_lz4_raises(tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "lz4", None)
    monkeypatch.setitem(sys.modules, "lz4.frame", None)
    with pytest.raises(ValueError) as err:
        _shuffled(_session(tmp_path, **{CODEC: "lz4"}))
    msg = str(err.value)
    assert "'lz4'" in msg and "'none'" in msg and "'zstd'" in msg
    shuffle_dir = tmp_path / "shuffle"
    assert not shuffle_dir.exists() or not list(shuffle_dir.rglob("*"))


def _zstd_round_trips(tmp_path, monkeypatch):
    plain, pm = _shuffled(_session(tmp_path))
    packed, zm = _shuffled(_session(tmp_path, **{CODEC: "zstd"}))
    assert packed.equals(plain)
    assert packed.schema.field("d").type == pa.decimal128(38, 2)
    assert zm["shuffleRawBytes"] == pm["shuffleRawBytes"]
    assert zm["shuffleBytesWritten"] < pm["shuffleBytesWritten"]


def _never_zlib(tmp_path, monkeypatch):
    assert get_codec("none") is None and get_codec(None) is None
    assert get_codec("zstd") is not zlib
    for name in ("zlib", "gzip", "deflate", "snappy"):
        with pytest.raises(ValueError, match="'none' or 'zstd'"):
            get_codec(name)
    monkeypatch.setitem(sys.modules, "zstandard", None)
    with pytest.raises(ValueError, match="'zstandard'"):
        get_codec("zstd")
    monkeypatch.setitem(sys.modules, "lz4", None)
    monkeypatch.setitem(sys.modules, "lz4.frame", None)
    with pytest.raises(ValueError, match="'lz4'"):
        get_codec("lz4")
    # where lz4 is installed, it is what the name selects
    frame = types.ModuleType("lz4.frame")
    pkg = types.ModuleType("lz4")
    pkg.frame = frame
    monkeypatch.setitem(sys.modules, "lz4", pkg)
    monkeypatch.setitem(sys.modules, "lz4.frame", frame)
    assert get_codec("lz4") is frame


CASES = {"default_writes_raw": _default_writes_raw,
         "missing_lz4_raises": _missing_lz4_raises,
         "zstd_round_trips": _zstd_round_trips,
         "never_zlib": _never_zlib}


@pytest.mark.parametrize("case", list(CASES))
def test_shuffle_codec(tmp_path, monkeypatch, case):
    CASES[case](tmp_path, monkeypatch)
