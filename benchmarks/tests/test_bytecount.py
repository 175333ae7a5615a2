"""The byte counts against numbers worked by hand from the schema."""
import json
import os

import pytest

from benchmarks.harness import bytecount, manifest
from benchmarks.harness.peaks import peaks
from benchmarks.tests.conftest import ROOT

LINEITEM, ORDERS, CUSTOMER = 6_001_215, 1_500_000, 150_000
HAND = {
    # shipdate 4 + two flags 1 + 1 + qty 8 + price 8 + disc 4 + tax 4 = 30 a
    # row; 4 groups x (1 + 1 + 4 x 16 + 8 + 8 + 4 + 8) = 4 x 94
    "q1": (LINEITEM * 30 + 4 * 94, LINEITEM),
    # customer 4 + 16 (char(10) in 16), orders 4 x 4, lineitem 4 + 4 + 8 + 4;
    # 10 rows x (4 + 4 + 4 + 16)
    "q3": (CUSTOMER * 20 + ORDERS * 16 + LINEITEM * 20 + 10 * 28,
           CUSTOMER + ORDERS + LINEITEM),
    # shipdate 4 + disc 4 + qty 8 + price 8 = 24 a row; one decimal(27,4)
    "q6": (LINEITEM * 24 + 16, LINEITEM),
}


def _config():
    with open(os.path.join(ROOT, "benchmarks/configs/tpch_sf1_hbm.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", sorted(HAND))
def test_least_bytes_and_scanned_rows(mix):
    config = _config()
    with open(os.path.join(ROOT, "benchmarks/traffic", mix + ".json")) as f:
        traffic = json.load(f)["queries"][0]
    card = manifest.cardinality(config, 1.0)
    assert bytecount.least_bytes(config["schema"], card, traffic["reads"],
                                 traffic["result"]) == HAND[mix][0]
    assert bytecount.scanned_rows(card, traffic["reads"]) == HAND[mix][1]


@pytest.mark.parametrize("declared,bytes_", [
    ("decimal(9,2)", 4), ("decimal(10,2)", 8), ("decimal(18,4)", 8),
    ("decimal(19,4)", 16), ("date", 4), ("int32", 4), ("int64", 8),
    ("char(1)", 1), ("char(10)", 16), ("char(25)", 32), ("key:orders", 4),
    ("key:huge", 8)])
def test_width(declared, bytes_):
    card = {"orders": 1_500_000, "huge": 3_000_000_000}
    assert bytecount.width(declared, card) == bytes_


@pytest.mark.parametrize("declared", ["float64", "varchar(44)"])
def test_width_refuses_a_type_it_does_not_know(declared):
    """A varchar's least bytes are not in its declaration: the count refuses
    it rather than guess high."""
    with pytest.raises(ValueError):
        bytecount.width(declared, {})


def test_peaks_v5e_and_no_default():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            peaks(kind)
