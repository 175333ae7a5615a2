"""NATION (clause 4.2.3): the specification's 25 fixed rows, at any scale
factor; only N_COMMENT hangs on the seed."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c

# (N_NAME, N_REGIONKEY) by N_NATIONKEY
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]


def generate(sf, seed, made):
    n = len(NATIONS)
    return pa.table({
        "n_nationkey": pa.array(np.arange(n, dtype=np.int64)),
        "n_name": pa.array([name for name, _ in NATIONS], pa.string()),
        "n_regionkey": pa.array(
            np.array([region for _, region in NATIONS], np.int64)),
        "n_comment": c.text(c.stream(seed, 9), seed, n, 31, 114),
    })
