"""Aux subsystems: LORE dump/replay, metrics, trace annotations."""
import json
import os

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.expr.expressions import col

from data_gen import IntegerGen, gen_df


def test_lore_dump_and_replay(tmp_path):
    s = st.TpuSession({
        "spark.rapids.tpu.sql.lore.idsToDump": "1",
        "spark.rapids.tpu.sql.lore.dumpPath": str(tmp_path),
    })
    df, at = gen_df(s, [("a", IntegerGen(lo=0, hi=100))], n=500, seed=95)
    q = df.filter(col("a") > 50).agg(F.count("*").alias("n"))
    n1 = q.collect()[0][0]
    # loreId-1 is the root (the aggregate); its input batches were dumped
    assert os.path.exists(tmp_path / "lore-meta.json")
    meta = json.load(open(tmp_path / "lore-meta.json"))
    assert "1" in meta
    from spark_rapids_tpu.utils.lore import load_input
    s2 = st.TpuSession()
    replayed = load_input(s2, str(tmp_path), 1)
    # input to the aggregate = filtered rows; re-running count must match
    assert replayed.count() == n1


def test_metrics_surface(session):
    df, _ = gen_df(session, [("a", IntegerGen())], n=300, seed=96)
    q = df.filter(col("a") > 0)
    q.to_arrow()
    ms = q.last_metrics()
    assert any("FilterExec" in k for k in ms)
    assert any("numOutputBatches" in v for v in ms.values())
