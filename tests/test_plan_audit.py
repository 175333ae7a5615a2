"""Plan-time static auditor (analysis/audit.py): verdict classes,
VALIDATE explain, strict mode, and the NOT_ON_TPU event-log surface.

The acceptance case: a dtype mismatch the binders accept but the device
kernels cannot run (MathUnary over a decimal128 two-limb buffer) used to
die mid-query with an opaque Arrow/XLA shape error; with
`sql.audit.strict` it now fails at PLAN time with the lore id + node
path, before a single batch is produced."""
import decimal
import json

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.analysis.audit import (RECOMPILE_RISK,
                                             WILL_FALLBACK,
                                             WILL_NOT_WORK, audit_plan)
from spark_rapids_tpu.expr.expressions import (MathUnary, UnsupportedExpr,
                                               col, lit)
from spark_rapids_tpu.plan import typesig
from spark_rapids_tpu.plan.planner import Planner


def _dec128_df(session):
    arr = pa.array([decimal.Decimal("12345678901234567890123.456"),
                    decimal.Decimal("2.500")], pa.decimal128(26, 3))
    return session.create_dataframe({"d": arr})


def _plan_report(df):
    planner = Planner(df._session.conf)
    planner.plan(df._plan)
    return planner.last_audit


# ----------------------------------------------------------------------
# the acceptance case: runtime-only dtype failure -> plan-time error
# ----------------------------------------------------------------------
def test_decimal128_math_caught_at_plan_time_without_execution(
        monkeypatch):
    """sqrt over decimal(26,3) binds (NUMERIC includes decimal) but the
    double-math emit reads the flat buffer — a [cap,2] limb pair. In
    strict mode the auditor raises at plan time with lore id + node
    path, and NO operator ever executes."""
    from spark_rapids_tpu.exec import nodes as xnodes
    executed = []
    orig = xnodes.InMemoryScanExec.execute_partition

    def counting(self, ctx, pid):
        executed.append(pid)
        return orig(self, ctx, pid)

    monkeypatch.setattr(xnodes.InMemoryScanExec, "execute_partition",
                        counting)
    s = st.TpuSession({"spark.rapids.tpu.sql.audit.strict": True})
    q = _dec128_df(s).select(MathUnary("sqrt", col("d")).alias("r"))
    with pytest.raises(UnsupportedExpr) as ei:
        q.to_arrow()
    msg = str(ei.value)
    assert "will_not_work" in msg
    assert "loreId=" in msg
    assert "Project" in msg          # the node path of the bind site
    assert "decimal(26,3)" in msg
    assert executed == [], "strict audit must fire before execution"


def test_non_strict_keeps_verdict_but_does_not_raise():
    s = st.TpuSession()
    q = _dec128_df(s).select(MathUnary("sqrt", col("d")).alias("r"))
    report = _plan_report(q)
    bad = report.of_kind(WILL_NOT_WORK)
    assert len(bad) == 1
    assert bad[0].lore_id is not None
    assert "MathUnary" in bad[0].reason
    assert not report.ok


# ----------------------------------------------------------------------
# verdict classes
# ----------------------------------------------------------------------
def test_unregistered_expression_tags_will_not_work(monkeypatch):
    """An expression class with no TypeSig registration is flagged: the
    auditor cannot vouch for device support it cannot look up."""
    s = st.TpuSession()
    monkeypatch.delitem(typesig.SIGS, "Upper")
    df = s.create_dataframe({"s": pa.array(["a", "b"])})
    q = df.select(F.upper(col("s")).alias("u"))
    report = _plan_report(q)
    bad = report.of_kind(WILL_NOT_WORK)
    assert any("unregistered expression Upper" in v.reason for v in bad)


def test_fallback_bearing_plan_tags_will_fallback_not_will_not_work():
    """A host-fallback projection (regex outside the NFA subset) is a
    will_fallback verdict — the query still succeeds — and strict mode
    must NOT fail the plan."""
    s = st.TpuSession({"spark.rapids.tpu.sql.audit.strict": True})
    df = s.create_dataframe({"s": pa.array(["ax", "bx"])})
    q = df.select(col("s").rlike("(?=a)x").alias("r"))
    report = _plan_report(q)
    assert report.of_kind(WILL_FALLBACK)
    assert not report.of_kind(WILL_NOT_WORK)
    assert q.to_pydict()["r"] == [False, False]   # strict: still runs


def test_python_exec_tags_will_fallback():
    s = st.TpuSession()
    df = s.create_dataframe({"a": [1, 2, 3]})
    q = df.map_in_pandas(lambda pdf: pdf, df.schema)
    report = _plan_report(q)
    fb = report.of_kind(WILL_FALLBACK)
    assert any("python_exec" in v.reason for v in fb)


def test_recompile_risk_on_non_pow2_batch_size():
    s = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 1000})
    df = s.create_dataframe({"a": [1, 2, 3]})
    report = _plan_report(df.select((col("a") + 1).alias("b")))
    risks = report.of_kind(RECOMPILE_RISK)
    assert any("sql.batchSizeRows=1000" in v.reason for v in risks)


def test_recompile_risk_on_numpy_typed_literal():
    s = st.TpuSession()
    df = s.create_dataframe({"f": [1.0, 2.0]})
    q = df.select((col("f") + lit(np.float64(1.5))).alias("x"))
    report = _plan_report(q)
    risks = report.of_kind(RECOMPILE_RISK)
    assert any("non-weak-typed literal" in v.reason for v in risks)


def test_clean_plan_has_no_findings():
    s = st.TpuSession()
    df = s.create_dataframe({"a": [1, 2, 3], "b": [1.0, 2.0, 3.0]})
    q = df.filter(col("a") > 1).group_by("a").agg(
        F.sum(col("b")).alias("s"))
    report = _plan_report(q)
    assert report.findings == []
    assert report.ok
    assert report.node_count >= 3


# ----------------------------------------------------------------------
# surfaces: VALIDATE explain, NOT_ON_TPU explain, event log
# ----------------------------------------------------------------------
def test_validate_explain_renders_verdict_tree():
    s = st.TpuSession()
    q = _dec128_df(s).select(MathUnary("sqrt", col("d")).alias("r"))
    text = q.explain("VALIDATE")
    assert "== PLAN AUDIT ==" in text
    assert "!!" in text                       # will_not_work tag
    assert "loreId=" in text
    assert "will_not_work" in text
    clean = s.create_dataframe({"a": [1]}).select(col("a"))
    text2 = clean.explain("VALIDATE")
    assert "no findings" in text2


def test_not_on_tpu_explain_includes_audit_findings():
    s = st.TpuSession()
    q = _dec128_df(s).select(MathUnary("sqrt", col("d")).alias("r"))
    text = q.explain("NOT_ON_TPU")
    assert "will_not_work" in text
    assert "MathUnary" in text


def test_plan_audit_event_in_event_log(tmp_path):
    s = st.TpuSession({
        "spark.rapids.tpu.sql.eventLog.enabled": True,
        "spark.rapids.tpu.sql.eventLog.dir": str(tmp_path)})
    df = s.create_dataframe({"s": pa.array(["ax", "bx"])})
    df.select(col("s").rlike("(?=a)x").alias("r")).to_arrow()
    events = [json.loads(line)
              for line in open(s.last_event_log, encoding="utf-8")]
    audits = [e for e in events if e["event"] == "plan_audit"]
    assert len(audits) == 1
    ev = audits[0]
    assert ev["ok"] is True                  # fallback is not a failure
    kinds = {f["kind"] for f in ev["findings"]}
    assert kinds == {WILL_FALLBACK}
    assert all(f["lore_id"] is not None for f in ev["findings"])


# ----------------------------------------------------------------------
# bind-site context on check() / check_tree() errors
# ----------------------------------------------------------------------
def test_check_tree_error_names_the_bind_site():
    s = st.TpuSession({"spark.rapids.tpu.sql.allowCpuFallback": False})
    df = s.create_dataframe({"arr": pa.array([[1, 2], [3]])})
    with pytest.raises(UnsupportedExpr, match=r"at Project expr 'h'"):
        df.select(F.hash(col("arr")).alias("h"))


def test_aggregate_check_error_names_the_bind_site():
    """A sig violation in a GROUP BY key (murmur3 over a nested type —
    the binder is permissive, the registry is not) reports the
    Aggregate bind site, not just the expression name."""
    s = st.TpuSession()
    df = s.create_dataframe({"arr": pa.array([[1, 2], [3]]),
                             "v": [1, 2]})
    with pytest.raises(UnsupportedExpr, match=r"at Aggregate key 'h'"):
        df.group_by(F.hash(col("arr")).alias("h")).agg(
            F.sum(col("v")).alias("s"))


def test_audit_runs_on_tagged_meta_directly():
    """audit_plan is usable on a raw tagged PlanMeta (no conversion) —
    the path the planner takes when conversion itself fails."""
    from spark_rapids_tpu.plan.planner import PlanMeta
    s = st.TpuSession()
    df = _dec128_df(s).select(MathUnary("sqrt", col("d")).alias("r"))
    meta = PlanMeta(df._plan)
    report = audit_plan(meta, s.conf)
    assert report.of_kind(WILL_NOT_WORK)
    assert report.of_kind(WILL_NOT_WORK)[0].lore_id is None
