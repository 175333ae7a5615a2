"""Multi-device (SPMD) execution tests on the 8-device virtual CPU mesh.

The pseudo-distributed analog of the reference's `local-cluster[N,..]`
integration runs (reference: integration_tests/README.md:205): conftest
provisions 8 virtual CPU devices; these tests exercise the mesh exchange
collective (parallel/collectives.py), the planner's mesh routing, and
distributed groupby/join end-to-end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as st
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops.kernel_utils import CV
from spark_rapids_tpu.parallel.mesh import make_mesh, shard_rows

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N_DEV)


def _run_exchange(mesh, cvs, mask, pids):
    """`exchange_cvs` over the mesh, one shard a device. `cvs`, `mask`
    and `pids` are global (shard s owns the s-th of N_DEV equal pieces of
    every leaf). Returns (out_cvs, out_mask, counts) as host arrays whose
    leading axis is the shard."""
    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.parallel.collectives import exchange_cvs

    def fn(tree):
        c, m, p = tree
        out_cvs, out_mask, count = exchange_cvs(list(c), m, p, N_DEV)
        return out_cvs, out_mask, count.reshape(1)

    step = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data")))
    tree = jax.tree.map(lambda a: shard_rows(mesh, a),
                        (tuple(cvs), mask, pids))
    out = jax.device_get(step(tree))
    return jax.tree.map(
        lambda a: np.asarray(a).reshape((N_DEV, -1) + a.shape[1:]), out)


def _string_cv(strs, cap):
    """The global CV of a string column laid out a shard at a time: each
    shard's bytes packed from the front of its own power-of-two byte
    buffer, offsets local to the shard. None is a null (no bytes)."""
    shards = [strs[i:i + cap] for i in range(0, len(strs), cap)]
    need = max(sum(len(b or b"") for b in sh) for sh in shards)
    bcap = 1 << max(need - 1, 0).bit_length()
    data, offs = [], []
    for sh in shards:
        d = b"".join(b or b"" for b in sh)
        buf = np.zeros(bcap, np.uint8)
        buf[:len(d)] = np.frombuffer(d, np.uint8)
        data.append(buf)
        o = np.zeros(cap + 1, np.int32)
        np.cumsum([len(b or b"") for b in sh], out=o[1:])
        offs.append(o)
    valid = np.array([b is not None for b in strs])
    return CV(jnp.asarray(np.concatenate(data)), jnp.asarray(valid),
              jnp.asarray(np.concatenate(offs))), bcap


def _want_rows(cap, mask, pids, shard):
    """Global row numbers `shard` must receive, in the exchange's order:
    by source shard, then by row within the source."""
    return [i for i in range(N_DEV * cap) if mask[i] and pids[i] == shard]


def _check_exchange(mesh, cap, cols, mask, pids):
    """Run the exchange over `cols` and hold it to its contract against
    the plain reference `_want_rows`. A column is a numpy array of
    N_DEV * cap rows (trailing dims ride along) or a list of
    Optional[bytes]; a fixed-width column's validity is `col != 7` where
    it has no trailing dims."""
    cvs, kinds = [], []
    for c in cols:
        if isinstance(c, list):
            cv, bcap = _string_cv(c, cap)
            cvs.append(cv)
            kinds.append(bcap)
        else:
            valid = (c != 7) if c.ndim == 1 and c.dtype != np.bool_ \
                else np.ones(len(c), np.bool_)
            cvs.append(CV(jnp.asarray(c), jnp.asarray(valid)))
            kinds.append(valid)
    out_cvs, out_mask, counts = _run_exchange(
        mesh, cvs, jnp.asarray(mask), jnp.asarray(pids.astype(np.int32)))
    ocap = N_DEV * cap
    assert out_mask.shape == (N_DEV, ocap)
    total = 0
    for t in range(N_DEV):
        want = _want_rows(cap, mask, pids, t)
        k = len(want)
        total += k
        assert int(counts[t, 0]) == k
        # the mask is the prefix, nothing else
        assert np.array_equal(out_mask[t], np.arange(ocap) < k)
        for c, kind, cv in zip(cols, kinds, out_cvs):
            assert not cv.validity[t][k:].any()
            if isinstance(c, list):
                off = cv.offsets[t]
                assert off.shape == (ocap + 1,) and off[0] == 0
                assert (np.diff(off) >= 0).all() and (off[k:] == off[k]).all()
                got = [bytes(cv.data[t][off[r]:off[r + 1]])
                       if cv.validity[t][r] else None for r in range(k)]
                assert got == [c[i] for i in want]
                assert cv.data[t].shape == (N_DEV * kind,)
                assert not cv.data[t][off[k]:].any()
            else:
                assert cv.data[t].dtype == c.dtype
                assert np.array_equal(cv.data[t][:k], c[want])
                assert np.array_equal(cv.validity[t][:k], kind[want])
    assert total == int(mask.sum())
    return out_cvs, out_mask, counts


def _strings(rng, n, null_share=0.0, empty_share=0.0):
    out = []
    for i in range(n):
        u = rng.random()
        if u < null_share:
            out.append(None)
        elif u < null_share + empty_share:
            out.append(b"")
        else:
            out.append(f"s{i}-".encode() + b"x" * int(rng.integers(0, 9)))
    return out


def test_exchange_rows_conserves_rows(mesh):
    """Every live row arrives on its target shard exactly once."""
    cap = 64
    n = cap * N_DEV
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 1 << 40, n).astype(np.int64)
    mask = rng.random(n) < 0.8
    pids = rng.integers(0, N_DEV, n)
    (cv,), out_mask, _ = _check_exchange(mesh, cap, [vals], mask, pids)
    assert sorted(cv.data[out_mask].tolist()) == sorted(vals[mask].tolist())


def test_exchange_rows_lands_on_target_shard(mesh):
    """Rows land in the output block of the shard named by their pid."""
    cap = 32
    n = cap * N_DEV
    rng = np.random.default_rng(4)
    vals = np.arange(n, dtype=np.int64)
    pids = rng.integers(0, N_DEV, n)
    (cv,), out_mask, _ = _check_exchange(mesh, cap, [vals],
                                         np.ones(n, np.bool_), pids)
    for shard in range(N_DEV):
        rows = cv.data[shard][out_mask[shard]]
        assert all(pids[int(r)] == shard for r in rows)


def test_exchange_cvs_strings_roundtrip(mesh):
    """String columns survive the byte exchange with exact contents."""
    cap = 32
    n = cap * N_DEV
    rng = np.random.default_rng(5)
    strs = _strings(rng, n)
    vals = np.arange(n, dtype=np.int64)
    mask = rng.random(n) < 0.9
    pids = rng.integers(0, N_DEV, n)
    (ids, scv), out_mask, _ = _check_exchange(mesh, cap, [vals, strs],
                                              mask, pids)
    got = {}
    for t in range(N_DEV):
        off = scv.offsets[t]
        for r in np.flatnonzero(out_mask[t]):
            got[int(ids.data[t][r])] = bytes(scv.data[t][off[r]:off[r + 1]])
    assert got == {i: strs[i] for i in range(n) if mask[i]}


def _case(name):
    """(cap, columns, mask, pids) of one case of the exchange's contract."""
    cap = 32
    n = cap * N_DEV
    rng = np.random.default_rng(sum(map(ord, name)))
    mask = rng.random(n) < 0.8
    pids = rng.integers(0, N_DEV, n)
    ints = rng.integers(0, 1 << 40, n).astype(np.int64)
    ints[rng.random(n) < 0.1] = 7           # nulls (see _check_exchange)
    cols = [ints]
    if name == "bool":
        cols = [rng.random(n) < 0.5]
    elif name == "int32":
        cols = [rng.integers(-50, 50, n).astype(np.int32)]
    elif name == "decimal128_limbs":
        cols = [rng.integers(-(1 << 62), 1 << 62, (n, 2)).astype(np.int64)]
    elif name == "mixed_widths":
        cols = [ints, rng.random(n) < 0.5,
                rng.integers(0, 9, n).astype(np.int32),
                rng.integers(0, 1 << 62, (n, 2)).astype(np.int64)]
    elif name == "every_row_to_one_peer":
        # a peer's bucket must hold a whole shard: B = cap is needed
        mask = np.ones(n, np.bool_)
        pids = np.full(n, 3)
    elif name == "shard_with_no_live_row":
        mask[2 * cap:3 * cap] = False
    elif name == "all_rows_dead":
        mask = np.zeros(n, np.bool_)
    elif name == "no_row_for_some_peers":
        pids = rng.integers(0, 2, n) * 5
    elif name == "strings_nulls_and_empties":
        cols = [ints, _strings(rng, n, null_share=0.2, empty_share=0.2)]
    elif name == "strings_one_shards_bytes_to_one_peer":
        # shard 1 fills its byte buffer to the last byte and sends all
        # of it to peer 5: a peer's byte bucket must hold a whole shard's
        strs = _strings(rng, n, null_share=0.1)
        strs[cap:2 * cap] = [b"y" * 16] * cap
        mask[cap:2 * cap] = True
        pids[cap:2 * cap] = 5
        cols = [strs, ints]
    elif name == "strings_all_rows_dead":
        cols = [_strings(rng, n, empty_share=0.3)]
        mask = np.zeros(n, np.bool_)
    elif name == "two_string_columns":
        cols = [_strings(rng, n, null_share=0.1), ints,
                _strings(rng, n, empty_share=0.5)]
    else:
        assert name == "int64"
    return cap, cols, mask, pids


@pytest.mark.parametrize("name", [
    "int64", "bool", "int32", "decimal128_limbs", "mixed_widths",
    "every_row_to_one_peer", "shard_with_no_live_row", "all_rows_dead",
    "no_row_for_some_peers", "strings_nulls_and_empties",
    "strings_one_shards_bytes_to_one_peer", "strings_all_rows_dead",
    "two_string_columns"])
def test_exchange_contract(mesh, name):
    """What `exchange_cvs` promises its callers: on every shard the
    received rows are a live prefix in (source shard, source row) order
    and equal the plain reference, value for value and byte for byte;
    validity is false and string lengths are 0 past the prefix."""
    cap, cols, mask, pids = _case(name)
    if name == "strings_one_shards_bytes_to_one_peer":
        cv, bcap = _string_cv(cols[0], cap)
        assert int(cv.offsets[2 * (cap + 1) - 1]) == bcap   # shard 1 is full
    _check_exchange(mesh, cap, cols, mask, pids)


# ---------------------------------------------------------------------
# the exchange's programs hold no scatter and no gather over what was
# received: the gain of PR 30, held off the chip
# ---------------------------------------------------------------------
def _indexed_rows(text, op):
    """Leading extent of the operand of every `op` (gather / scatter) in
    a lowered program's StableHLO. The operand types follow the op's
    attributes (gather) or its update region (scatter)."""
    import re
    return [int(m) for m in re.findall(
        r'"stablehlo\.%s"\(.*?\}[>)] : \(tensor<(\d+)[x>]' % op, text,
        flags=re.S)]


def _exchange_stage(with_string):
    """The exchange stage of a shuffled join's left input, as the planner
    builds it (kind `exchange`; `stage.exchange` is the round-based
    `MeshExchangeExec` it degrades to)."""
    from spark_rapids_tpu.exec.spmd_stage import SpmdStageExec
    from spark_rapids_tpu.plan.planner import Planner
    s = st.TpuSession({
        "spark.rapids.tpu.mesh.devices": N_DEV,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    left = {"k": pa.array(np.arange(40, dtype=np.int64)),
            "v": pa.array(np.arange(40, dtype=np.int32))}
    if with_string:
        left["s"] = pa.array([f"x{i}" for i in range(40)])
    right = {"k": pa.array(np.arange(40, dtype=np.int64)),
             "w": pa.array(np.arange(40, dtype=np.int64))}
    q = s.create_dataframe(left).join(s.create_dataframe(right), on=["k"])
    root = Planner(s.conf).plan(q._plan)
    stage = next(nd for nd in _walk(root) if isinstance(nd, SpmdStageExec)
                 and len(nd.schema.fields) == len(left))
    assert stage.kind == "exchange"
    return stage


@pytest.mark.parametrize("with_string", [False, True],
                         ids=["fixed_width", "with_string"])
@pytest.mark.parametrize("program", ["stage", "round"])
def test_exchange_program_has_no_scatter_over_received_slots(
        program, with_string):
    """Lower the fused stage program (kind `exchange`) and the round
    program of `MeshExchangeExec` on the CPU mesh and read the StableHLO:
    over fixed-width columns there is no scatter at all, and no gather
    whose operand is as long as what a shard receives (n x cap rows:
    today none at all, the reorder is a sort);
    with a string column nothing indexes n x cap rows or n x bcap bytes
    (`take_strings`' own scatter and gathers over ONE shard's bytes
    stay)."""
    cap, bcap = 64, 512
    stage = _exchange_stage(with_string)
    ex = stage.exchange
    fields = ex.schema.fields
    has_offsets = [f.dtype.is_variable_width for f in fields]
    assert any(has_offsets) == with_string

    def g(rows, dtype):
        return jax.ShapeDtypeStruct((N_DEV * rows,), dtype)

    cvs = [CV(g(bcap, jnp.uint8), g(cap, jnp.bool_), g(cap + 1, jnp.int32))
           if f.dtype.is_variable_width
           else CV(g(cap, f.dtype.np_dtype), g(cap, jnp.bool_))
           for f in fields]
    mask = g(cap, jnp.bool_)
    if program == "stage":
        step = stage._program(has_offsets, ())._prog._fn
        text = jax.jit(step).lower(((cvs, mask),)).as_text()
    else:
        from spark_rapids_tpu.exec.mesh_exchange import _flatten_cvs
        step = ex._build_program(has_offsets)._fn
        text = jax.jit(step).lower(_flatten_cvs(cvs), mask).as_text()
    assert "stablehlo.all_to_all" in text
    assert "stablehlo.dynamic_slice" in text
    assert "stablehlo.dynamic_update_slice" in text
    # the payload rides ONE two-operand sort, a word a turn of a loop
    assert text.count('"stablehlo.sort"') == 1
    gathers = _indexed_rows(text, "gather")
    scatters = _indexed_rows(text, "scatter")
    if with_string:
        # the reader is not blind: take_strings' own are found
        assert scatters and max(scatters) <= bcap + 1, scatters
        assert gathers and max(gathers) <= bcap + 1, gathers
    else:
        assert scatters == [], scatters
        assert max(gathers, default=0) <= cap + 1, gathers


def test_stage_counts_the_slots_it_was_sent():
    """`shardSlotsReceived` beside `shardRowsReceivedMax`: every peer
    sends a bucket of the shard's whole capacity, so a stage's slots are
    n x capacity and no shard can receive more rows than that."""
    s = st.TpuSession({"spark.rapids.tpu.mesh.devices": N_DEV,
                       "spark.rapids.tpu.sql.batchSizeRows": 128})
    rng = np.random.default_rng(17)
    n = 2000
    df = s.create_dataframe({
        "k": pa.array(rng.integers(0, 40, n).astype(np.int64)),
        "v": pa.array(rng.integers(0, 9, n).astype(np.int64))})
    q = df.group_by("k").agg(F.sum("v").alias("sv"))
    assert q.to_arrow().num_rows == 40
    ms = [m for m in q.last_metrics().values() if m.get("spmdStages")]
    assert len(ms) == 1
    slots, most = ms[0]["shardSlotsReceived"], ms[0]["shardRowsReceivedMax"]
    assert slots % N_DEV == 0 and slots // N_DEV >= 128
    assert 0 < most <= slots


def test_planner_routes_mesh_exchange():
    s = st.TpuSession({"spark.rapids.tpu.mesh.devices": N_DEV})
    df = s.create_dataframe({"k": pa.array([1, 2], pa.int32()),
                             "v": pa.array([3, 4], pa.int64())})
    plan = df.group_by("k").agg(F.sum("v").alias("s"))
    root, _ = plan._execute()
    from spark_rapids_tpu.exec.mesh_exchange import MeshExchangeExec
    kinds = {type(op).__name__ for op in _walk(root)}
    assert "MeshExchangeExec" in kinds, kinds


def test_distributed_groupby_matches_single_host():
    rng = np.random.default_rng(11)
    n = 1024
    keys = rng.integers(0, 100, n).astype(np.int64)
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    data = {"k": pa.array(keys), "v": pa.array(vals)}

    s1 = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128})
    single = s1.create_dataframe(data).group_by("k").agg(
        F.sum("v").alias("sv"), F.count("v").alias("c"),
        F.min("v").alias("mn"), F.max("v").alias("mx")).to_arrow()
    sm = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128,
                        "spark.rapids.tpu.mesh.devices": N_DEV})
    meshed = sm.create_dataframe(data).group_by("k").agg(
        F.sum("v").alias("sv"), F.count("v").alias("c"),
        F.min("v").alias("mn"), F.max("v").alias("mx")).to_arrow()

    def to_map(t):
        return {t.column(0)[i].as_py():
                tuple(t.column(j)[i].as_py() for j in range(1, 5))
                for i in range(t.num_rows)}
    assert to_map(meshed) == to_map(single)


def test_distributed_groupby_string_keys_with_nulls():
    rng = np.random.default_rng(12)
    n = 512
    kpool = ["alpha", "beta", "gamma", None, "", "delta-longer-key"]
    keys = [kpool[int(i)] for i in rng.integers(0, len(kpool), n)]
    vals = rng.integers(0, 100, n).astype(np.int64)
    data = {"k": pa.array(keys), "v": pa.array(vals)}
    sm = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128,
                        "spark.rapids.tpu.mesh.devices": N_DEV})
    out = sm.create_dataframe(data).group_by("k").agg(
        F.sum("v").alias("sv")).to_arrow()
    got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
    want = {}
    for k, v in zip(keys, vals):
        want[k] = want.get(k, 0) + int(v)
    assert got == want


def test_distributed_join_matches_single_host():
    rng = np.random.default_rng(13)
    n = 512
    lk = rng.integers(0, 60, n).astype(np.int64)
    lv = rng.integers(0, 1000, n).astype(np.int64)
    rk = np.arange(60).astype(np.int64)
    rv = rng.integers(0, 9, 60).astype(np.int64)
    ldata = {"k": pa.array(lk), "lv": pa.array(lv)}
    rdata = {"k": pa.array(rk), "rv": pa.array(rv)}

    def run(conf, want_mesh=False):
        s = st.TpuSession(conf)
        l = s.create_dataframe(ldata)
        r = s.create_dataframe(rdata)
        j = l.join(r, on=["k"], how="inner")
        if want_mesh:
            root, _ = j._execute()
            kinds = {type(op).__name__ for op in _walk(root)}
            assert "MeshExchangeExec" in kinds, kinds
        out = j.to_arrow()
        return sorted(zip(out.column(0).to_pylist(),
                          out.column(1).to_pylist(),
                          out.column(2).to_pylist()))

    single = run({"spark.rapids.tpu.sql.batchSizeRows": 128})
    # force the shuffled path (a tiny broadcast threshold) so the mesh
    # exchange is actually exercised; small builds would broadcast
    meshed = run({"spark.rapids.tpu.sql.batchSizeRows": 128,
                  "spark.rapids.tpu.mesh.devices": N_DEV,
                  "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 16},
                 want_mesh=True)
    assert meshed == single
    # small build under mesh: broadcast (no exchange), same answer
    bc = run({"spark.rapids.tpu.sql.batchSizeRows": 128,
              "spark.rapids.tpu.mesh.devices": N_DEV})
    assert bc == single


@pytest.mark.parametrize("how", ["left", "right", "full", "left_semi",
                                 "left_anti"])
def test_distributed_outer_joins_match_single_host(how):
    rng = np.random.default_rng(17)
    n = 256
    lk = rng.integers(0, 40, n).astype(np.int64)
    lv = np.arange(n).astype(np.int64)
    rk = rng.integers(20, 60, 64).astype(np.int64)
    rv = np.arange(64).astype(np.int64)
    ldata = {"k": pa.array(lk), "lv": pa.array(lv)}
    rdata = {"k": pa.array(rk), "rv": pa.array(rv)}

    def run(conf):
        s = st.TpuSession(conf)
        l = s.create_dataframe(ldata)
        r = s.create_dataframe(rdata)
        out = l.join(r, on=["k"], how=how).to_arrow()
        return sorted((tuple(out.column(i)[j].as_py()
                             for i in range(out.num_columns)))
                      for j in range(out.num_rows))

    single = run({"spark.rapids.tpu.sql.batchSizeRows": 128})
    meshed = run({"spark.rapids.tpu.sql.batchSizeRows": 128,
                  "spark.rapids.tpu.mesh.devices": N_DEV,
                  "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": 16})
    assert meshed == single


def test_mesh_repartition_row_conservation():
    """repartition(k) over the mesh keeps every row exactly once."""
    n = 777
    vals = list(range(n))
    sm = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128,
                        "spark.rapids.tpu.mesh.devices": N_DEV})
    df = sm.create_dataframe({"k": pa.array([v % 13 for v in vals],
                                            pa.int64()),
                              "v": pa.array(vals, pa.int64())})
    try:
        out = df.repartition(N_DEV, "k").to_arrow()
    except (AttributeError, TypeError):
        pytest.skip("repartition API not exposed on DataFrame")
    assert sorted(out.column(1).to_pylist()) == vals


def test_mesh_skewed_shard_spills_and_completes(tmp_path, monkeypatch):
    """One shard receives ~90% of the rows, under a device budget far
    smaller than the input: the chunked exchange must spill its queued and
    received rounds (UCXShuffleTransport.scala:49 bounce-buffer analog)
    rather than hold everything resident — and still answer correctly."""
    import spark_rapids_tpu.memory.device as dev_mod
    import spark_rapids_tpu.memory.spill as spill_mod

    n = 16384
    rng = np.random.default_rng(7)
    keys = np.where(rng.random(n) < 0.9, 7,
                    rng.integers(0, 1000, n)).astype(np.int64)
    vals = rng.integers(-50, 50, n).astype(np.int64)
    tags = [f"tag-{int(k) % 11}" for k in keys]
    data = {"k": pa.array(keys), "v": pa.array(vals),
            "t": pa.array(tags)}

    single = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 256}) \
        .create_dataframe(data).group_by("k").agg(
            F.sum("v").alias("sv"), F.count("t").alias("c")).to_arrow()

    # 64 KiB << input: compaction (maybe_compact + hash-partial shrink)
    # cut resident bytes enough that the old 512 KiB budget no longer
    # forced any spill
    dm = dev_mod.DeviceManager(budget_bytes=64 << 10)
    store = spill_mod.SpillStore(dm, spill_dir=str(tmp_path))
    monkeypatch.setattr(dev_mod, "_GLOBAL", dm)
    monkeypatch.setattr(spill_mod, "_STORE", store)

    sm = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 256,
                        "spark.rapids.tpu.mesh.devices": N_DEV})
    meshed = sm.create_dataframe(data).group_by("k").agg(
        F.sum("v").alias("sv"), F.count("t").alias("c")).to_arrow()

    def to_map(t):
        return {t.column(0)[i].as_py(): (t.column(1)[i].as_py(),
                                         t.column(2)[i].as_py())
                for i in range(t.num_rows)}
    assert to_map(meshed) == to_map(single)
    assert store.metrics["spillToHost"] > 0, store.metrics


def test_mesh_dataframe_reexecution_is_repeatable():
    """The session caches exec trees; a second action on the same mesh
    DataFrame must replay the exchanged partitions, not find them drained."""
    n = 600
    rng = np.random.default_rng(21)
    data = {"k": pa.array(rng.integers(0, 20, n).astype(np.int64)),
            "v": pa.array(rng.integers(0, 9, n).astype(np.int64))}
    sm = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128,
                        "spark.rapids.tpu.mesh.devices": N_DEV})
    df = sm.create_dataframe(data).group_by("k").agg(F.sum("v").alias("s"))
    first = sorted(zip(df.to_arrow().column(0).to_pylist(),
                       df.to_arrow().column(1).to_pylist()))
    second = sorted(zip(df.to_arrow().column(0).to_pylist(),
                        df.to_arrow().column(1).to_pylist()))
    assert first == second and len(first) == 20


def test_mesh_non_power_of_two_devices():
    """Skewed receive on a 3-device mesh: bucketed slice capacities must
    clamp to the shard receive region (out_cap = 3*row_cap isn't 2^k)."""
    n = 3000
    rng = np.random.default_rng(23)
    keys = np.where(rng.random(n) < 0.9, 5,
                    rng.integers(0, 30, n)).astype(np.int64)
    data = {"k": pa.array(keys),
            "v": pa.array(rng.integers(0, 9, n).astype(np.int64)),
            "s": pa.array([f"x{int(k)}" for k in keys])}
    single = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128}) \
        .create_dataframe(data).group_by("k").agg(
            F.sum("v").alias("sv"), F.count("s").alias("c")).to_arrow()
    meshed = st.TpuSession({"spark.rapids.tpu.sql.batchSizeRows": 128,
                            "spark.rapids.tpu.mesh.devices": 3}) \
        .create_dataframe(data).group_by("k").agg(
            F.sum("v").alias("sv"), F.count("s").alias("c")).to_arrow()

    def to_map(t):
        return {t.column(0)[i].as_py(): (t.column(1)[i].as_py(),
                                         t.column(2)[i].as_py())
                for i in range(t.num_rows)}
    assert to_map(meshed) == to_map(single)


def _walk(node):
    yield node
    for m in getattr(node, "members", []) or []:
        yield m
    for c in node.children:
        yield from _walk(c)


def test_make_mesh_too_few_devices_raises_not_cpu_fallback():
    """Too few devices on the default platform is an error: a mesh never
    moves to another platform (it used to return virtual CPU devices)."""
    with pytest.raises(ValueError, match="need 9 devices, have 8"):
        make_mesh(len(jax.devices()) + 1)
