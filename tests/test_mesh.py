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


def _run_exchange(mesh, arrays, mask, pids, use_cvs=False, cvs=None):
    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.parallel.collectives import (exchange_cvs,
                                                       exchange_rows)
    n = N_DEV

    if use_cvs:
        flat = []
        has_off = []
        for cv in cvs:
            flat.extend([cv.data, cv.validity])
            has_off.append(cv.offsets is not None)
            if cv.offsets is not None:
                flat.append(cv.offsets)

        def fn(flat_in, m, p):
            it = iter(flat_in)
            rebuilt = []
            i = 0
            for ho in has_off:
                if ho:
                    rebuilt.append(CV(flat_in[i], flat_in[i + 1],
                                      flat_in[i + 2]))
                    i += 3
                else:
                    rebuilt.append(CV(flat_in[i], flat_in[i + 1]))
                    i += 2
            out_cvs, out_mask = exchange_cvs(rebuilt, m, p, n)
            out_flat = []
            for cv in out_cvs:
                out_flat.extend([cv.data, cv.validity])
                if cv.offsets is not None:
                    out_flat.append(cv.offsets)
            return tuple(out_flat), out_mask

        step = jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(tuple(P("data") for _ in flat), P("data"),
                      P("data")),
            out_specs=(tuple(P("data") for _ in range(
                sum(3 if h else 2 for h in has_off))), P("data"))))
        sharded = tuple(shard_rows(mesh, a) for a in flat)
        return step(sharded, shard_rows(mesh, mask),
                    shard_rows(mesh, pids))

    def fn(arrs, m, p):
        out, om = exchange_rows(list(arrs), m, p, n)
        return tuple(out), om

    step = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(tuple(P("data") for _ in arrays), P("data"), P("data")),
        out_specs=(tuple(P("data") for _ in arrays), P("data"))))
    sharded = tuple(shard_rows(mesh, a) for a in arrays)
    return step(sharded, shard_rows(mesh, mask), shard_rows(mesh, pids))


def test_exchange_rows_conserves_rows(mesh):
    """Every live row arrives on its target shard exactly once."""
    cap = 64
    n = cap * N_DEV
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.integers(0, 1 << 40, n).astype(np.int64))
    mask = jnp.asarray(rng.random(n) < 0.8)
    pids = jnp.asarray(rng.integers(0, N_DEV, n).astype(np.int32))
    (out,), out_mask = _run_exchange(mesh, [vals], mask, pids)
    out_h = np.asarray(jax.device_get(out))
    om_h = np.asarray(jax.device_get(out_mask))
    got = sorted(out_h[om_h].tolist())
    want = sorted(np.asarray(vals)[np.asarray(mask)].tolist())
    assert got == want


def test_exchange_rows_lands_on_target_shard(mesh):
    """Rows land in the output block of the shard named by their pid."""
    cap = 32
    n = cap * N_DEV
    rng = np.random.default_rng(4)
    vals = jnp.arange(n, dtype=jnp.int64)
    mask = jnp.ones(n, jnp.bool_)
    pids = jnp.asarray(rng.integers(0, N_DEV, n).astype(np.int32))
    (out,), out_mask = _run_exchange(mesh, [vals], mask, pids)
    # output is length n*N_DEV; shard s owns slice [s*n, (s+1)*n)
    out_h = np.asarray(jax.device_get(out)).reshape(N_DEV, -1)
    om_h = np.asarray(jax.device_get(out_mask)).reshape(N_DEV, -1)
    pids_h = np.asarray(pids)
    for shard in range(N_DEV):
        rows = out_h[shard][om_h[shard]]
        assert all(pids_h[int(r)] == shard for r in rows)


def test_exchange_cvs_strings_roundtrip(mesh):
    """String columns survive the byte exchange with exact contents."""
    cap = 32
    n = cap * N_DEV
    rng = np.random.default_rng(5)
    strs = [f"s{i}-" + "x" * int(rng.integers(0, 9)) for i in range(n)]
    bs = [x.encode() for x in strs]
    offs = np.zeros(n + 1, np.int32)
    np.cumsum([len(b) for b in bs], out=offs[1:])
    # pad byte buffer so it splits evenly across shards AND each shard's
    # local offsets slice is addressable: lay out per-shard
    data_parts, off_parts, bcap = [], [], 0
    per_shard = [bs[i * cap:(i + 1) * cap] for i in range(N_DEV)]
    bcap = max(sum(len(b) for b in p) for p in per_shard)
    bcap = 1 << (bcap - 1).bit_length()
    for p in per_shard:
        d = b"".join(p)
        arr = np.zeros(bcap, np.uint8)
        arr[:len(d)] = np.frombuffer(d, np.uint8)
        data_parts.append(arr)
        o = np.zeros(cap + 1, np.int32)
        np.cumsum([len(b) for b in p], out=o[1:])
        off_parts.append(o)
    data = jnp.asarray(np.concatenate(data_parts))
    offsets = jnp.asarray(np.concatenate(off_parts))
    valid = jnp.ones(n, jnp.bool_)
    vals = jnp.arange(n, dtype=jnp.int64)
    mask = jnp.asarray(rng.random(n) < 0.9)
    pids = jnp.asarray(rng.integers(0, N_DEV, n).astype(np.int32))

    cvs = [CV(vals, valid.copy()), CV(data, valid, offsets)]
    out_flat, out_mask = _run_exchange(mesh, None, mask, pids,
                                       use_cvs=True, cvs=cvs)
    om = np.asarray(jax.device_get(out_mask))
    ids = np.asarray(jax.device_get(out_flat[0]))[om]
    sdata = np.asarray(jax.device_get(out_flat[2]))
    soff_all = np.asarray(jax.device_get(out_flat[4]))
    # string CV per shard: data [N_DEV*bcap * ...]. Reconstruct row strings
    out_cap = n  # per-shard row capacity after exchange = N_DEV*cap = n
    got = {}
    n_off = out_cap + 1
    sb = sdata.reshape(N_DEV, -1)
    for shard in range(N_DEV):
        offs_s = soff_all[shard * n_off:(shard + 1) * n_off]
        msk_s = om[shard * out_cap:(shard + 1) * out_cap]
        ids_s = np.asarray(jax.device_get(out_flat[0]))[
            shard * out_cap:(shard + 1) * out_cap]
        for r in range(out_cap):
            if msk_s[r]:
                got[int(ids_s[r])] = bytes(
                    sb[shard][offs_s[r]:offs_s[r + 1]]).decode()
    mask_h = np.asarray(mask)
    want = {i: strs[i] for i in range(n) if mask_h[i]}
    assert got == want


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
