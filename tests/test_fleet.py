"""Multi-host serving fabric (spark_rapids_tpu/fleet/): the cluster
cache tier, invalidation broadcast, sticky routing, and warm-state
publication, exercised with 2-3 in-process members on one box.

In-process members are honest stand-ins for separate processes because
each member serves only its OWN export store over a real socket; the
tests simulate "another process's cold local cache" by clearing the
shared process-global result cache between members. Soundness claims
(lost broadcast, stale entry) are tested against real file overwrites.
"""
import json
import os
import socket

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as st
from spark_rapids_tpu import fleet
from spark_rapids_tpu.config import (FLEET_DIRECTORY,
                                     FLEET_PEER_MAX_INFLIGHT,
                                     FLEET_TENANT_MAX_INFLIGHT,
                                     RESULT_CACHE_ENABLED,
                                     WARM_PACK_RECORD)
from spark_rapids_tpu.fleet import context as fctx
from spark_rapids_tpu.fleet.directory import (PeerDirectory, PeerInfo,
                                              rendezvous_order)
from spark_rapids_tpu.fleet.router import RouteRejected, Router
from spark_rapids_tpu.plan import stats as plan_stats
from spark_rapids_tpu.runtime import faults, result_cache

SQL = "SELECT sum(b) AS x FROM t WHERE a > 10"


@pytest.fixture(autouse=True)
def _fleet_clean():
    yield
    faults.clear_plan()
    fleet.reset()
    result_cache.clear()


@pytest.fixture()
def fabric(tmp_path):
    """One session + data table + joined default member A."""
    data = tmp_path / "data"
    data.mkdir()
    p = str(data / "t.parquet")
    pq.write_table(pa.table({"a": list(range(100)),
                             "b": [i * 2 for i in range(100)]}), p)
    s = st.TpuSession()
    s.set_conf(RESULT_CACHE_ENABLED.key, True)
    s.set_conf(FLEET_DIRECTORY.key, str(tmp_path / "fleet"))
    s.read.parquet(p).create_or_replace_temp_view("t")
    a = fleet.join(s)
    members = [a]

    def spawn():
        m = fleet.FleetMember(s, s.conf, str(tmp_path / "fleet"))
        members.append(m)
        return m

    yield s, a, spawn, p
    for m in members:
        m.leave()


def _arrow(s, sql=SQL):
    return s.sql(sql).to_arrow()


# ---------------------------------------------------------------------
# cluster cache tier
# ---------------------------------------------------------------------
def test_peer_hit_byte_identity(fabric):
    s, a, spawn, _ = fabric
    with fctx.scoped(a):
        ref = _arrow(s)
    assert a.stats["fleet_publishes"] == 1
    b = spawn()
    result_cache.clear()            # B's "process" starts cold
    with fctx.scoped(b):
        got = _arrow(s)
    assert got.equals(ref)          # byte-identical arrow table
    assert b.stats["fleet_peer_hits"] == 1
    assert result_cache.stats()["result_cache_peer_hits"] == 1
    # adopted without re-export: B never serves what it did not compute
    assert b.export.stats()["entries"] == 0


def test_peer_miss_recomputes_locally(fabric):
    s, a, spawn, _ = fabric
    b = spawn()
    with fctx.scoped(b):
        got = _arrow(s)             # nobody has it: fleet-wide miss
    assert got.num_rows == 1
    assert b.stats["fleet_peer_misses"] >= 1
    assert b.stats["fleet_peer_hits"] == 0


def test_uncache_broadcast_reaches_peers(fabric):
    s, a, spawn, _ = fabric
    with fctx.scoped(a):
        _arrow(s)
    assert a.export.stats()["entries"] == 1
    b = spawn()
    df = s.sql(SQL)
    with fctx.scoped(b):
        df.uncache()                # B's uncache must not leave stale
    assert a.export.stats()["entries"] == 0   # ...entries on peer A
    assert b.stats["fleet_inv_broadcasts"] >= 1
    assert a.stats["fleet_inv_applied"] >= 1
    result_cache.clear()
    with fctx.scoped(b):
        got = _arrow(s)             # miss-then-recompute, not a hit
    assert b.stats["fleet_peer_hits"] == 0
    assert got.num_rows == 1


def test_invalidate_prefix_broadcasts(fabric):
    s, a, spawn, p = fabric
    with fctx.scoped(a):
        _arrow(s)
    b = spawn()
    with fctx.scoped(b):
        result_cache.invalidate_prefix(os.path.dirname(p))
    assert a.export.stats()["entries"] == 0
    assert b.stats["fleet_inv_broadcasts"] == 1


@pytest.mark.parametrize("lost", ["never_sent", "every_send_fails"])
def test_lost_broadcast_soundness_via_snapshot_keys(fabric, lost):
    """A peer that never hears an invalidation holds its stale entry
    under a key embedding the OLD file snapshot; a requester re-stats
    before computing its key, so it asks for a key nobody holds and
    recomputes against the new bytes. The broadcast is lost either
    because none runs or because every send of it fails (counted)."""
    s, a, spawn, p = fabric
    with fctx.scoped(a):
        stale = _arrow(s)
    assert a.export.stats()["entries"] == 1
    # external overwrite
    pq.write_table(pa.table({"a": list(range(100)),
                             "b": [i * 3 for i in range(100)]}), p)
    b = spawn()
    if lost == "every_send_fails":
        faults.install_plan("peer.fetch:prob=1:raise=FetchFailed")
        try:
            with fctx.scoped(b):
                result_cache.invalidate_prefix(os.path.dirname(p))
        finally:
            faults.clear_plan()
        assert b.stats["fleet_inv_broadcast_failures"] >= 1
        assert a.export.stats()["entries"] == 1   # A never heard it
    result_cache.clear()
    with fctx.scoped(b):
        fresh = _arrow(s)
    assert not fresh.equals(stale)
    assert fresh.to_pydict()["x"][0] == sum(
        i * 3 for i in range(100) if i > 10)
    assert b.stats["fleet_peer_hits"] == 0    # stale key unreachable


def test_stale_entry_rejected_by_requester_restat(fabric):
    """Defense in depth for the race the key discipline cannot see:
    the entry's key is still current on the requester's view, but the
    files changed between the owner's publish and the fetch. The
    shipped snapshot is re-stat'd on the requester and the entry is
    rejected, counted, recomputed."""
    s, a, spawn, p = fabric
    with fctx.scoped(a):
        _arrow(s)
    old_key = next(iter(a.export._entries))
    _, _, meta = a.export._entries[old_key]
    assert meta["snapshot"]         # publish recorded the snapshot
    pq.write_table(pa.table({"a": list(range(100)),
                             "b": [i * 5 for i in range(100)]}), p)
    b = spawn()
    got = b.consult(old_key)        # ask for the now-stale key directly
    assert got is None
    assert b.stats["fleet_peer_stale_rejected"] == 1
    assert b.stats["fleet_peer_hits"] == 0


def test_peer_fetch_fault_degrades_byte_identical(fabric):
    s, a, spawn, _ = fabric
    with fctx.scoped(a):
        ref = _arrow(s)
    b = spawn()
    result_cache.clear()
    faults.install_plan("peer.fetch:prob=1:raise=FetchFailed")
    try:
        with fctx.scoped(b):
            got = _arrow(s)         # every fetch fails -> recompute
    finally:
        faults.clear_plan()
    assert got.equals(ref)
    assert b.stats["fleet_peer_fetch_failures"] >= 1
    assert b.stats["fleet_peer_hits"] == 0


def test_peer_fetch_delay_still_hits(fabric):
    s, a, spawn, _ = fabric
    with fctx.scoped(a):
        ref = _arrow(s)
    b = spawn()
    result_cache.clear()
    faults.install_plan("peer.fetch:nth=1:delay=20")
    try:
        with fctx.scoped(b):
            got = _arrow(s)
    finally:
        faults.clear_plan()
    assert got.equals(ref)
    assert b.stats["fleet_peer_hits"] == 1


def test_fleet_confs_never_split_cache_keys():
    """sql.fleet.* keys NECESSARILY differ per member (directory,
    advertise host); they must not flow into result-cache keys or no
    cross-peer key would ever match."""
    from spark_rapids_tpu.config import TpuConf
    c1 = TpuConf({"spark.rapids.tpu.sql.fleet.directory": "/a",
                  "spark.rapids.tpu.sql.batchSizeRows": 1024})
    c2 = TpuConf({"spark.rapids.tpu.sql.fleet.directory": "/b",
                  "spark.rapids.tpu.sql.batchSizeRows": 1024})
    c3 = TpuConf({"spark.rapids.tpu.sql.batchSizeRows": 2048})
    assert result_cache._conf_fp(c1) == result_cache._conf_fp(c2)
    assert result_cache._conf_fp(c1) != result_cache._conf_fp(c3)


# ---------------------------------------------------------------------
# membership + rendezvous routing
# ---------------------------------------------------------------------
def test_rendezvous_minimal_reassignment():
    peers = ["h:1", "h:2", "h:3"]
    keys = [("q", ("fp", i)) for i in range(60)]
    owner3 = {k: rendezvous_order(k, peers)[0] for k in keys}
    survivors = ["h:1", "h:3"]
    owner2 = {k: rendezvous_order(k, survivors)[0] for k in keys}
    for k in keys:
        if owner3[k] != "h:2":
            assert owner2[k] == owner3[k]   # unaffected keys stay put
        else:
            assert owner2[k] in survivors
    # and every member computes the same order independently
    assert rendezvous_order(keys[0], list(reversed(peers))) == \
        rendezvous_order(keys[0], peers)


def test_directory_liveness_skips_dead_pids(tmp_path):
    d = PeerDirectory(str(tmp_path))
    d.register(PeerInfo("h:1", "h", 1, pid=os.getpid()))
    d.register(PeerInfo("h:2", "h", 2, pid=2 ** 22 + 12345))
    live = [p.peer_id for p in d.peers()]
    assert live == ["h:1"]
    assert [p.peer_id for p in d.peers(live_only=False)] == \
        ["h:1", "h:2"]


def _routing_member(tmp_path, s, gw_peers=3, **conf):
    for k, v in conf.items():
        s.set_conf(k, v)
    m = fleet.FleetMember(s, s.conf, str(tmp_path / "fleet"),
                          gateway_addr=("127.0.0.1", 9000))
    for i in range(1, gw_peers):
        m.directory.register(PeerInfo(f"fake:{i}", "127.0.0.1", 20000 + i,
                                      gw_host="127.0.0.1",
                                      gw_port=21000 + i))
    m.refresh_peers()
    return m


def test_router_sticky_then_spill(tmp_path):
    s = st.TpuSession()
    m = _routing_member(tmp_path, s, gw_peers=3,
                        **{FLEET_PEER_MAX_INFLIGHT.key: 1})
    try:
        r = Router(m)
        fp = ("fp", "sticky")
        first = r.route(fp)
        assert first["sticky"]
        second = r.route(fp)        # owner saturated: stable spill
        assert not second["sticky"]
        assert second["peer_id"] != first["peer_id"]
        assert r.stats()["fleet_route_sticky"] == 1
        assert r.stats()["fleet_route_spill"] == 1
        r.done(first["lease"])
        third = r.route(fp)         # slot freed: sticky again
        assert third["sticky"] and third["peer_id"] == first["peer_id"]
    finally:
        m.leave()


def test_router_tenant_cap_rejects(tmp_path):
    s = st.TpuSession()
    m = _routing_member(tmp_path, s, gw_peers=2,
                        **{FLEET_TENANT_MAX_INFLIGHT.key: 2})
    try:
        r = Router(m)
        l1 = r.route(("fp", 1), tenant="analytics")
        r.route(("fp", 2), tenant="analytics")
        with pytest.raises(RouteRejected):
            r.route(("fp", 3), tenant="analytics")
        # other tenants are unaffected; freeing a lease re-admits
        assert r.route(("fp", 3), tenant="etl")["peer_id"]
        r.done(l1["lease"])
        assert r.route(("fp", 3), tenant="analytics")["peer_id"]
        assert r.stats()["fleet_route_rejected"] == 1
    finally:
        m.leave()


def test_router_rebalances_on_peer_death(tmp_path):
    s = st.TpuSession()
    m = _routing_member(tmp_path, s, gw_peers=3)
    try:
        r = Router(m)
        fps = [("fp", i) for i in range(40)]
        before = {fp: r.route(fp)["peer_id"] for fp in fps}
        assert len(set(before.values())) == 3   # all peers used
        m.directory.deregister("fake:1")        # peer dies
        m.refresh_peers()
        after = {fp: r.route(fp)["peer_id"] for fp in fps}
        for fp in fps:
            if before[fp] != "fake:1":
                assert after[fp] == before[fp]  # survivors keep keys
            else:
                assert after[fp] != "fake:1"    # orphans reassigned
    finally:
        m.leave()


# ---------------------------------------------------------------------
# gateway verbs
# ---------------------------------------------------------------------
def _rpc(f, **req):
    f.write(json.dumps(req) + "\n")
    f.flush()
    return json.loads(f.readline())


def test_gateway_route_and_fleet_verbs(fabric):
    s, a, spawn, _ = fabric
    srv = s.serve()
    try:
        member = s._fleet_member
        assert member is not None
        with socket.create_connection(srv.address) as sock:
            f = sock.makefile("rw")
            out = _rpc(f, op="route", sql=SQL, tenant="t1")
            assert out["ok"] and out["peer_id"] == member.peer_id
            assert out["sticky"] and (out["host"], out["port"]) == \
                srv.address
            assert _rpc(f, op="route_done",
                        lease=out["lease"])["released"]
            info = _rpc(f, op="fleet")
            assert info["ok"] and info["peer_id"] == member.peer_id
            assert any(p["peer_id"] == member.peer_id
                       for p in info["peers"])
            assert info["router"]["fleet_route_sticky"] == 1
            # submits through the gateway publish as this member
            out = _rpc(f, op="submit", sql=SQL)
            assert out["ok"]
            import time
            deadline = time.time() + 30
            while time.time() < deadline:
                st_ = _rpc(f, op="status", query_id=out["query_id"])
                if st_.get("state") in ("FINISHED", "FAILED"):
                    break
                time.sleep(0.01)
            assert st_["state"] == "FINISHED"
            assert member.export.stats()["entries"] >= 1
    finally:
        srv.close()
        s.stop()


def test_gateway_metrics_exposes_fleet_gauges(fabric):
    s, a, spawn, _ = fabric
    with fctx.scoped(a):
        _arrow(s)
    srv = s.serve()
    try:
        with socket.create_connection(srv.address) as sock:
            f = sock.makefile("rw")
            out = _rpc(f, op="metrics")
            assert out["ok"]
            gauges = out["metrics"]["gauges"]
            # the registered "fleet" pull-gauge fn expands per stat
            assert gauges.get("fleet_fleet_publishes") == 1, \
                sorted(k for k in gauges if k.startswith("fleet"))
            assert gauges.get("fleet_fleet_peers_live") == 1
    finally:
        srv.close()
        s.stop()


# ---------------------------------------------------------------------
# warm-state publication
# ---------------------------------------------------------------------
def test_cold_join_pulls_warm_state(fabric):
    s, a, spawn, _ = fabric
    s.set_conf(WARM_PACK_RECORD.key, "/dev/null")  # enables recording
    with fctx.scoped(a):
        _arrow(s)                   # SQL recorded into the manifest
    plan_stats._calibration_record(("fleet-test-key",), 42.0)
    b = fleet.FleetMember(s, s.conf, str(a.directory.root))
    try:
        summary = b.pull_warm_state()
        assert summary["status"] == "ok"
        assert summary["donor"] == a.peer_id
        pre = summary.get("preload")
        assert pre and pre["status"] == "ok"
        assert pre["queries"] >= 1   # the donor's recorded SQL arrived
        assert pre["queries_planned"] >= 1   # ...and replayed warm
        assert a.stats["fleet_warm_served"] == 1
        assert b.stats["fleet_warm_pulls"] == 1
    finally:
        b.leave()


def test_warm_calibration_export_import_round_trip(fabric):
    """The calibration half of the warm payload, isolated: in-process
    members share ONE calibration table, so the pull path cannot show
    adoption (the importer already 'has' everything) — exercise the
    wire-shaped export/import pair directly against a cleared table,
    which is exactly a separate process's view."""
    s, a, spawn, _ = fabric
    s.set_conf(WARM_PACK_RECORD.key, "/dev/null")
    plan_stats._calibration_record(("fleet-test-key",), 42.0)
    payload = a.warm_state_payload()
    assert dict(payload["calibration"])[("fleet-test-key",)] == 42.0
    plan_stats.clear_calibration()            # the joiner's cold table
    adopted = plan_stats.import_calibration(payload["calibration"])
    assert adopted >= 1
    with plan_stats.calibration_scope(True):
        assert plan_stats.calibration_lookup(("fleet-test-key",)) == 42.0
    # local observations beat peer entries: re-import adopts nothing
    assert plan_stats.import_calibration(payload["calibration"]) == 0


def test_warm_pull_skips_without_donor(tmp_path):
    s = st.TpuSession()
    m = fleet.FleetMember(s, s.conf, str(tmp_path / "solo"))
    try:
        assert m.pull_warm_state() == {"status": "skipped"}
    finally:
        m.leave()


def test_join_noop_without_directory_conf():
    s = st.TpuSession()
    assert fleet.join(s) is None
    assert fctx.default_member() is None
