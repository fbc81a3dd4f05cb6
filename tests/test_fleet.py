"""Elastic replicated serving fleet suite (``make fleet``).

Covers the four fleet modules plus their satellites:

  * consistent-hash ring — determinism across instances, bounded
    reshuffle on member change, distinct preference walks;
  * membership directory — announce/scan/deregister, heartbeat
    freshness, tolerance of torn/garbage records, leader election by
    freshest heartbeat;
  * WAL follower — live shipping onto a follower graph, abort
    holdback + late-abort resync, the three tailing edge cases the
    issue names (open mid-segment-rotation, torn tail waits instead of
    erroring, leader ``truncate_through`` resyncs instead of
    stranding), staleness gauges;
  * replica lifecycle + router — warm join ladder, per-instance
    ``/healthz``+``/metrics`` on ephemeral ports (two replicas on one
    host), drain choreography, dead-replica re-dispatch with zero lost
    answers, typed-shed answers never retried, typed
    ``NoReplicaAvailable`` when the fleet is empty, ``/debug/fleet``;
  * chaos points — ``fleet.route`` fires deterministically from a
    seeded plan;
  * the failover harness — ``benchmarks/fleet_chaos.py`` smoke report
    asserted end to end (marked slow: three real child processes).
"""

import io
import json
import os
import socket
import time
import urllib.request

import numpy as np
import pytest

from quiver_tpu import telemetry
from quiver_tpu.fleet import (FLEET_STATES, ConsistentHashRing,
                              FleetReplica, FleetRouter,
                              MembershipDirectory, ReplicaInfo,
                              WALFollower, fleet_status)
from quiver_tpu.recovery import blockio
from quiver_tpu.recovery.wal import (WriteAheadLog, encode_abort,
                                     encode_edge_op)
from quiver_tpu.resilience import chaos
from quiver_tpu.resilience.breaker import reset as breakers_reset
from quiver_tpu.resilience.errors import (ChaosFault, LoadShed,
                                          NoReplicaAvailable)
from quiver_tpu.stream import StreamingGraph
from quiver_tpu.utils.topology import CSRTopo

pytestmark = pytest.mark.fleet

N_NODES = 64


def _topo():
    src = np.arange(N_NODES, dtype=np.int64)
    dst = (src + 1) % N_NODES
    return CSRTopo(edge_index=np.stack([src, dst]))


def _graph():
    return StreamingGraph(_topo(), delta_capacity=4096)


def counter_value(name, **labels):
    from quiver_tpu.telemetry.registry import metric_key

    return telemetry.snapshot()["counters"].get(
        metric_key(name, labels), 0)


def gauge_value(name, **labels):
    from quiver_tpu.telemetry.registry import metric_key

    return telemetry.snapshot()["gauges"].get(metric_key(name, labels))


@pytest.fixture(autouse=True)
def _clean():
    yield
    chaos.uninstall()
    breakers_reset()


# ------------------------------------------------------------- ring
class TestConsistentHashRing:
    def test_deterministic_across_instances(self):
        a, b = ConsistentHashRing(vnodes=32), ConsistentHashRing(vnodes=32)
        a.set_members(["r0", "r1", "r2"])
        b.set_members(["r2", "r0", "r1"])  # order must not matter
        for p in range(32):
            assert a.preference(p) == b.preference(p)

    def test_preference_walk_distinct_and_complete(self):
        r = ConsistentHashRing(vnodes=16)
        r.set_members(["a", "b", "c"])
        for p in range(16):
            prefs = r.preference(p)
            assert sorted(prefs) == ["a", "b", "c"]
            assert len(set(prefs)) == 3
        assert r.preference(0, n=2) == r.preference(0)[:2]

    def test_member_change_reshuffles_partially(self):
        r = ConsistentHashRing(vnodes=64)
        r.set_members(["a", "b", "c"])
        before = {p: r.preference(p, 1)[0] for p in range(256)}
        r.set_members(["a", "b", "c", "d"])
        after = {p: r.preference(p, 1)[0] for p in range(256)}
        moved = sum(1 for p in before if after[p] != before[p])
        # consistent hashing: only partitions adopted by the new member
        # move — everything that moved must have moved TO d, and the
        # move fraction stays near 1/N, never a full reshuffle
        assert all(after[p] == "d" for p in before if after[p] != before[p])
        assert 0 < moved < 128

    def test_empty_ring(self):
        assert ConsistentHashRing(vnodes=4).preference(0) == []


# ------------------------------------------------------- membership
class TestMembership:
    def test_announce_scan_deregister(self, tmp_path):
        d = MembershipDirectory(tmp_path, heartbeat_timeout_s=5.0)
        d.announce(ReplicaInfo("r0", state="serving", port=1234,
                               role="leader"))
        d.announce(ReplicaInfo("r1", state="booting", port=1235))
        got = d.replicas()
        assert [r.replica_id for r in got] == ["r0", "r1"]
        assert d.get("r0").port == 1234
        assert d.leader().replica_id == "r0"
        assert d.deregister("r1") is True
        assert d.deregister("r1") is False
        assert [r.replica_id for r in d.replicas()] == ["r0"]

    def test_freshness_window(self, tmp_path):
        # a window wide enough for a worker that is held up between the
        # write and the read (50 ms was not, under six loaded workers)
        d = MembershipDirectory(tmp_path, heartbeat_timeout_s=0.5)
        d.announce(ReplicaInfo("r0", state="serving"))
        assert [r.replica_id for r in d.replicas(fresh_only=True)] \
            == ["r0"]
        time.sleep(0.7)
        assert d.replicas(fresh_only=True) == []
        # stale records remain visible to operators
        assert [r.replica_id for r in d.replicas()] == ["r0"]
        assert gauge_value("fleet_replicas_total", state="serving") == 0.0

    def test_garbage_record_skipped_not_fatal(self, tmp_path):
        d = MembershipDirectory(tmp_path, heartbeat_timeout_s=5.0)
        d.announce(ReplicaInfo("r0", state="serving"))
        (tmp_path / "replica-torn.json").write_bytes(b'{"repl')
        before = counter_value("fleet_membership_parse_errors_total")
        assert [r.replica_id for r in d.replicas()] == ["r0"]
        assert counter_value(
            "fleet_membership_parse_errors_total") == before + 1

    def test_unknown_state_rejected(self, tmp_path):
        d = MembershipDirectory(tmp_path)
        with pytest.raises(ValueError, match="unknown fleet state"):
            d.announce(ReplicaInfo("r0", state="zombie"))

    def test_states_ladder(self):
        assert FLEET_STATES == ("booting", "replaying", "warming",
                                "serving", "draining")

    def test_status_document(self, tmp_path):
        d = MembershipDirectory(tmp_path, heartbeat_timeout_s=5.0)
        d.announce(ReplicaInfo("r0", state="serving"))
        doc = d.status()
        assert doc["replicas"][0]["fresh"] is True
        assert doc["replicas"][0]["heartbeat_age_s"] >= 0.0


# ----------------------------------------------------- WAL follower
class _Tail:
    """Follower-side sink recording every applied record."""

    def __init__(self):
        self.applied = []

    def __call__(self, lsn, op, src, dst, ts):
        self.applied.append((lsn, op, list(map(int, src)),
                             list(map(int, dst))))


def _follower(wal_dir, tail, **kw):
    kw.setdefault("grace_s", 30.0)  # holdback resolves via successors
    kw.setdefault("name", "t")
    return WALFollower(str(wal_dir), apply_fn=tail, **kw)


class TestWALFollower:
    def test_ships_committed_records(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        for i in range(5):
            w.append(encode_edge_op("add", [i], [i + 1]))
        tail = _Tail()
        f = _follower(tmp_path / "wal", tail)
        f.poll_once()
        # newest record held back (abort holdback), 4 committed
        assert [lsn for lsn, *_ in tail.applied] == [0, 1, 2, 3]
        assert f.status()["staleness_lsn"] == 1
        w.append(encode_edge_op("add", [9], [10]))
        f.poll_once()  # successor slot proves no abort for lsn 4
        assert [lsn for lsn, *_ in tail.applied] == [0, 1, 2, 3, 4]
        w.close()

    def test_grace_expiry_commits_tail(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        w.append(encode_edge_op("add", [1], [2]))
        tail = _Tail()
        f = _follower(tmp_path / "wal", tail, grace_s=0.02)
        f.poll_once()
        assert tail.applied == []  # inside the grace window
        time.sleep(0.05)
        f.poll_once()
        assert [lsn for lsn, *_ in tail.applied] == [0]
        assert f.status()["staleness_lsn"] == 0
        w.close()

    def test_abort_holdback_skips_aborted_record(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        w.append(encode_edge_op("add", [1], [2]))      # lsn 0: commits
        w.append(encode_edge_op("add", [3], [4]))      # lsn 1: aborted
        w.append(encode_abort(1))                      # lsn 2
        w.append(encode_edge_op("add", [5], [6]))      # lsn 3: commits
        w.append(encode_edge_op("add", [7], [8]))      # lsn 4: successor
        tail = _Tail()
        before = counter_value("fleet_ship_aborted_total", replica="t")
        f = _follower(tmp_path / "wal", tail)
        f.poll_once()
        assert [lsn for lsn, *_ in tail.applied] == [0, 3]
        assert counter_value("fleet_ship_aborted_total",
                             replica="t") == before + 1
        assert f.applied_lsn == 3  # lsn 4 held pending a successor
        w.close()

    def test_late_abort_triggers_resync(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        w.append(encode_edge_op("add", [1], [2]))      # lsn 0
        tail = _Tail()
        resyncs = []

        def resync():
            # a real resync_fn restores the newest checkpoint; here the
            # checkpoint "covers" both records, so resume past them
            resyncs.append(True)
            return 2

        f = _follower(tmp_path / "wal", tail, grace_s=0.0,
                      resync_fn=resync)
        f.poll_once()  # grace 0: lsn 0 commits immediately
        assert [lsn for lsn, *_ in tail.applied] == [0]
        w.append(encode_abort(0))                      # late abort
        before = counter_value("fleet_ship_late_aborts_total",
                               replica="t")
        f.poll_once()
        assert resyncs == [True]
        assert counter_value("fleet_ship_late_aborts_total",
                             replica="t") == before + 1
        assert f.applied_lsn == 1  # resumed at the resync watermark
        w.close()

    def test_torn_tail_waits_instead_of_erroring(self, tmp_path):
        """Satellite: a torn tail is a write in progress — the follower
        must keep its offset and re-poll, never raise or misframe."""
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        w.append(encode_edge_op("add", [1], [2]))
        w.append(encode_edge_op("add", [3], [4]))
        seg = os.path.join(str(tmp_path / "wal"),
                           sorted(os.listdir(tmp_path / "wal"))[0])
        # frame the next record out-of-band and append only half of it:
        # exactly what a reader racing the leader's write() observes
        buf = io.BytesIO()
        blockio.write_record(buf, encode_edge_op("add", [5], [6]))
        frame = buf.getvalue()
        with open(seg, "ab") as fh:
            fh.write(frame[:len(frame) // 2])
        tail = _Tail()
        f = _follower(tmp_path / "wal", tail)
        before = counter_value("fleet_ship_torn_waits_total", replica="t")
        f.poll_once()
        f.poll_once()  # still torn: waits again, no error, no re-count
        assert [lsn for lsn, *_ in tail.applied] == [0]  # lsn 1 held
        assert counter_value("fleet_ship_torn_waits_total",
                             replica="t") == before + 1
        assert f.status()["last_error"] is None
        with open(seg, "ab") as fh:  # the leader finishes its write
            fh.write(frame[len(frame) // 2:])
        f.poll_once()
        assert [lsn for lsn, *_ in tail.applied] == [0, 1]
        w.close()

    def test_opens_mid_segment_rotation(self, tmp_path):
        """Satellite: a follower whose start watermark lands inside a
        sealed middle segment repositions correctly and ships across
        the rotation boundary."""
        w = WriteAheadLog(tmp_path / "wal", fsync="always",
                          segment_bytes=1)  # roll after every record
        for i in range(6):
            w.append(encode_edge_op("add", [i], [i + 1]))
        assert len(os.listdir(tmp_path / "wal")) > 1
        tail = _Tail()
        f = _follower(tmp_path / "wal", tail, start_lsn=2)
        f.poll_once()
        assert [lsn for lsn, *_ in tail.applied] == [3, 4]  # 5 held
        w.append(encode_edge_op("add", [9], [9]))
        f.poll_once()
        assert [lsn for lsn, *_ in tail.applied] == [3, 4, 5]
        w.close()

    def test_truncate_through_resyncs_not_strands(self, tmp_path):
        """Satellite: leader checkpoint + ``truncate_through`` deletes
        segments a lagging follower needed — it must resync from the
        checkpoint watermark, not strand or silently skip."""
        w = WriteAheadLog(tmp_path / "wal", fsync="always",
                          segment_bytes=1)
        for i in range(6):
            w.append(encode_edge_op("add", [i], [i + 1]))
        # barrier checkpoint covered lsns 0..3; the log drops them
        w.truncate_through(3)
        tail = _Tail()
        resyncs = []

        def resync():
            resyncs.append(True)
            return 4  # checkpoint watermark + 1

        f = _follower(tmp_path / "wal", tail, start_lsn=-1,
                      resync_fn=resync)
        before = counter_value("fleet_ship_resyncs_total", replica="t")
        f.poll_once()
        assert resyncs == [True]
        assert counter_value("fleet_ship_resyncs_total",
                             replica="t") == before + 1
        assert [lsn for lsn, *_ in tail.applied] == [4]  # 5 held
        assert f.status()["resyncs"] == 1
        # without a resync_fn the same situation is a loud error
        f2 = _follower(tmp_path / "wal", _Tail(), start_lsn=-1)
        from quiver_tpu.recovery.errors import WALError

        with pytest.raises(WALError, match="stranded"):
            f2.poll_once()
        w.close()

    def test_staleness_gauges_published(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")
        w.append(encode_edge_op("add", [1], [2]))
        f = _follower(tmp_path / "wal", _Tail(), name="stale-t")
        f.poll_once()
        assert gauge_value("fleet_replica_staleness_lsn",
                           replica="stale-t") == 1.0
        assert gauge_value("fleet_replica_staleness_seconds",
                           replica="stale-t") >= 0.0
        w.append(encode_edge_op("add", [3], [4]))
        w.append(encode_edge_op("add", [5], [6]))
        time.sleep(0.0)
        f.poll_once()
        assert f.status()["applied_lsn"] == 1
        w.close()

    def test_thread_loop_survives_apply_errors(self, tmp_path):
        w = WriteAheadLog(tmp_path / "wal", fsync="always")

        def bad_apply(*a):
            raise RuntimeError("apply exploded")

        w.append(encode_edge_op("add", [1], [2]))
        w.append(encode_edge_op("add", [3], [4]))
        f = WALFollower(str(tmp_path / "wal"), apply_fn=bad_apply,
                        grace_s=0.0, poll_interval_s=0.01,
                        name="bad").start()
        deadline = time.time() + 5
        while time.time() < deadline and \
                f.status()["last_error"] is None:
            time.sleep(0.01)
        assert "apply exploded" in (f.status()["last_error"] or "")
        assert f.is_running()
        f.stop()
        assert not f.is_running()
        w.close()


# ------------------------------------------- replica + router (e2e)
@pytest.fixture
def fleet(tmp_path):
    """One in-process leader + one follower over a shared root, plus a
    router; tears everything down in reverse order."""
    import quiver_tpu.config as config_mod

    cfg = config_mod.get_config()
    saved = {k: getattr(cfg, k) for k in
             ("fleet_ship_poll_ms", "fleet_ship_grace_ms")}
    config_mod.update(fleet_ship_poll_ms=10.0, fleet_ship_grace_ms=60.0)
    root = str(tmp_path / "dur")
    fdir = str(tmp_path / "fleet")
    members = []

    def spawn(rid, role, **kw):
        rep = FleetReplica(rid, fleet_dir=fdir, root=root,
                           graph_factory=_graph, role=role,
                           heartbeat_s=0.1, **kw).boot()
        members.append(rep)
        return rep

    directory = MembershipDirectory(fdir, heartbeat_timeout_s=2.0)
    routers = []

    def make_router(**kw):
        kw.setdefault("scan_ttl_s", 0.0)
        kw.setdefault("request_timeout_s", 1.0)
        r = FleetRouter(directory, **kw)
        routers.append(r)
        return r

    yield type("F", (), {"spawn": staticmethod(spawn),
                         "router": staticmethod(make_router),
                         "directory": directory, "root": root,
                         "fleet_dir": fdir, "members": members})
    for r in routers:
        r.close()
    for rep in reversed(members):
        rep.stop()
    config_mod.update(**saved)


def _ingest(leader, n, start=0):
    for i in range(start, start + n):
        leader.lane.submit([i % N_NODES], [(i * 7 + 3) % N_NODES])
    for _ in range(n):
        _u, res = leader.lane.results.get(timeout=10)
        assert not isinstance(res, Exception), res


class TestFleetEndToEnd:
    def test_join_ladder_and_replication(self, fleet):
        leader = fleet.spawn("r0", "leader")
        _ingest(leader, 10)
        leader.manager.checkpoint(timeout=10)
        follower = fleet.spawn("r1", "follower")
        assert follower.state == "serving"
        assert follower.graph.version == leader.graph.version
        # live shipping: new leader writes reach the follower
        _ingest(leader, 10, start=10)
        deadline = time.time() + 10
        while time.time() < deadline and \
                follower.graph.version != leader.graph.version:
            time.sleep(0.02)
        assert follower.graph.version == leader.graph.version
        assert gauge_value("fleet_join_seconds", replica="r1") > 0.0
        info = fleet.directory.get("r1")
        assert info.state == "serving" and info.role == "follower"

    def test_two_replicas_metrics_coexist_one_host(self, fleet):
        """Satellite: two replicas' /healthz + /metrics must coexist on
        one host via ephemeral ports, each reporting ITS OWN ladder."""
        leader = fleet.spawn("r0", "leader")
        leader.manager.checkpoint(timeout=10)
        follower = fleet.spawn("r1", "follower")
        m0, m1 = leader.expose_metrics(), follower.expose_metrics()
        assert m0.port != m1.port and m0.port > 0 and m1.port > 0
        docs = {}
        for port in (m0.port, m1.port):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
                doc = json.loads(r.read())
                docs[doc["replica_id"]] = doc
                assert r.status == 200
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
                assert r.status == 200
        assert docs["r0"]["role"] == "leader"
        assert docs["r1"]["role"] == "follower"
        assert "staleness_lsn" in docs["r1"]

    def test_router_routes_and_debug_fleet(self, fleet):
        leader = fleet.spawn("r0", "leader")
        leader.manager.checkpoint(timeout=10)
        fleet.spawn("r1", "follower")
        router = fleet.router()
        for i in range(20):
            reply = router.request([i, i + 1], seq=i)
            assert reply["status"] == "ok"
            assert reply["seq"] == i
            assert reply["replica"] in ("r0", "r1")
        served = {rid: counter_value("fleet_router_requests_total",
                                     replica=rid, status="ok")
                  for rid in ("r0", "r1")}
        assert sum(served.values()) >= 20
        doc = fleet_status()
        assert doc["active"] is True
        assert sorted(doc["eligible"]) == ["r0", "r1"]
        ms = leader.expose_metrics()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{ms.port}/debug/fleet",
                timeout=5) as r:
            served_doc = json.loads(r.read())
        assert served_doc["active"] is True
        assert served_doc["membership"]["replicas"]

    def test_dead_replica_redispatch_zero_lost(self, fleet):
        """A replica that vanishes without drain: its requests must be
        re-dispatched and answered, never lost."""
        leader = fleet.spawn("r0", "leader")
        leader.manager.checkpoint(timeout=10)
        follower = fleet.spawn("r1", "follower")
        # wide partition space so the 2-member ring gives r1 ownership
        # of some partitions (8 partitions can all land on one member)
        router = fleet.router(partitions=64)
        # hard-stop the follower's endpoint WITHOUT deregistering —
        # membership still says serving, exactly like a kill -9
        follower._server.shutdown()
        follower._server.server_close()
        answered = 0
        for i in range(32):
            reply = router.request([i], seq=i)
            assert reply["status"] == "ok"
            assert reply["replica"] == "r0"
            answered += 1
        assert answered == 32
        redis = counter_value("fleet_router_redispatch_total",
                              replica="r1")
        assert redis > 0

    def test_shed_is_an_answer_not_a_retry(self, fleet):
        def shedding_service(ids, tenant):
            raise LoadShed("saturated", lane="test")

        leader = fleet.spawn("r0", "leader",
                             service_fn=shedding_service)
        router = fleet.router()
        before = counter_value("fleet_router_redispatch_total",
                               replica="r0")
        reply = router.request([1])
        assert reply["status"] == "shed"
        assert reply["error"] == "LoadShed"
        # a typed shed is final — no re-dispatch happened for it
        assert counter_value("fleet_router_redispatch_total",
                             replica="r0") == before

    def test_empty_fleet_is_typed_answer(self, fleet):
        router = fleet.router(route_retries=1)
        with pytest.raises(NoReplicaAvailable):
            router.request([1])
        assert counter_value("fleet_router_unroutable_total") >= 1

    def test_drain_stops_admission_then_deregisters(self, fleet):
        leader = fleet.spawn("r0", "leader")
        leader.manager.checkpoint(timeout=10)
        follower = fleet.spawn("r1", "follower")
        assert fleet.directory.get("r1") is not None
        follower.drain(timeout=5)
        assert follower.state == "draining"
        assert fleet.directory.get("r1") is None
        # direct dispatch to a draining replica is an honest refusal
        with socket.create_connection(("127.0.0.1", follower.port),
                                      timeout=5) as conn:
            conn.sendall(b'{"ids": [1]}\n')
            with conn.makefile("rb") as fh:
                reply = json.loads(fh.readline())
        assert reply["status"] == "unavailable"
        # the router no longer sees it
        router = fleet.router()
        for i in range(8):
            assert router.request([i])["replica"] == "r0"

    def test_chaos_point_route_fires_from_seeded_plan(self, fleet):
        leader = fleet.spawn("r0", "leader")
        router = fleet.router()
        assert router.request([1])["status"] == "ok"
        chaos.install(chaos.ChaosPlan(seed=7).fail(
            "fleet.route", exc=ChaosFault("fleet.route", 0), times=1))
        with pytest.raises(ChaosFault):
            router.request([2])
        # deterministic: the plan spent its single shot
        assert router.request([3])["status"] == "ok"


# ------------------------------------------------- failover harness
@pytest.mark.slow
class TestFleetChaosHarness:
    def test_smoke_report_contract(self):
        from benchmarks.fleet_chaos import check, run_fleet_chaos

        report = run_fleet_chaos(smoke=True, seed=0)
        # zero lost answers across all phases, kill -9 confirmed
        assert report["lost_answers"] == 0
        assert report["failover"]["kill_returncode"] == -9
        for phase in ("baseline", "burst", "cool"):
            p = report["phases"][phase]
            assert p["offered"] == p["ok"] + p["shed"] + p["error"] \
                + p["unroutable"]
            assert p["unanswered"] == 0
        # warm rejoin through the shared compilation cache, staleness
        # back under the configured bound
        assert report["rejoin"]["pcache_hits"] > 0
        assert report["rejoin"]["within_bound"] is True
        # the non-latency acceptance criteria all hold
        assert [f for f in check(report) if "p99" not in f] == []


# ------------------------------------------- fleet autonomy satellites
class TestMembershipAutonomy:
    def test_leader_epoch_wins_and_conflict_counted(self, tmp_path):
        """Split-brain window: a deposed leader's still-fresh record
        must lose to the successor's higher epoch, and the overlap must
        be observable."""
        d = MembershipDirectory(str(tmp_path), heartbeat_timeout_s=5.0)
        d.announce(ReplicaInfo("old", state="serving", role="leader",
                               epoch=3))
        d.announce(ReplicaInfo("new", state="serving", role="leader",
                               epoch=4))
        before = counter_value("fleet_leader_conflicts_total")
        leader = d.leader()
        assert leader.replica_id == "new"
        assert counter_value("fleet_leader_conflicts_total") == before + 1
        # single fresh leader: no conflict tick
        d.deregister("old")
        mid = counter_value("fleet_leader_conflicts_total")
        assert d.leader().replica_id == "new"
        assert counter_value("fleet_leader_conflicts_total") == mid

    def test_epoch_roundtrip_and_legacy_default(self):
        info = ReplicaInfo("r0", epoch=7)
        assert ReplicaInfo.from_dict(info.to_dict()).epoch == 7
        legacy = info.to_dict()
        legacy.pop("epoch")  # a record from a pre-election build
        assert ReplicaInfo.from_dict(legacy).epoch == -1

    def test_record_unlinked_between_listdir_and_open(self, tmp_path,
                                                      monkeypatch):
        """Satellite: a record deregistered between the directory scan's
        listdir and its open must be skipped and counted, never fatal."""
        d = MembershipDirectory(str(tmp_path), heartbeat_timeout_s=5.0)
        d.announce(ReplicaInfo("real", state="serving"))
        real_listdir = os.listdir

        def ghost_listdir(path):
            return list(real_listdir(path)) + ["replica-ghost.json"]

        monkeypatch.setattr(os, "listdir", ghost_listdir)
        before = counter_value("fleet_membership_parse_errors_total")
        out = d.replicas()
        assert [r.replica_id for r in out] == ["real"]
        assert counter_value(
            "fleet_membership_parse_errors_total") == before + 1


class TestFleetAutonomySatellites:
    def test_draining_healthz_is_503_with_state(self, fleet):
        """Satellite: /healthz during drain answers 503 with the
        draining state in the body, so load balancers depool while
        operators still see a live, finishing process."""
        import urllib.error

        leader = fleet.spawn("r0", "leader")
        srv = leader.expose_metrics()
        leader.drain(timeout=5)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5)
        assert exc.value.code == 503
        body = json.loads(exc.value.read())
        assert body["state"] == "draining"
        assert body["ready"] is False

    def test_simultaneous_join_and_drain_consistent(self, fleet):
        """Satellite: a join stretched across a concurrent drain leaves
        router eligibility and ring membership consistent — the joiner
        in, the drained member out, nothing half-present."""
        import threading

        leader = fleet.spawn("r0", "leader")
        leader.manager.checkpoint(timeout=10)
        f1 = fleet.spawn("r1", "follower")
        router = fleet.router()
        router.refresh(force=True)
        assert sorted(router.ring.members) == ["r0", "r1"]
        # stretch r2's join window across r1's drain
        chaos.install(chaos.ChaosPlan(seed=3).delay(
            "fleet.join", delay_s=0.3, times=1))
        joined = {}

        def join():
            joined["rep"] = fleet.spawn("r2", "follower")

        t = threading.Thread(target=join)
        t.start()
        f1.drain(timeout=5)
        t.join(timeout=30)
        assert "rep" in joined and joined["rep"].state == "serving"
        assert fleet.directory.get("r1") is None
        router.refresh(force=True)
        assert sorted(router.ring.members) == ["r0", "r2"]
        with router._lock:
            eligible = sorted(router._eligible)
        assert eligible == ["r0", "r2"]
        for i in range(8):
            assert router.request([i])["replica"] in ("r0", "r2")
