"""Mesh-native sharded serving suite (docs/SHARDING.md).

The tentpole contract under test: N virtual devices serve as ONE
logical replica — row-range-sharded feature store (halo exchange as a
``shard_map`` collective), frontier exchange reusing the overlay
sampler per shard, and the two pins that make it deployable:

  * **bit-identity** — the sharded sample→gather path produces exactly
    the bytes the single-device staged path produces, for every shard
    count in {1, 2, 4, 8};
  * **steady state builds nothing** — after warmup, serving a fixed
    frontier ladder traces zero new executables and restacks zero
    sharded views.

Plus the fleet face of the tier: shard-group membership/routing
(a group is routable only when complete and fully healthy; one dead
member makes the whole logical replica typed-unavailable, never a
partial answer) and per-shard WAL segments with a coherent group
manifest.  Also hosts the ported MULTICHIP dryrun assertions: 8-device
DP training over the row-sharded dist stack with zero overflow, and
all-to-all DistFeature exactness.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu import telemetry
from quiver_tpu.analysis.retrace_guard import count_jit_builds
from quiver_tpu.mesh import (DATA_AXIS, SHARD_AXIS, MeshFeature,
                             MeshSampler, build_mesh, match_partition_rules,
                             mesh_status, require_devices, shard_ranges)
from quiver_tpu.ops.sample import sample_neighbors_overlay
from quiver_tpu.resilience.breaker import reset as breakers_reset

pytestmark = pytest.mark.mesh

N, D = 1000, 16


def counter_value(name, **labels):
    from quiver_tpu.telemetry.registry import metric_key

    return telemetry.snapshot()["counters"].get(metric_key(name, labels), 0)


def gauge_value(name, **labels):
    from quiver_tpu.telemetry.registry import metric_key

    return telemetry.snapshot()["gauges"].get(metric_key(name, labels))


@pytest.fixture(autouse=True)
def _clean_breakers():
    yield
    breakers_reset()


@pytest.fixture
def table(rng):
    return rng.standard_normal((N, D)).astype(np.float32)


def _csr(rng, n=N, avg_deg=8):
    deg = rng.integers(1, avg_deg * 2, n)
    indptr = np.zeros(n + 1, np.int32)
    indptr[1:] = np.cumsum(deg)
    indices = rng.integers(0, n, indptr[-1]).astype(np.int32)
    return indptr, indices


# ------------------------------------------------------------ topology
class TestTopology:
    def test_shard_ranges_cover_exactly(self):
        rps, ranges = shard_ranges(10, 4)
        assert rps == 3
        assert ranges == [(0, 3), (3, 6), (6, 9), (9, 10)]
        # ownership is a shift: every id maps into its range
        for i in range(10):
            s = i // rps
            lo, hi = ranges[s]
            assert lo <= i < hi

    def test_require_devices_names_the_flag(self):
        with pytest.raises(RuntimeError, match="xla_force_host_platform"):
            require_devices(jax.device_count() + 1)

    def test_build_mesh_axes(self):
        mesh = build_mesh(4)
        assert mesh.axis_names == (DATA_AXIS, SHARD_AXIS)
        assert mesh.shape[SHARD_AXIS] == 4
        assert mesh.shape[DATA_AXIS] == 1

    def test_match_partition_rules(self):
        from jax.sharding import PartitionSpec as P

        tree = {"layers_0": {"kernel": np.zeros((2, 2)),
                             "bias": np.zeros(2)}}
        specs = match_partition_rules(
            [("kernel", P(SHARD_AXIS)), ("bias", P())], tree)
        assert specs["layers_0"]["kernel"] == P(SHARD_AXIS)
        assert specs["layers_0"]["bias"] == P()
        with pytest.raises(ValueError, match="no partition rule"):
            match_partition_rules([("kernel", P())], tree)

    def test_mesh_off_by_default(self):
        from quiver_tpu.config import get_config

        assert get_config().mesh_shards == 0
        with pytest.raises(ValueError, match="mesh_shards"):
            MeshFeature(np.zeros((4, 2), np.float32))


# ------------------------------------------------- sharded feature store
class TestMeshFeature:
    @pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
    def test_gather_bit_identical_to_staged(self, rng, table, n_shards):
        """The acceptance pin: sharded gather == single-device staged
        path, bitwise, for every rehearsal shard count."""
        from quiver_tpu.feature import Feature

        staged = Feature(device_cache_size=N, cache_unit="rows") \
            .from_cpu_tensor(table)
        mf = MeshFeature(table, n_shards=n_shards)
        for B in (1, 7, 64, 200):
            ids = rng.integers(0, N, B)
            want = np.asarray(staged[ids])
            got = np.asarray(mf[ids])
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got, table[ids])

    def test_gather_int_dtype_sentinel(self, rng):
        """Integer tables use iinfo.min as the pmax identity — exact."""
        t = rng.integers(-2**30, 2**30, (N, 4)).astype(np.int32)
        mf = MeshFeature(t, n_shards=4)
        ids = rng.integers(0, N, 50)
        np.testing.assert_array_equal(np.asarray(mf[ids]), t[ids])

    def test_steady_state_zero_restacks_zero_builds(self, rng, table):
        mf = MeshFeature(table, n_shards=4)
        streams = [rng.integers(0, N, 64) for _ in range(4)]
        for ids in streams:          # warm epoch: faults + builds happen
            mf[ids]
        restacks = mf.restacks
        with count_jit_builds() as c:
            for ids in streams * 2:  # steady state: same ladder again
                np.testing.assert_array_equal(np.asarray(mf[ids]),
                                              table[ids])
        assert c.builds == 0, c.describe()
        assert mf.restacks == restacks

    @pytest.mark.retrace_budget(2)
    def test_budget_marker_pins_warmed_gather(self, rng, table):
        """The marker counts the whole test: one gather collective +
        one page-fault scatter on first touch of the B=64 bucket, then
        NOTHING — repeated serving stays inside the budget."""
        mf = MeshFeature(table, n_shards=2)
        ids = rng.integers(0, N, 64)
        for _ in range(4):
            mf[ids]

    def test_overflow_falls_back_exact(self, rng, table):
        """A pool too small for the batch working set answers exactly
        from the host table and ticks the fallback counter."""
        mf = MeshFeature(table, n_shards=2, page_rows=8, pool_pages=1)
        before = counter_value("feature_page_fallback_total")
        ids = rng.integers(0, N, 128)
        np.testing.assert_array_equal(np.asarray(mf[ids]), table[ids])
        assert counter_value("feature_page_fallback_total") > before
        assert mf.fallbacks >= 1

    def test_warm_executables_idempotent(self, table):
        mf = MeshFeature(table, n_shards=2)
        built = mf.warm_executables()
        assert built > 0
        assert mf.warm_executables() == 0

    def test_halo_counters_move(self, rng, table):
        mf = MeshFeature(table, n_shards=4)
        ids = rng.integers(0, N, 32)
        sent0 = counter_value("mesh_halo_bytes_total", direction="send")
        mf[ids]
        sent1 = counter_value("mesh_halo_bytes_total", direction="send")
        assert sent1 - sent0 == 32 * D * 4 * 3  # B rows to (n-1) shards
        assert counter_value("mesh_halo_bytes_total",
                             direction="recv") > 0


# ------------------------------------------------- frontier exchange
class TestMeshSampler:
    @pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
    def test_sample_bit_identical(self, rng, n_shards):
        indptr, indices = _csr(rng)
        ms = MeshSampler(indptr, indices, n_shards=n_shards)
        tomb = jnp.zeros(len(indices), jnp.int32)
        for trial in range(3):
            seeds = rng.integers(0, N, 32)
            key = jax.random.PRNGKey(trial)
            got = ms.sample(seeds, 8, key)
            ref = sample_neighbors_overlay(
                jnp.asarray(indptr), jnp.asarray(indices), tomb,
                jnp.zeros(N + 1, jnp.int32), jnp.zeros(8, jnp.int32),
                jnp.asarray(seeds, jnp.int32), 8, key,
                gather_mode=ms.gather_mode, sample_rng=ms.sample_rng)
            for f in ("nbrs", "mask", "counts", "eid"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, f)),
                    np.asarray(getattr(ref, f)), err_msg=f)

    def test_frontier_gauge_tracks_ownership(self, rng):
        indptr, indices = _csr(rng)
        ms = MeshSampler(indptr, indices, n_shards=4)
        seeds = np.arange(ms.rows_per_shard // 2)  # all owned by shard 0
        ms.sample(seeds, 4, jax.random.PRNGKey(0))
        assert gauge_value("mesh_shard_frontier_rows",
                           shard="0") == len(seeds)
        assert gauge_value("mesh_shard_frontier_rows", shard="1") == 0

    def test_sample_then_gather_pipeline_bit_identical(self, rng, table):
        """The full sharded serving hop: frontier sample + neighbour
        feature gather — bitwise equal to the unsharded pipeline."""
        indptr, indices = _csr(rng)
        ms = MeshSampler(indptr, indices, n_shards=4)
        mf = MeshFeature(table, n_shards=4)
        seeds = rng.integers(0, N, 16)
        key = jax.random.PRNGKey(11)
        out = ms.sample(seeds, 8, key)
        nbrs = np.asarray(out.nbrs)
        mask = np.asarray(out.mask)
        flat = np.where(mask, nbrs, 0).reshape(-1)
        got = np.asarray(mf[flat])
        ref = sample_neighbors_overlay(
            jnp.asarray(indptr), jnp.asarray(indices),
            jnp.zeros(len(indices), jnp.int32),
            jnp.zeros(N + 1, jnp.int32), jnp.zeros(8, jnp.int32),
            jnp.asarray(seeds, jnp.int32), 8, key,
            gather_mode=ms.gather_mode, sample_rng=ms.sample_rng)
        ref_flat = np.where(np.asarray(ref.mask),
                            np.asarray(ref.nbrs), 0).reshape(-1)
        np.testing.assert_array_equal(flat, ref_flat)
        np.testing.assert_array_equal(got, table[ref_flat])

    def test_every_shard_lives_on_its_own_device(self, rng, table):
        """Shard s's CSR and frame pool are committed to device s — not
        parked on device 0 and resharded — and the mesh-wide view is
        assembled from the pools where they are."""
        indptr, indices = _csr(rng)
        ms = MeshSampler(indptr, indices, n_shards=4)
        mf = MeshFeature(table, n_shards=4)
        mf[rng.integers(0, N, 32)]                   # stacks the view
        want = [str(d) for d in ms.mesh.devices.reshape(-1)]
        assert len(set(want)) == 4
        assert [p["device"] for p in ms.stats()["placement"]] == want
        assert [s["device"] for s in mf.stats()["shards"]] == want
        assert [str(s.device) for s in
                mf._frames_g.addressable_shards] == want
        # after a fault the pool is still where it was
        assert [str(next(iter(s.frames.devices())))
                for s in mf._stores] == want

    def test_steady_state_sampler_builds_nothing(self, rng):
        indptr, indices = _csr(rng)
        ms = MeshSampler(indptr, indices, n_shards=4)
        key = jax.random.PRNGKey(0)
        ms.sample(rng.integers(0, N, 32), 8, key)   # warm (B=32, k=8)
        execs = ms.stats()["executables"]
        with count_jit_builds() as c:
            for trial in range(4):
                ms.sample(rng.integers(0, N, 32), 8,
                          jax.random.PRNGKey(trial))
        assert c.builds == 0, c.describe()
        assert ms.stats()["executables"] == execs


# ---------------------------------------- MULTICHIP dryrun assertions
class TestMultichipDryrun:
    """Ported from the driver's MULTICHIP dryrun: the 8-device DP dist
    stack stays exact and overflow-free at dryrun scale."""

    def test_dp_training_8dev_zero_overflow(self):
        from quiver_tpu.dist.e2e import run_dist_training

        out = run_dist_training(n_devices=8, n_nodes=512, avg_deg=8,
                                feat_dim=8, batch_per_dev=8,
                                sizes=[4, 3], steps=2, seed=0)
        assert all(np.isfinite(l) for l in out["losses"])
        assert out["sampler_overflow"].sum() == 0
        assert out["feature_overflow"] == 0

    def test_dist_feature_all_to_all_exact(self, rng):
        from quiver_tpu.dist import DistFeature, PartitionInfo
        from quiver_tpu.utils.mesh import make_mesh

        nhosts = 8
        mesh = make_mesh(("data",), devices=jax.devices()[:nhosts])
        full = rng.normal(size=(256, 8)).astype(np.float32)
        g2h = rng.integers(0, nhosts, 256).astype(np.int32)
        info = PartitionInfo(host=0, hosts=nhosts, global2host=g2h)
        df = DistFeature.from_global_feature(full, mesh, info)
        ids = rng.integers(0, 256, (nhosts, 32)).astype(np.int32)
        out = np.asarray(df.lookup(ids))
        for h in range(nhosts):
            np.testing.assert_allclose(out[h], full[ids[h]], rtol=1e-6)


# --------------------------------------------------- subprocess rehearsal
class TestSubprocessRehearsal:
    def test_mesh_in_isolated_device_count(self, devices_subprocess):
        """The conftest helper boots a child with its OWN virtual device
        count — here a 2-device mesh gathers exactly in a process whose
        device count differs from the suite's 8."""
        code = """
import numpy as np
from quiver_tpu.mesh import MeshFeature
t = np.arange(40, dtype=np.float32).reshape(10, 4)
mf = MeshFeature(t, n_shards=2)
ids = np.array([0, 3, 5, 9, 9, 1])
assert (np.asarray(mf[ids]) == t[ids]).all()
print("MESH_CHILD_OK", mf.n_shards)
"""
        res = devices_subprocess(code, n_devices=2)
        assert res.returncode == 0, res.stderr
        assert "MESH_CHILD_OK 2" in res.stdout


# ------------------------------------------------------ shard groups
class TestShardGroups:
    def _info(self, rid, gid=None, idx=0, count=0, state="serving"):
        import time as _t

        from quiver_tpu.fleet.membership import ReplicaInfo

        detail = {}
        if gid is not None:
            detail = {"shard_group": gid, "shard_index": idx,
                      "shard_count": count}
        return ReplicaInfo(replica_id=rid, state=state,
                           heartbeat=_t.time(), detail=detail)

    def test_grouping_and_completeness(self):
        from quiver_tpu.fleet.membership import (group_complete,
                                                 shard_groups)

        infos = [self._info("b", "g1", 1, 2), self._info("a", "g1", 0, 2),
                 self._info("solo")]
        groups = shard_groups(infos)
        assert list(groups) == ["g1"]
        assert [m.replica_id for m in groups["g1"]] == ["a", "b"]
        assert group_complete(groups["g1"])
        # half-booted, duplicated, or disagreeing groups never route
        assert not group_complete([self._info("a", "g1", 0, 2)])
        assert not group_complete([self._info("a", "g1", 0, 2),
                                   self._info("b", "g1", 0, 2)])
        assert not group_complete([self._info("a", "g1", 0, 2),
                                   self._info("b", "g1", 1, 3)])
        assert not group_complete([])

    def test_router_routes_complete_group_as_unit(self, tmp_path):
        from quiver_tpu.fleet import FleetRouter, MembershipDirectory

        d = MembershipDirectory(str(tmp_path), heartbeat_timeout_s=30.0)
        d.announce(self._info("s0", "g1", 0, 2))
        d.announce(self._info("s1", "g1", 1, 2))
        d.announce(self._info("solo"))
        router = FleetRouter(d, scan_ttl_s=0.0)
        try:
            router.refresh(force=True)
            assert sorted(router.ring.members) == ["group:g1", "solo"]
            assert gauge_value("fleet_shard_group_members",
                               group="g1") == 2
            st = router.status()
            assert st["shard_groups"] == {"g1": ["s0", "s1"]}
        finally:
            router.close()

    def test_incomplete_group_takes_no_traffic(self, tmp_path):
        from quiver_tpu.fleet import FleetRouter, MembershipDirectory
        from quiver_tpu.resilience.errors import NoReplicaAvailable

        d = MembershipDirectory(str(tmp_path), heartbeat_timeout_s=30.0)
        d.announce(self._info("s0", "g1", 0, 2))  # shard 1 never joined
        router = FleetRouter(d, scan_ttl_s=0.0, route_retries=1)
        try:
            router.refresh(force=True)
            assert router.ring.members == ()
            with pytest.raises(NoReplicaAvailable):
                router.request([1], sleep=lambda _s: None)
        finally:
            router.close()

    def test_unhealthy_member_removes_whole_group(self, tmp_path):
        from quiver_tpu.fleet import FleetRouter, MembershipDirectory

        d = MembershipDirectory(str(tmp_path), heartbeat_timeout_s=30.0)
        d.announce(self._info("s0", "g1", 0, 2))
        d.announce(self._info("s1", "g1", 1, 2))
        router = FleetRouter(d, scan_ttl_s=0.0)
        try:
            router.refresh(force=True)
            assert "group:g1" in router.ring.members
            with router._lock:
                router._health_ok["s1"] = False   # non-coordinator dies
            router.refresh(force=True)
            assert router.ring.members == ()
        finally:
            router.close()


# --------------------------------------- shard group end-to-end serving
class TestShardGroupServing:
    def _spawn_member(self, tmp_path, members, rid, idx, service_fn):
        from quiver_tpu.fleet import FleetReplica
        from quiver_tpu.stream import StreamingGraph
        from quiver_tpu.utils.topology import CSRTopo

        def _graph():
            src = np.arange(8, dtype=np.int64)
            return CSRTopo(edge_index=np.stack([src, (src + 1) % 8]))

        rep = FleetReplica(
            rid, fleet_dir=str(tmp_path / "fleet"),
            root=str(tmp_path / f"dur-{rid}"),
            graph_factory=lambda: StreamingGraph(_graph(),
                                                 delta_capacity=64),
            role="leader", heartbeat_s=0.1, service_fn=service_fn,
            shard_group="g1", shard_index=idx, shard_count=2).boot()
        members.append(rep)
        return rep

    def test_group_failover_typed_unavailable(self, tmp_path):
        """The acceptance scenario: a 2-member shard group serves as
        one unit; one member dying yields a typed NoReplicaAvailable —
        answered (with an error), never dropped, never partial."""
        from quiver_tpu.fleet import FleetRouter, MembershipDirectory
        from quiver_tpu.resilience.errors import NoReplicaAvailable

        members = []
        directory = MembershipDirectory(str(tmp_path / "fleet"),
                                        heartbeat_timeout_s=5.0)
        router = None
        try:
            s0 = self._spawn_member(
                tmp_path, members, "s0", 0,
                lambda ids, tenant: {"answered_by": "s0",
                                     "n": len(ids)})
            self._spawn_member(
                tmp_path, members, "s1", 1,
                lambda ids, tenant: {"answered_by": "s1",
                                     "n": len(ids)})
            router = FleetRouter(directory, scan_ttl_s=0.0,
                                 request_timeout_s=2.0, route_retries=1)
            router.refresh(force=True)
            assert router.ring.members == ("group:g1",)
            # requests land on the shard-0 coordinator of the group
            reply = router.request([1, 2, 3])
            assert reply["status"] == "ok"
            assert reply["replica"] == "s0"
            assert reply["answered_by"] == "s0"
            assert counter_value("fleet_router_requests_total",
                                 replica="group:g1", status="ok") >= 1
            # one member dies -> the group leaves the ring -> typed
            # unavailable for every caller; no request is silently lost
            members[1].stop()
            router.refresh(force=True)
            assert router.ring.members == ()
            with pytest.raises(NoReplicaAvailable):
                router.request([1], sleep=lambda _s: None)
            # the surviving member alone must NOT serve group traffic
            assert directory.get("s0") is not None
            assert s0.state == "serving"
        finally:
            if router is not None:
                router.close()
            for rep in reversed(members):
                rep.stop()

    def test_member_announces_shard_detail(self, tmp_path):
        from quiver_tpu.fleet import FleetReplica

        os.makedirs(tmp_path / "fleet", exist_ok=True)
        rep = FleetReplica("m0", fleet_dir=str(tmp_path / "fleet"),
                           root=str(tmp_path / "dur"),
                           shard_group="g7", shard_index=1,
                           shard_count=4)
        info = rep._info()
        assert info.shard_group == "g7"
        assert info.shard_index == 1
        assert info.shard_count == 4
        # unsharded replicas carry none of the keys (pre-mesh records)
        plain = FleetReplica("m1", fleet_dir=str(tmp_path / "fleet"),
                             root=str(tmp_path / "dur"))
        assert plain._info().shard_group is None
        assert "shard_index" not in plain._info().detail


# ------------------------------------------------- per-shard WAL + manifest
class TestShardGroupWAL:
    def test_coherent_replay_stops_at_manifest(self, tmp_path):
        from quiver_tpu.recovery.shardwal import ShardGroupWAL

        w = ShardGroupWAL(str(tmp_path), n_shards=2, group="g1",
                          fsync="off")
        for i in range(4):
            w.append(0, f"a{i}".encode())
        w.append(1, b"b0")
        m = w.publish_manifest()
        assert m.lsns == [3, 0]
        # writes AFTER the group commit point are the un-acked tail
        w.append(0, b"a4")
        w.append(1, b"b1")
        got0 = [p for _lsn, p in w.replay(0)]
        got1 = [p for _lsn, p in w.replay(1)]
        assert got0 == [b"a0", b"a1", b"a2", b"a3"]
        assert got1 == [b"b0"]
        assert w.tail_lsns() == [1, 1]
        w.close()

    def test_no_manifest_replays_nothing(self, tmp_path):
        from quiver_tpu.recovery.shardwal import ShardGroupWAL

        w = ShardGroupWAL(str(tmp_path), n_shards=2, fsync="off")
        w.append(0, b"x")
        assert list(w.replay(0)) == []
        w.close()

    def test_manifest_survives_reopen_and_versions(self, tmp_path):
        from quiver_tpu.recovery.shardwal import (ShardGroupWAL,
                                                  load_manifest)

        w = ShardGroupWAL(str(tmp_path), n_shards=2, fsync="off")
        w.append(0, b"x")
        v1 = w.publish_manifest().version
        w.append(1, b"y")
        v2 = w.publish_manifest().version
        assert v2 == v1 + 1
        w.close()
        # a fresh process resumes versioning past what is on disk
        w2 = ShardGroupWAL(str(tmp_path), n_shards=2, fsync="off")
        assert load_manifest(str(tmp_path)).version == v2
        assert w2.publish_manifest().version == v2 + 1
        got = [p for _lsn, p in w2.replay(1)]
        assert got == [b"y"]
        w2.close()

    def test_garbage_manifest_is_loud(self, tmp_path):
        from quiver_tpu.recovery.errors import RecoveryError
        from quiver_tpu.recovery.shardwal import load_manifest

        path = tmp_path / "group-manifest.json"
        path.write_bytes(b"{torn")
        with pytest.raises(RecoveryError, match="manifest"):
            load_manifest(str(tmp_path))

    def test_truncate_through_manifest(self, tmp_path):
        from quiver_tpu.recovery.shardwal import ShardGroupWAL

        w = ShardGroupWAL(str(tmp_path), n_shards=1, fsync="off",
                          segment_bytes=64)
        for i in range(40):
            w.append(0, b"payload-%d" % i)
        w.publish_manifest()
        assert w.truncate_through_manifest() > 0
        # everything the manifest vouches for past the cut is intact
        lsns = [lsn for lsn, _p in w.replay(0)]
        assert lsns == sorted(lsns)
        assert lsns[-1] == 39
        w.close()


# --------------------------------------------------------- observability
class TestMeshObservability:
    def test_mesh_status_active_document(self, table):
        mf = MeshFeature(table, n_shards=2)
        doc = mesh_status()
        assert doc["active"] is True
        assert doc["n_shards"] == 2
        assert doc["feature"]["rows_per_shard"] == mf.rows_per_shard

    def test_debug_mesh_endpoint(self, table):
        from quiver_tpu.telemetry.export import MetricsServer

        # hold a strong ref: the /debug/mesh registry is a weakref and
        # the instance's internal cycle frees on an arbitrary gc tick
        mf = MeshFeature(table, n_shards=2)
        srv = MetricsServer()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/debug/mesh",
                    timeout=10) as resp:
                doc = json.loads(resp.read())
            assert doc["active"] is True
            assert doc["n_shards"] == 2
            assert doc["feature"]["rows_per_shard"] == mf.rows_per_shard
        finally:
            srv.close()

    def test_gather_seconds_histogram_observes(self, rng, table):
        from quiver_tpu.telemetry.registry import metric_key

        mf = MeshFeature(table, n_shards=2)
        mf[rng.integers(0, N, 16)]
        hists = telemetry.snapshot()["histograms"]
        key = metric_key("mesh_shard_gather_seconds", {})
        assert sum(hists[key]["counts"]) >= 1
