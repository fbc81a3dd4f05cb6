"""Paged feature store + ragged page-gather kernel suite (``make paged``).

Correctness bar (docs/FEATURE_CACHE.md): a Feature with the paged store
enabled must return rows BIT-IDENTICAL to the staged three-tier merge
under every residency mix — hot-only, overlay hits, host faults, mixed
traffic, pool overflow fallback, ``feature_order`` translation — while
the executable count collapses from the staged ``(B, bucket)`` grid to
at most two programs per batch size (the ragged gather plus the
page-fault scatter), and page residency survives a checkpoint/restore
cycle including a kill -9 (the ``make crash`` variant).

``feature_paged=off`` (the default) must be a byte-identical no-op:
no ``feature_page_*`` metric keys, no ``("paged", ...)`` executable
keys — PR 9 behavior untouched.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import quiver_tpu.config as config_mod
from quiver_tpu import Feature, telemetry
from quiver_tpu.analysis.retrace_guard import count_jit_builds
from quiver_tpu.ops.paged import (DEVICE, HOST, OVERLAY, PageTable,
                                  _plan_geometry, default_page_rows)
from quiver_tpu.ops.pallas.page_gather_kernel import page_gather

pytestmark = pytest.mark.paged

REPO = Path(__file__).resolve().parents[1]

# one geometry shared by the feature-level suites: 512 rows, 128 hot,
# page_rows=8 -> 16 hot pages + 48 host pages
N, D, HOT, R = 512, 16, 128, 8
N_HOST_PAGES = (N - HOT) // R


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0.0)


def _feats(rng, n=N, d=D):
    return rng.standard_normal((n, d)).astype(np.float32)


def _paged_feature(feats, hot_rows=HOT, **kw):
    f = Feature(device_cache_size=hot_rows,
                cache_unit="rows").from_cpu_tensor(feats)
    kw.setdefault("page_rows", R)
    f.enable_paging(**kw)
    return f


def _cold_ids(rng, size, lo=HOT, hi=N):
    return rng.integers(lo, hi, size=size).astype(np.int64)


# ------------------------------------------------------------- geometry
class TestGeometry:
    def test_transaction_multiple_and_floor(self):
        for row_bytes in (4, 12, 64, 128, 512, 640):
            r = default_page_rows(row_bytes)
            assert (r * row_bytes) % 512 == 0, row_bytes
            assert r * row_bytes >= 4096, row_bytes

    def test_odd_row_width_still_aligns(self):
        # odd byte widths force r up to a multiple of 512 rows — the
        # page stays whole-transaction even for awkward dims
        r = default_page_rows(7)
        assert (r * 7) % 512 == 0 and r * 7 >= 4096

    def test_target_override(self):
        assert default_page_rows(128, target_bytes=512) == 4

    def test_block_plan_is_lane_friendly_and_bounded(self):
        for page_rows, dim in ((8, 16), (32, 128), (256, 1024)):
            block, ppb = _plan_geometry(page_rows, dim, 4)
            assert block % 8 == 0 and 8 <= block <= 128
            assert ppb == block  # worst case: every row its own page


# ------------------------------------------------------------ page table
class TestPageTable:
    def test_partition_and_initial_states(self):
        t = PageTable(n_rows=100, cache_count=20, page_rows=8,
                      pool_pages=4)
        assert t.n_pages == 13 and t.hot_pages == 3
        assert t.n_host_pages == 10 and t.pool_pages == 4
        assert t.n_frames == 7
        assert all(t.state_of(p) == DEVICE for p in range(3))
        assert all(t.state_of(p) == HOST for p in range(3, 13))
        assert t.resident_pages() == 3  # hot pages are pinned resident

    def test_fault_and_invalidate_transitions(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=8)
        t = f.paged.table
        page = (HOT // R) + 2                 # a host-space page
        nid = np.array([page * R + 3], dtype=np.int64)
        assert t.state_of(page) == HOST
        f[nid]                                 # gather faults it in
        assert t.state_of(page) == OVERLAY
        f.invalidate_rows(nid)                 # stream mutation drops it
        assert t.state_of(page) == HOST

    def test_pool_clamped_to_host_pages(self, rng):
        f = _paged_feature(_feats(rng), pool_pages=10_000)
        assert f.paged.table.pool_pages == N_HOST_PAGES


# ------------------------------------------------------------ raw kernel
class TestKernel:
    def test_hand_built_plan_matches_reference(self):
        """Drive ``page_gather`` directly with a hand-built ragged plan
        (two blocks, different distinct-page counts, padded tail)."""
        rng = np.random.default_rng(7)
        F, pr, d, block, ppb = 5, 4, 8, 8, 8
        frames = rng.standard_normal((F, pr, d)).astype(np.float32)
        nb, M, B = 2, 16, 13           # 3 padded rows in block 1
        blk_np = np.array([3, 2], dtype=np.int32)
        blk_pages = np.zeros(nb * ppb, dtype=np.int32)
        blk_pages[0:3] = [0, 2, 4]
        blk_pages[ppb:ppb + 2] = [1, 3]
        row_lp = np.zeros(M, dtype=np.int32)
        row_off = np.zeros(M, dtype=np.int32)
        for i in range(B):
            b = i // block
            row_lp[i] = rng.integers(0, blk_np[b])
            row_off[i] = rng.integers(0, pr)
        out = np.asarray(page_gather(
            jnp.asarray(frames), jnp.asarray(blk_pages),
            jnp.asarray(blk_np), jnp.asarray(row_lp),
            jnp.asarray(row_off), page_rows=pr, block=block, ppb=ppb,
            interpret=True))
        assert out.shape == (M, d)
        for i in range(M):
            src = blk_pages[(i // block) * ppb + row_lp[i]]
            np.testing.assert_array_equal(out[i], frames[src, row_off[i]])


# -------------------------------------------------- bit-identical mixes
class TestMosaicEnvelope:
    """Interpret mode takes any width; Mosaic does not.  On a TPU the
    shapes the chip's compiler refused (tests/test_aot_compile.py) are
    refused by name, not run through a staged fallback."""

    @pytest.mark.parametrize("dim", [100, 602])
    def test_enable_paging_on_a_tpu_names_the_lane_constraint(
            self, rng, monkeypatch, dim):
        from quiver_tpu.ops.pallas import KernelConstraintError

        feat = rng.normal(size=(64, dim)).astype(np.float32)
        f = Feature(device_cache_size=16,
                    cache_unit="rows").from_cpu_tensor(feat)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(KernelConstraintError, match="128 lanes"):
            f.enable_paging()
        assert f.paged is None

    def test_kernel_on_a_tpu_names_the_smem_constraint(self):
        from quiver_tpu.ops.pallas import KernelConstraintError

        m = 1 << 20
        with pytest.raises(KernelConstraintError, match="SMEM"):
            jax.eval_shape(
                lambda *a: page_gather(*a, page_rows=8, block=128,
                                       ppb=128),
                jax.ShapeDtypeStruct((64, 8, 128), jnp.float32),
                jax.ShapeDtypeStruct((m,), jnp.int32),
                jax.ShapeDtypeStruct((m // 128,), jnp.int32),
                jax.ShapeDtypeStruct((m,), jnp.int32),
                jax.ShapeDtypeStruct((m,), jnp.int32))

    def test_interpret_mode_is_not_checked(self, rng):
        """CPU tests keep running the kernel at D=100."""
        feat = rng.normal(size=(64, 100)).astype(np.float32)
        f = Feature(device_cache_size=16,
                    cache_unit="rows").from_cpu_tensor(feat)
        f.enable_paging()
        ids = rng.integers(0, 64, 32)
        np.testing.assert_array_equal(np.asarray(f[ids]), feat[ids])


class TestPagedEquivalence:
    """Seeded property suite: every residency mix must come back equal
    to the source tensor bit for bit (float32 rows pass through gathers
    and scatters untouched — any mismatch is a planner/kernel bug)."""

    def test_hot_only(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=16)
        for _ in range(4):
            ids = rng.integers(0, HOT, size=64).astype(np.int64)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        assert f.paged.table.cache.resident == 0  # never touched host

    def test_overlay_hits_serve_without_refaulting(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=16)
        ids = _cold_ids(rng, 64, hi=HOT + 16 * R)  # <= 16 distinct pages
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        faults = _counter("feature_page_faults_total")
        hits = _counter("feature_page_hits_total")
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        assert _counter("feature_page_faults_total") == faults
        assert _counter("feature_page_hits_total") > hits

    def test_host_faults_fresh_pages_every_batch(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=N_HOST_PAGES)
        for i in range(6):                    # disjoint 8-page windows
            lo = HOT + i * 8 * R
            ids = rng.integers(lo, lo + 8 * R, size=48).astype(np.int64)
            faults = _counter("feature_page_faults_total")
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
            assert _counter("feature_page_faults_total") > faults

    def test_mixed_traffic_vs_staged_reference(self, rng):
        """The headline property: paged vs the PR-9 staged overlay on
        the SAME stream, compared row for row."""
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=N_HOST_PAGES)
        ref = Feature(device_cache_size=HOT,
                      cache_unit="rows").from_cpu_tensor(feats)
        ref.enable_cold_cache(rows=64, admit_threshold=2)
        for i in range(30):
            B = int(rng.integers(1, 128))
            ids = rng.integers(0, N, size=B).astype(np.int64)
            if i % 3 == 0:                    # duplicates in one batch
                ids[: B // 2 + 1] = ids[0]
            got = np.asarray(f[ids])
            np.testing.assert_array_equal(got, np.asarray(ref[ids]))
            np.testing.assert_array_equal(got, feats[ids])

    def test_boundary_page_straddles_hot_edge(self, rng):
        """cache_count not a page multiple: the boundary DEVICE page is
        padded with REAL host rows, so ids just past the hot edge are
        served from the pinned page, not zeros."""
        feats = _feats(rng)
        f = _paged_feature(feats, hot_rows=HOT + 2, pool_pages=16)
        assert f.cache_count % R != 0          # genuinely straddles
        ids = np.arange(f.cache_count - 4, f.cache_count + 8,
                        dtype=np.int64)
        faults = _counter("feature_page_faults_total")
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        # rows in the boundary page's tail came from DEVICE, only the
        # ids past the page boundary faulted
        assert _counter("feature_page_faults_total") <= faults + 1

    def test_feature_order_translation(self, rng):
        prob = rng.random(N)
        feats = _feats(rng)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats, prob=prob)
        f.enable_paging(page_rows=R, pool_pages=N_HOST_PAGES)
        for _ in range(5):
            ids = rng.integers(0, N, size=64).astype(np.int64)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])

    def test_pool_overflow_falls_back_bit_identical(self, rng):
        """A batch whose page working set exceeds the OVERLAY pool must
        fall back to the staged merge — correct, counted, never wrong."""
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=8)
        ids = (HOT + rng.choice(N - HOT, size=96,
                                replace=False)).astype(np.int64)
        before = _counter("feature_page_fallback_total")
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        assert f.paged.fallbacks > 0
        assert _counter("feature_page_fallback_total") > before

    def test_tail_partial_page(self, rng):
        """N not a page multiple: the last HOST page is short; gathering
        its rows must not read past the host tail."""
        feats = _feats(rng, n=N + 3)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats)
        f.enable_paging(page_rows=R, pool_pages=16)
        ids = np.arange(N - 2, N + 3, dtype=np.int64)  # spans the tail
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])


# ------------------------------------------------- bucket-edge sentinel
class TestBucketEdgeRegression:
    """Satellite: the staged path's padding sentinel.  When the cold
    count lands EXACTLY on a pow2/quarter-octave bucket edge, padded
    lanes must stay out of range of both the staging buffer and the
    output scatter (``_stage``/``_stage_overlay`` carry bounds
    assertions; these streams would trip them if the sentinel ever
    regressed)."""

    EDGES = (15, 16, 17, 31, 32, 33, 63, 64)

    def test_staged_cold_count_on_bucket_edges(self, rng):
        feats = _feats(rng)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats)
        for n_cold in self.EDGES:
            n_hot = max(0, 64 - n_cold)
            ids = np.concatenate([
                rng.integers(0, HOT, size=n_hot),
                HOT + rng.choice(N - HOT, size=n_cold, replace=False),
            ]).astype(np.int64)
            rng.shuffle(ids)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])

    def test_whole_batch_cold_equals_bucket(self, rng):
        # B == n_cold == bucket: zero pad lanes, sentinel never built
        feats = _feats(rng)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats)
        ids = (HOT + rng.choice(N - HOT, size=64,
                                replace=False)).astype(np.int64)
        np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])

    def test_overlay_hit_and_fresh_counts_on_edges(self, rng):
        feats = _feats(rng)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats)
        f.enable_cold_cache(rows=64, admit_threshold=1)
        warm = (HOT + np.arange(32)).astype(np.int64)
        f[warm]                                # admitted on first touch
        for n_hit, n_fresh in ((16, 16), (32, 17), (31, 32), (16, 0)):
            ids = np.concatenate([
                warm[:n_hit],
                HOT + 200 + rng.choice(100, size=n_fresh, replace=False),
            ]).astype(np.int64)
            rng.shuffle(ids)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])


# --------------------------------------------------------- retrace guard
@pytest.fixture
def warmed_paged(rng):
    """A paged feature pre-warmed over a fixed batch list (two epochs:
    every page the stream touches is resident, every executable built)
    — fixture setup runs OUTSIDE the retrace counting window."""
    feats = _feats(rng, n=1024)
    f = Feature(device_cache_size=256,
                cache_unit="rows").from_cpu_tensor(feats)
    f.enable_paging(page_rows=R, pool_pages=(1024 - 256) // R)
    batches = [rng.integers(0, 1024, size=64).astype(np.int64)
               for _ in range(6)]
    for _ in range(2):
        for ids in batches:
            f[ids]
    return f, feats, batches


class TestRetraceBudget:
    def test_steady_state_builds_zero_programs(self, warmed_paged):
        f, feats, batches = warmed_paged
        keys_before = set(f._merge_cache)
        with count_jit_builds() as c:
            for ids in batches:
                np.testing.assert_array_equal(np.asarray(f[ids]),
                                              feats[ids])
        assert c.builds == 0, c.describe()
        assert set(f._merge_cache) == keys_before
        # ONE ragged gather program serves every residency mix at B=64
        assert [k for k in f._merge_cache if k[0] == "paged"] \
            == [("paged", 64)]

    @pytest.mark.retrace_budget(2)
    def test_budget_marker_enforces_steady_state(self, warmed_paged):
        f, _feats_, batches = warmed_paged
        for ids in batches:
            f[ids]

    def test_fewer_executables_than_staged_grid(self, rng):
        """The tentpole's executable-count claim: the staged path keys
        programs on (B, pow2 cold bucket) — a fixed-B stream with
        drifting cold fractions builds one per bucket.  The paged path
        builds ONE gather program for all of them."""
        feats = _feats(rng, n=1024)
        f = Feature(device_cache_size=256,
                    cache_unit="rows").from_cpu_tensor(feats)
        f.enable_paging(page_rows=R, pool_pages=(1024 - 256) // R)
        ref = Feature(device_cache_size=256,
                      cache_unit="rows").from_cpu_tensor(feats)
        for n_cold in (3, 9, 17, 33, 48):      # buckets 16, 32, 64
            ids = np.concatenate([
                rng.integers(0, 256, size=64 - n_cold),
                rng.integers(256, 1024, size=n_cold),
            ]).astype(np.int64)
            np.testing.assert_array_equal(np.asarray(f[ids]),
                                          np.asarray(ref[ids]))
        paged_gathers = [k for k in f._merge_cache if k[0] == "paged"]
        staged_merges = [k for k in ref._merge_cache
                         if isinstance(k[0], int)]
        assert len(paged_gathers) == 1
        assert len(staged_merges) >= 3


# --------------------------------------------------------- off identity
class TestPagedOffIdentity:
    def test_off_is_byte_identical_to_pr9(self, rng):
        """feature_paged=off (default): no paged store, no
        feature_page_* metric keys, no paged executable keys — the
        staged path untouched."""
        telemetry.reset()
        feats = _feats(rng)
        f = Feature(device_cache_size=HOT,
                    cache_unit="rows").from_cpu_tensor(feats)
        f.enable_cold_cache(rows=64, admit_threshold=1)
        assert f.paged is None
        for _ in range(5):
            ids = rng.integers(0, N, size=64).astype(np.int64)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        snap = telemetry.snapshot()
        keys = list(snap.get("counters", {})) + list(snap.get("gauges", {}))
        assert not any(k.startswith("feature_page_") for k in keys), keys
        assert all(k[0] not in ("paged", "pgfault")
                   for k in f._merge_cache)

    def test_config_on_auto_enables(self, rng):
        cfg = config_mod.get_config()
        saved = {k: getattr(cfg, k) for k in
                 ("feature_paged", "feature_page_rows",
                  "feature_page_pool")}
        config_mod.update(feature_paged="on", feature_page_rows=R,
                          feature_page_pool=16)
        try:
            feats = _feats(rng)
            f = Feature(device_cache_size=HOT,
                        cache_unit="rows").from_cpu_tensor(feats)
            assert f.paged is not None
            assert f.paged.table.page_rows == R
            assert f.paged.table.pool_pages == 16
            ids = rng.integers(0, N, size=64).astype(np.int64)
            np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
        finally:
            config_mod.update(**saved)


# ------------------------------------------------------------- recovery
def _graph_factory():
    from quiver_tpu.stream import StreamingGraph
    from quiver_tpu.utils.topology import CSRTopo

    src = np.arange(64, dtype=np.int64)
    dst = (src + 1) % 64
    return StreamingGraph(CSRTopo(edge_index=np.stack([src, dst])),
                          delta_capacity=512)


@pytest.fixture
def _clean_recovery():
    cfg = config_mod.get_config()
    saved = {k: getattr(cfg, k) for k in
             ("recovery_dir", "recovery_cache_dir",
              "recovery_retrace_budget")}
    yield
    from quiver_tpu.recovery.manager import set_active
    from quiver_tpu.recovery.registry import get_program_registry

    get_program_registry().unseal()
    set_active(None)
    config_mod.update(**saved)


class TestPagedRecovery:
    def _warm(self, rng, f):
        # confined to a 16-page window so the working set fits the pool
        ids = (HOT + rng.choice(16 * R, size=64,
                                replace=False)).astype(np.int64)
        f[ids]
        return ids

    def test_export_restore_round_trip(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=16)
        ids = self._warm(rng, f)
        assert f.paged.table.cache.resident > 0
        state = f.export_coldcache_state()
        assert state is not None and state["kind"] == "paged"
        assert state["page_rows"] == R

        f2 = _paged_feature(feats, pool_pages=16)
        warmed = f2.restore_coldcache_state(state)
        assert warmed == f.paged.table.cache.resident * R
        np.testing.assert_array_equal(f2.paged.table.cache.node_of,
                                      f.paged.table.cache.node_of)
        # restored pages serve real values without re-faulting
        faults = _counter("feature_page_faults_total")
        np.testing.assert_array_equal(np.asarray(f2[ids]), feats[ids])
        assert _counter("feature_page_faults_total") == faults

    def test_paged_snapshot_with_paging_off_degrades(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=16)
        ids = self._warm(rng, f)
        state = f.export_coldcache_state()

        off = Feature(device_cache_size=HOT,
                      cache_unit="rows").from_cpu_tensor(feats)
        off.enable_cold_cache(rows=64, admit_threshold=1)
        assert off.restore_coldcache_state(state) == 0  # cold, not a crash
        np.testing.assert_array_equal(np.asarray(off[ids]), feats[ids])

    def test_staged_snapshot_into_paged_build_starts_cold(self, rng):
        feats = _feats(rng)
        staged = Feature(device_cache_size=HOT,
                         cache_unit="rows").from_cpu_tensor(feats)
        staged.enable_cold_cache(rows=64, admit_threshold=1)
        ids = self._warm(rng, staged)
        state = staged.export_coldcache_state()
        assert state.get("kind") != "paged"

        f2 = _paged_feature(feats, pool_pages=16)
        assert f2.restore_coldcache_state(state) == 0
        np.testing.assert_array_equal(np.asarray(f2[ids]), feats[ids])

    def test_page_geometry_mismatch_refuses(self, rng):
        feats = _feats(rng)
        f = _paged_feature(feats, pool_pages=16)
        self._warm(rng, f)
        state = f.export_coldcache_state()
        f2 = _paged_feature(feats, page_rows=2 * R, pool_pages=16)
        with pytest.raises(ValueError, match="page geometry"):
            f2.restore_coldcache_state(state)

    def test_manager_round_trip_restores_residency(self, tmp_path, rng,
                                                   _clean_recovery):
        from quiver_tpu.recovery.manager import RecoveryManager

        root = str(tmp_path / "r")
        feats = _feats(rng)
        mgr = RecoveryManager(root, graph_factory=_graph_factory)
        mgr.boot()
        f = _paged_feature(feats, pool_pages=16)
        mgr.attach_feature("feat", f)
        ids = self._warm(rng, f)
        resident = f.paged.table.cache.resident
        assert resident > 0
        mgr.checkpoint()
        mgr.close()

        mgr2 = RecoveryManager(root, graph_factory=_graph_factory)
        mgr2.boot()
        f2 = _paged_feature(feats, pool_pages=16)
        warmed = mgr2.attach_feature("feat", f2)
        assert warmed == resident * R
        np.testing.assert_array_equal(f2.paged.table.cache.node_of,
                                      f.paged.table.cache.node_of)
        np.testing.assert_array_equal(np.asarray(f2[ids]), feats[ids])
        mgr2.close()

    def test_manager_mismatched_geometry_starts_cold(self, tmp_path, rng,
                                                     _clean_recovery):
        """Through the manager the ValueError is caught: a re-tuned
        page size boots cold instead of refusing."""
        from quiver_tpu.recovery.manager import RecoveryManager

        root = str(tmp_path / "r")
        feats = _feats(rng)
        mgr = RecoveryManager(root, graph_factory=_graph_factory)
        mgr.boot()
        f = _paged_feature(feats, pool_pages=16)
        mgr.attach_feature("feat", f)
        ids = self._warm(rng, f)
        mgr.checkpoint()
        mgr.close()

        mgr2 = RecoveryManager(root, graph_factory=_graph_factory)
        mgr2.boot()
        f2 = _paged_feature(feats, page_rows=2 * R, pool_pages=16)
        assert mgr2.attach_feature("feat", f2) == 0
        np.testing.assert_array_equal(np.asarray(f2[ids]), feats[ids])
        mgr2.close()


# --------------------------------------------------------- kill -9 crash
def _spawn(code, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO), PYTHONUNBUFFERED="1")
    return subprocess.Popen(
        [sys.executable, "-c", code, *map(str, argv)],
        cwd=str(REPO), env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


# The paged-crash child: boot the recovery tier, fault a deterministic
# set of pages, checkpoint, print the resident page set, then spin until
# SIGKILLed — no atexit, no flush beyond the prints.
_PAGED_CHILD = r"""
import json
import sys
import time

import numpy as np

from quiver_tpu.feature import Feature
from quiver_tpu.recovery.manager import RecoveryManager
from quiver_tpu.stream import StreamingGraph
from quiver_tpu.utils.topology import CSRTopo

root, seed = sys.argv[1], int(sys.argv[2])
rng = np.random.default_rng(seed)
feats = rng.standard_normal((512, 16)).astype(np.float32)

def factory():
    src = np.arange(64, dtype=np.int64)
    dst = (src + 1) % 64
    return StreamingGraph(CSRTopo(edge_index=np.stack([src, dst])),
                          delta_capacity=512)

mgr = RecoveryManager(root, graph_factory=factory)
mgr.boot()
f = Feature(device_cache_size=128,
            cache_unit="rows").from_cpu_tensor(feats)
f.enable_paging(page_rows=8, pool_pages=16)
mgr.attach_feature("feat", f)
ids = (128 + rng.choice(128, size=64, replace=False)).astype(np.int64)
f[ids]
mgr.checkpoint()
cache = f.paged.table.cache
resident = sorted(int(p) for p in cache.node_of[cache.node_of >= 0])
print("RESIDENT " + json.dumps(resident), flush=True)
print("READY", flush=True)
while True:
    time.sleep(0.1)
"""


@pytest.mark.crash
def test_kill9_then_recover_restores_page_residency(tmp_path,
                                                    _clean_recovery):
    """``make crash`` variant: a real child checkpoints page residency
    and is SIGKILLed mid-serve; a fresh process must re-warm exactly the
    pages the child reported resident and serve them correctly."""
    from quiver_tpu.recovery.manager import RecoveryManager

    root, seed = str(tmp_path / "r"), 77
    proc = _spawn(_PAGED_CHILD, root, seed)
    resident = None
    try:
        deadline = time.time() + 120
        for line in proc.stdout:
            if line.startswith("RESIDENT "):
                resident = json.loads(line.split(" ", 1)[1])
            if line.strip() == "READY":
                break
            assert time.time() < deadline, "child never reached READY"
        assert resident, (
            "child died before checkpointing: "
            + (proc.stderr.read() or "")[-2000:])
        proc.kill()                            # SIGKILL, no mercy
        proc.wait(timeout=30)
        assert proc.returncode == -signal.SIGKILL
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    # the parent replays the child's exact build (same seed)
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((512, 16)).astype(np.float32)
    mgr = RecoveryManager(root, graph_factory=_graph_factory)
    mgr.boot()
    f = _paged_feature(feats, pool_pages=16)
    warmed = mgr.attach_feature("feat", f)
    assert warmed == len(resident) * R
    cache = f.paged.table.cache
    got = sorted(int(p) for p in cache.node_of[cache.node_of >= 0])
    assert got == resident
    ids = (HOT + rng.choice(N - HOT, size=64,
                            replace=False)).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(f[ids]), feats[ids])
    mgr.close()


# ------------------------------------------------------------- tooling
def test_paged_module_is_in_the_lint_hot_set():
    """quiverlint must treat ops/paged.py as hot-path code (QT001's
    implicit-device_get rule and friends apply)."""
    import fnmatch

    from quiver_tpu.analysis.core import _DEFAULT_HOT

    assert any(fnmatch.fnmatch("quiver_tpu/ops/paged.py", pat)
               for pat in _DEFAULT_HOT)
