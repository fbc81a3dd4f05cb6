"""Contract of bench.py's emission and section machinery.

The run prints one JSON line holding only what THIS run measured on the
device it names; a section that fails or times out makes the run fail;
a backend without a TPU is refused unless a CPU rehearsal was asked for
by name.  These tests pin that without any device.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}


def _emit(capsys, sections, failed, device):
    bench._emit_result(sections, failed, device)
    return json.loads(capsys.readouterr().out.strip())


class TestEmitResult:
    def test_tpu_headline_is_scored_and_names_its_device(self, capsys):
        out = _emit(capsys, {"sampling": {"seps": 3.429e7}}, {}, TPU)
        assert out["ok"] is True and out["device"] == TPU
        assert out["vs_baseline"] == 1.0 and out["failed"] == {}

    def test_cpu_rehearsal_is_never_scored(self, capsys):
        out = _emit(capsys, {"sampling": {"seps": 1e7}}, {}, CPU)
        assert out["device"]["platform"] == "cpu"
        assert out["vs_baseline"] is None  # never scored vs the GPU

    def test_missing_headline_is_zero_and_unscored(self, capsys):
        out = _emit(capsys, {}, {}, TPU)
        assert out["vs_baseline"] is None and out["value"] == 0.0

    def test_failed_section_makes_the_result_not_ok(self, capsys):
        out = _emit(capsys, {"feature": {"hot_gbs": 1.0}},
                    {"e2e": "RuntimeError: boom"}, TPU)
        assert out["ok"] is False
        assert out["failed"] == {"e2e": "RuntimeError: boom"}


class TestSectionRunner:
    @pytest.fixture(autouse=True)
    def _empty_registry(self):
        # the runner attaches the registry's delta to a section, gauges
        # included, and the registry is process-global: start from empty,
        # whatever an earlier file in this worker left in it
        from quiver_tpu import telemetry

        telemetry.reset()

    def test_result_is_kept_and_returned(self):
        r = bench._SectionRunner()
        assert r.run("sampling_B1024", 30, lambda: {"seps": 42.0}) == {
            "seps": 42.0}
        assert r.sections == {"sampling_B1024": {"seps": 42.0}}
        assert r.failed == {}

    def test_no_state_survives_the_runner(self, tmp_path, monkeypatch):
        """Nothing is replayed: a second runner measures again."""
        monkeypatch.chdir(tmp_path)
        bench._SectionRunner().run("feature", 30, lambda: {"hot_gbs": 1.0})
        calls = []
        out = bench._SectionRunner().run(
            "feature", 30, lambda: calls.append(1) or {"hot_gbs": 2.0})
        assert out == {"hot_gbs": 2.0} and calls == [1]
        assert list(tmp_path.iterdir()) == []

    def test_failed_section_is_recorded_and_the_rest_still_run(self):
        r = bench._SectionRunner()

        def boom():
            raise RuntimeError("device lost")

        assert r.run("e2e", 30, boom) is None
        assert r.failed == {"e2e": "RuntimeError: device lost"}
        assert "e2e" not in r.sections
        assert r.run("serving", 30, lambda: {"ok": 1}) == {"ok": 1}

    def test_timed_out_section_fails(self):
        r = bench._SectionRunner()
        assert r.run("slow", 1, lambda: time.sleep(5)) is None
        assert r.failed["slow"].startswith("_SectionTimeout")

    def test_bounded_does_not_swallow(self):
        with pytest.raises(ValueError):
            with bench._bounded("x", 30):
                raise ValueError("kept")


def _run_bench(*args, env=None):
    env = dict(os.environ, **(env or {}))
    return subprocess.run([sys.executable, "bench.py", *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


class TestBackendGate:
    def test_full_run_without_a_tpu_is_refused(self):
        """No ``--small``: a measurement path that found no chip."""
        p = _run_bench("--sections", "serving_qos",
                       env={"JAX_PLATFORMS": "cpu"})
        assert p.returncode == 2 and p.stdout == ""
        assert "needs a TPU" in p.stderr

    def test_failed_section_fails_the_run(self):
        """A rehearsal whose one section raises: the JSON line says so
        and the exit code is non-zero."""
        p = _run_bench("--small", "--sections", "e2e", "--gather-mode",
                       "no-such-mode", env={"JAX_PLATFORMS": "cpu"})
        assert p.returncode == 1, p.stderr[-2000:]
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["ok"] is False and "e2e" in out["failed"]
        assert out["device"]["platform"] == "cpu"
        assert out["vs_baseline"] is None


class TestServingSetupCache:
    """_serving_setup's cache must not key on id(topo) alone: a collected
    topo's address can be recycled by a NEW same-shape graph and serve a
    stale sampler/feature pair (round-5 advisor carry-over)."""

    def _topo(self, seed):
        import numpy as np

        from quiver_tpu.utils.topology import CSRTopo

        rng = np.random.default_rng(seed)
        src = rng.integers(0, 40, 300)
        dst = rng.integers(0, 40, 300)
        return CSRTopo(edge_index=np.stack([src, dst]))

    def test_hit_same_topo_miss_fresh_topo_and_strong_ref(self, monkeypatch):
        monkeypatch.setattr(bench, "_SERVING_CACHE", {})
        t1 = self._topo(0)
        v1 = bench._serving_setup(t1, dim=4, classes=2, hidden=4)
        assert bench._serving_setup(t1, 4, 2, 4) is v1  # cache hit
        # the cache pins the keyed topo alive so its id cannot be reused
        assert bench._SERVING_CACHE["topo"] is t1
        # a different graph object never reuses the entry, even when the
        # node/edge counts happen to collide
        t2 = self._topo(1)
        assert (t2.node_count, t2.edge_count) == (t1.node_count,
                                                  t1.edge_count)
        v2 = bench._serving_setup(t2, 4, 2, 4)
        assert v2 is not v1
        assert bench._SERVING_CACHE["topo"] is t2
