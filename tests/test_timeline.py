"""Unified timeline profiler suite (ISSUE 11).

Covers the cross-subsystem event bus (:mod:`quiver_tpu.telemetry.
timeline`), the perf gate (``benchmarks/perfgate.py``), the
hostile-label Prometheus escaping fix, and the hardened XLA-profiler
wrapper.

The load-bearing tests:

  * the OFF path is pinned at exactly one module-global read per emit
    site (``on.__code__.co_names``) and instrumented subsystems create
    NO rings while the timeline is off;
  * a >=8-thread hammer with a live export mid-emission: per-thread
    monotone ordering, bounded ring capacity with honest drop counts,
    and a merged Chrome trace Perfetto can load;
  * perfgate exit codes: seed -> 0, unchanged re-run -> 0, injected
    synthetic regression -> 1 (through the real compare path).
"""

import json
import os
import re
import sys
import threading
import time
from pathlib import Path

import pytest

from quiver_tpu import telemetry
from quiver_tpu.telemetry import flightrec, timeline

pytestmark = pytest.mark.timeline

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    # restore, don't force off: telemetry is process-global, and a worker
    # that runs another file after this one (xdist hands out files by
    # size, not by name) would find every counter a no-op
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(was)
    telemetry.reset()


# ------------------------------------------------------------ gating
class TestGating:
    def test_off_path_is_one_global_read(self):
        # THE zero-overhead-off contract: the guard every hot emit site
        # uses compiles to a single module-global load.  If this fails,
        # someone added work to the off path — that is a perf
        # regression at every instrumented call site in the library.
        assert timeline.on.__code__.co_names == ("_ON",)

    def test_off_timeline_records_nothing_from_subsystems(self):
        assert not timeline.on()
        # exercise instrumented subsystems with the timeline off
        with telemetry.span("off.scope"):
            pass
        ctx = flightrec.new_trace()
        with flightrec.activate(ctx):
            flightrec.event("off.event", {"seconds": 0.001})
        flightrec.get_recorder().finish(ctx, 0.001)
        st = timeline.status()
        assert st["enabled"] is False
        assert st["threads"] == 0 and st["events"] == 0

    def test_enable_respects_telemetry_kill_switch(self):
        telemetry.set_enabled(False)
        assert timeline.enable() is False
        assert not timeline.on()

    def test_spans_and_flightrec_land_when_on(self):
        timeline.enable()
        with telemetry.span("demo.scope"):
            pass
        ctx = flightrec.new_trace()
        with flightrec.activate(ctx):
            flightrec.event("sample", {"seconds": 0.002})
        flightrec.get_recorder().finish(ctx, 0.01, lane="test")
        names = {e[2] for r in timeline._seen_rings() for e in r.ordered()}
        assert {"demo.scope", "sample", "request"} <= names
        # correlation: the flightrec-originated events carry the trace id
        doc = timeline.chrome_trace()
        tids = {e["args"].get("trace_id") for e in doc["traceEvents"]
                if e.get("name") in ("sample", "request")}
        assert tids == {ctx.trace_id}


# ------------------------------------------------------------ hammer
class TestConcurrentHammer:
    N_THREADS = 8
    PER_THREAD = 3000
    CAP = 512

    def test_hammer_with_live_export(self):
        timeline.enable(capacity=self.CAP)
        start = threading.Barrier(self.N_THREADS + 2)
        done = threading.Event()
        export_docs = []

        def emitter(t):
            start.wait()
            for i in range(self.PER_THREAD):
                timeline.emit(f"hammer.t{t}", cat="app", dur_s=1e-7,
                              attrs={"i": i})

        def exporter():
            start.wait()
            while not done.is_set():
                # live export DURING emission must never crash or
                # return a malformed doc
                doc = timeline.chrome_trace()
                json.dumps(doc)
                export_docs.append(len(doc["traceEvents"]))

        threads = [threading.Thread(target=emitter, args=(t,))
                   for t in range(self.N_THREADS)]
        exp = threading.Thread(target=exporter)
        for th in threads:
            th.start()
        exp.start()
        start.wait()
        for th in threads:
            th.join()
        done.set()
        exp.join()

        st = timeline.status()
        # bounded capacity: each ring kept at most CAP events and the
        # overflow is counted, not silently lost
        assert st["events"] <= self.N_THREADS * self.CAP + self.CAP
        total = self.N_THREADS * self.PER_THREAD
        assert st["dropped"] >= total - self.N_THREADS * self.CAP
        assert export_docs, "live exporter never ran"

        doc = timeline.chrome_trace()
        by_tid = {}
        for e in doc["traceEvents"]:
            if e["ph"] == "M" or not e["name"].startswith("hammer."):
                continue
            by_tid.setdefault(e["tid"], []).append(e)
        assert len(by_tid) == self.N_THREADS
        for tid, evs in by_tid.items():
            # per-thread ordering: the ring unwraps oldest-first, and
            # one thread's timestamps are monotone
            ts = [e["ts"] for e in evs]
            assert ts == sorted(ts), f"tid {tid} out of order"
            idx = [e["args"]["i"] for e in evs]
            assert idx == sorted(idx)
            assert len(evs) <= self.CAP

    def test_reset_during_emission_is_safe(self):
        timeline.enable(capacity=64)
        stop = threading.Event()

        def emitter():
            while not stop.is_set():
                if timeline.on():
                    timeline.emit("churn", cat="app")

        th = threading.Thread(target=emitter)
        th.start()
        try:
            for _ in range(20):
                timeline.reset()
                timeline.enable(capacity=64)
                timeline.chrome_trace()
        finally:
            stop.set()
            th.join()
        timeline.reset()
        assert timeline.status()["threads"] == 0


# ------------------------------------------------------------ chrome trace
class TestChromeTrace:
    def test_slices_instants_and_metadata(self, tmp_path):
        timeline.enable()
        timeline.emit("dur.ev", cat="wal", dur_s=0.005)
        timeline.instant("inst.ev", cat="chaos", attrs={"k": 1})
        path = timeline.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert {"M", "X", "i"} <= phases
        x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert x["dur"] == pytest.approx(5000, rel=0.01)  # microseconds
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"quiver_tpu"}

    def test_category_inference(self):
        timeline.enable()
        timeline.emit("sample")            # serving stage map
        timeline.emit("feature.page_fault")  # dotted prefix remap
        timeline.emit("wal.fsync")
        doc = timeline.chrome_trace()
        cats = {e["name"]: e["cat"] for e in doc["traceEvents"]
                if e["ph"] != "M"}
        assert cats["sample"] == "serving"
        assert cats["feature.page_fault"] == "paged"
        assert cats["wal.fsync"] == "wal"


# ------------------------------------------------------------ endpoints
class TestHttpEndpoints:
    def test_debug_timeline_and_programs_roundtrip(self):
        from urllib.error import HTTPError
        from urllib.request import urlopen

        from quiver_tpu.telemetry.export import start_http_server

        timeline.enable()
        timeline.emit("http.ev", cat="app", dur_s=0.001)
        srv = start_http_server(port=0)
        try:
            doc = json.loads(urlopen(f"{srv.url}/debug/timeline",
                                     timeout=5).read())
            assert any(e.get("name") == "http.ev"
                       for e in doc["traceEvents"])
            # the blocking program profiler and its route went together
            with pytest.raises(HTTPError) as gone:
                urlopen(f"{srv.url}/debug/programs", timeout=5)
            assert gone.value.code == 404
        finally:
            srv.close()


# ------------------------------------------------------------ escaping
_SERIES_RE = re.compile(r'^(\w+)\{(.*)\} ([0-9.eE+-]+)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return (v.replace("\\n", "\n").replace('\\"', '"')
             .replace("\\\\", "\\"))


class TestPrometheusEscaping:
    def test_backslash_label_roundtrips_end_to_end(self):
        # the registry's reserved-character check blocks , = { } " \n
        # at metric-creation time, but a backslash sails through — and
        # unescaped it corrupts the exposition format (prometheus reads
        # `\\` as one backslash, a lone `\t` as an escape sequence)
        from quiver_tpu.telemetry.export import to_prometheus_text

        hostile = 'dom\\ain\\tenant'
        telemetry.counter("escape_test_total", tenant=hostile).inc(3)
        text = to_prometheus_text(telemetry.snapshot())
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("escape_test_total{"))
        m = _SERIES_RE.match(line)
        assert m, f"unparseable series line: {line!r}"
        labels = dict(_LABEL_RE.findall(m.group(2)))
        assert _unescape(labels["tenant"]) == hostile
        assert float(m.group(3)) == 3.0

    def test_formatter_escapes_fully_hostile_values(self):
        # _fmt_labels is also fed labels the registry never vetted
        # (histogram `le`, snapshot post-processors): it must escape
        # quote/newline/backslash itself, one series per LINE
        from quiver_tpu.telemetry.export import _fmt_labels

        hostile = 'ev"il\\ten\nant'
        rendered = _fmt_labels({"tenant": hostile})
        assert "\n" not in rendered
        labels = dict(_LABEL_RE.findall(rendered.strip("{}")))
        assert _unescape(labels["tenant"]) == hostile

    def test_plain_labels_unchanged(self):
        from quiver_tpu.telemetry.export import to_prometheus_text

        telemetry.counter("plain_total", tenant="tenant-a").inc()
        text = to_prometheus_text(telemetry.snapshot())
        assert 'plain_total{tenant="tenant-a"} 1' in text


# ------------------------------------------------------------ perfgate
def _perfgate():
    sys.path.insert(0, str(REPO / "benchmarks"))
    import perfgate

    return perfgate


class TestPerfgate:
    @pytest.fixture()
    def fast_metrics(self, monkeypatch):
        pg = _perfgate()
        ticker = {"n": 0}

        def fast():
            ticker["n"] += 1
            return 5.0  # deterministic "measurement"

        monkeypatch.setattr(pg, "METRICS", {"fast": fast})
        return pg

    def test_seed_then_pass_then_injected_regression(self, tmp_path,
                                                     fast_metrics,
                                                     monkeypatch):
        pg = fast_metrics
        state = str(tmp_path / "state.json")
        out = str(tmp_path / "PERFGATE.json")
        argv = ["--state", state, "--out", out, "--k", "3"]
        assert pg.main(argv) == 0
        assert json.load(open(out))["status"] == "seeded"
        # baseline persisted under the top-level "perfgate" key
        disk = json.load(open(state))
        assert "perfgate" in disk

        assert pg.main(argv) == 0
        assert json.load(open(out))["status"] == "pass"

        monkeypatch.setenv("QUIVER_PERFGATE_INJECT", "2.0")
        assert pg.main(argv) == 1
        verdict = json.load(open(out))
        assert verdict["status"] == "regression"
        assert verdict["regressions"] == ["fast"]
        assert verdict["metrics"]["fast"]["injected_factor"] == 2.0
        # honest stamping: this suite pins the CPU backend
        assert verdict["source"] == "cpu_rehearsal"

        # report-only (the CPU CI mode): verdict written, exit 0
        assert pg.main(argv + ["--report-only"]) == 0
        assert json.load(open(out))["status"] == "regression"

    def test_skipped_metric_degrades_not_dies(self, tmp_path,
                                              monkeypatch):
        pg = _perfgate()

        def boom():
            raise RuntimeError("native dep missing")

        monkeypatch.setattr(pg, "METRICS", {"ok": lambda: 1.0,
                                            "broken": boom})
        state = str(tmp_path / "state.json")
        out = str(tmp_path / "PERFGATE.json")
        argv = ["--state", state, "--out", out, "--k", "2"]
        assert pg.main(argv) == 0  # seeds with the one working metric
        assert pg.main(argv) == 0
        verdict = json.load(open(out))
        assert "error" in verdict["measured"]["broken"]

    def test_noise_below_threshold_passes(self, tmp_path, monkeypatch):
        pg = _perfgate()
        val = {"v": 10.0}
        monkeypatch.setattr(pg, "METRICS", {"m": lambda: val["v"]})
        state = str(tmp_path / "s.json")
        out = str(tmp_path / "o.json")
        argv = ["--state", state, "--out", out, "--k", "3"]
        assert pg.main(argv) == 0
        val["v"] = 11.0  # +10%: under the 30% relative floor
        assert pg.main(argv) == 0
        val["v"] = 20.0  # +100%: a real regression
        assert pg.main(argv) == 1


# ------------------------------------------------------------ xla profiler
class TestProfileTraceHardening:
    def test_degrades_to_noop_and_warns_once(self, tmp_path, capsys,
                                             monkeypatch):
        import quiver_tpu.utils.trace as trace_mod

        monkeypatch.setattr(trace_mod, "_PROFILE_WARNED", False)
        # double-start: the inner span must degrade, never raise
        with trace_mod.profile_trace(str(tmp_path / "a")):
            with trace_mod.profile_trace(str(tmp_path / "b")):
                pass
            with trace_mod.profile_trace(str(tmp_path / "c")):
                pass
        err = capsys.readouterr().err
        assert err.count("profiler unavailable") == 1  # warn ONCE
