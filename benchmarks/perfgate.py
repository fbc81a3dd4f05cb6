"""Noise-aware perf-regression gate (docs/BENCHMARKS.md "Perfgate").

``bench.py`` answers "how fast is the library?"; this gate answers the
cheaper CI question "did THIS change make it slower?".  It runs a
k-rep micro-bench over a fixed set of library hot paths (WAL append,
span + timeline emit overhead, Prometheus exposition, the CPU
sampler), compares each metric's MIN-of-k (timing noise on a shared
host is strictly additive, so the min is the stable run-to-run
estimator; median + MAD ride along to size the noise threshold)
against the committed baseline in ``.bench_state.json`` (top-level
``"perfgate"`` key, one entry per backend), and writes a
``PERFGATE.json`` verdict.

Noise model: wall-clock micro-benches on shared runners jitter, so a
raw threshold would flap.  A metric regresses only when the slowdown
clears BOTH bars:

  * ``config.perfgate_mad_mult`` x the MAD-derived robust sigma
    (1.4826 x max(baseline MAD, current MAD)) — statistically clear of
    the measured run-to-run noise;
  * ``config.perfgate_rel_floor`` x baseline — large enough in
    relative terms to be worth gating on at all (a statistically-clear
    2% drift on a 40 µs metric is not a gate-worthy regression).

Honesty stamping (same rules as bench.py): the verdict carries the
backend this process actually initialized and
``source: "cpu_rehearsal"`` unless it ran on real silicon — a CPU CI
verdict can never masquerade as device evidence.  CI runs with
``--report-only`` on CPU-only runners: the verdict is still written
and uploaded, but the exit code stays 0 (soft-fail).

Exit codes: 0 = pass / baseline seeded / report-only; 1 = regression.

Test hook: ``QUIVER_PERFGATE_INJECT`` multiplies measured medians by a
factor (``"2.0"`` for all metrics, or ``"wal_append:3.0"`` for one) —
the synthetic regression the acceptance test drives through the real
compare path.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

STATE_PATH = os.path.join(_REPO, ".bench_state.json")
OUT_PATH = os.path.join(_REPO, "PERFGATE.json")

# the mesh_gather metric needs a multi-device mesh.  When this module
# loads before jax initializes (CI: `python benchmarks/perfgate.py`),
# stage the CPU-rehearsal virtual slice; embedders that already booted
# a backend (bench --check, tests) are unaffected — the flag is only
# read at backend init, and the metric clamps its shard count to the
# devices actually visible.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()


# ---------------------------------------------------------------- metrics
def _m_wal_append() -> float:
    """ms per 200 batched-fsync WAL appends (blockio + framing path)."""
    from quiver_tpu.recovery.wal import WriteAheadLog

    with tempfile.TemporaryDirectory() as root:
        wal = WriteAheadLog(root, fsync="batch", batch_bytes=1 << 20)
        payload = b"x" * 128
        t0 = time.perf_counter()
        for _ in range(200):
            wal.append(payload)
        dt = time.perf_counter() - t0
        wal.close()
    return dt * 1e3


def _m_spans() -> float:
    """ms per 5000 span open/close (aggregation path, no retention)."""
    from quiver_tpu import telemetry

    tracer = telemetry.SpanTracer(tracing=False)
    t0 = time.perf_counter()
    for _ in range(5000):
        with tracer.span("perfgate.scope"):
            pass
    return (time.perf_counter() - t0) * 1e3


def _m_timeline_emit() -> float:
    """ms per 5000 timeline emits into a private ring set."""
    from quiver_tpu.telemetry import timeline

    timeline.reset()
    if not timeline.enable(capacity=8192):
        raise RuntimeError("telemetry disabled")
    try:
        t0 = time.perf_counter()
        for _ in range(5000):
            timeline.emit("perfgate.emit", cat="app", dur_s=1e-6)
        return (time.perf_counter() - t0) * 1e3
    finally:
        timeline.reset()


def _m_prom_text() -> float:
    """ms to render a 600-series registry snapshot as Prometheus text."""
    from quiver_tpu.telemetry import MetricsRegistry
    from quiver_tpu.telemetry.export import to_prometheus_text

    reg = MetricsRegistry()
    for i in range(200):
        reg.counter("perfgate_counter_total", shard=str(i)).inc(float(i))
        reg.gauge("perfgate_gauge", shard=str(i)).set(float(i))
        reg.histogram("perfgate_hist_seconds", shard=str(i)).observe(
            i * 1e-3)
    snap = reg.snapshot()
    t0 = time.perf_counter()
    to_prometheus_text(snap)
    return (time.perf_counter() - t0) * 1e3


def _m_sampler_cpu() -> float:
    """ms per CPU-lane sample batch on a 20K-node synthetic graph."""
    import numpy as np

    from quiver_tpu import CSRTopo
    from quiver_tpu.sampler import GraphSageSampler

    rng = np.random.default_rng(0)
    n, deg = 20_000, 15
    indices = rng.integers(0, n, size=n * deg, dtype=np.int64)
    indptr = np.arange(0, n * deg + 1, deg, dtype=np.int64)
    topo = CSRTopo(indptr=indptr, indices=indices)
    sampler = GraphSageSampler(topo, [10, 5], mode="CPU")
    seeds = rng.integers(0, n, size=256, dtype=np.int64)
    sampler.sample(seeds)  # warm (allocators, native table setup)
    t0 = time.perf_counter()
    for _ in range(5):
        sampler.sample(seeds)
    return (time.perf_counter() - t0) / 5 * 1e3


def _m_fleet_trace_stamp() -> float:
    """ms per 1000 fleet trace stamp + finish pairs — the federation-ON
    request-path bookkeeping (TraceContext, payload stamp, hop record,
    timeline slice when on) without any network in the number."""
    from quiver_tpu.fleet import FleetRouter, MembershipDirectory
    from quiver_tpu.telemetry import flightrec

    with tempfile.TemporaryDirectory() as fdir:
        router = FleetRouter(MembershipDirectory(fdir),
                             federation=True, scan_ttl_s=60.0)
        rec = flightrec.get_recorder()
        try:
            t0 = time.perf_counter()
            for _ in range(1000):
                req = {"ids": [1], "tenant": None}
                ctx, hop = router._trace_begin(req, None, 1)
                if ctx is None:
                    raise RuntimeError("telemetry disabled")
                router._trace_finish(hop, ctx)
                rec.finish(ctx, 0.0, lane="perfgate")
            dt = time.perf_counter() - t0
        finally:
            router.close()
    return dt * 1e3


def _m_fleet_router_off() -> float:
    """ms per 200 federation-OFF ``router.request`` round trips against
    an in-process echo replica — the one-config-check request path the
    disabled plane must keep byte-identical to PR 13."""
    import socketserver
    import threading

    from quiver_tpu.fleet import FleetRouter, MembershipDirectory
    from quiver_tpu.fleet.membership import ReplicaInfo

    class _Echo(socketserver.StreamRequestHandler):
        def handle(self):
            while True:
                if not self.rfile.readline():
                    return
                self.wfile.write(b'{"status": "ok"}\n')

    class _Srv(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    with tempfile.TemporaryDirectory() as fdir:
        srv = _Srv(("127.0.0.1", 0), _Echo)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            directory = MembershipDirectory(fdir,
                                            heartbeat_timeout_s=60.0)
            directory.announce(ReplicaInfo(
                "echo", state="serving", port=srv.server_address[1]))
            router = FleetRouter(directory, scan_ttl_s=60.0,
                                 federation=False)
            router.request([1])  # warm: scan, ring, breaker, socket
            t0 = time.perf_counter()
            for i in range(200):
                router.request([1], seq=i)
            dt = time.perf_counter() - t0
            router.close()
        finally:
            srv.shutdown()
            srv.server_close()
    return dt * 1e3


def _m_mesh_gather() -> float:
    """ms per warmed 4-shard mesh gather batch (B=256) on the CPU
    rehearsal mesh — the steady-state sharded-serving hot path: shard
    ownership planning, the shard_map collective, halo accounting.
    Clamps to the visible device count when an embedder initialized a
    smaller backend before the rehearsal flag could be staged."""
    import jax
    import numpy as np

    from quiver_tpu.mesh import MeshFeature

    rng = np.random.default_rng(0)
    table = rng.standard_normal((20_000, 32)).astype(np.float32)
    mf = MeshFeature(table, n_shards=min(4, jax.device_count()))
    ids = rng.integers(0, 20_000, 256)
    mf[ids].block_until_ready()  # warm: faults, restack, gather build
    t0 = time.perf_counter()
    for _ in range(10):
        out = mf[ids]
    out.block_until_ready()
    return (time.perf_counter() - t0) / 10 * 1e3


def _m_quiverlint_run() -> float:
    """ms for one full quiverlint pass over the lint targets — parse,
    ONE shared Program build, every per-file and program rule (QT001..
    QT015 incl. the staging-dataflow fixpoint).  The v3 one-parse
    architecture is only honest if whole-repo analysis stays cheap
    enough for tier-1; this metric is the receipt."""
    from quiver_tpu.analysis import analyze_paths

    t0 = time.perf_counter()
    res = analyze_paths(["quiver_tpu", "bench.py"], root=_REPO)
    dt = time.perf_counter() - t0
    if res.errors:
        raise RuntimeError(f"lint errors: {res.errors[:3]}")
    return dt * 1e3


METRICS: Dict[str, Callable[[], float]] = {
    "wal_append": _m_wal_append,
    "spans": _m_spans,
    "timeline_emit": _m_timeline_emit,
    "prom_text": _m_prom_text,
    "sampler_cpu": _m_sampler_cpu,
    "fleet_trace_stamp": _m_fleet_trace_stamp,
    "fleet_router_off": _m_fleet_router_off,
    "mesh_gather": _m_mesh_gather,
    "quiverlint_run": _m_quiverlint_run,
}


# ---------------------------------------------------------------- measure
def _mad(xs: List[float]) -> float:
    med = statistics.median(xs)
    return statistics.median([abs(x - med) for x in xs])


def measure(k: int, log=print) -> Dict[str, dict]:
    """Median-of-k per metric.  A metric that raises is reported as
    skipped (``error``), never crashes the gate — CI must degrade, not
    die, when e.g. the native sampler isn't built."""
    out: Dict[str, dict] = {}
    for name, fn in METRICS.items():
        try:
            fn()  # one warmup rep outside the sample
            xs = [fn() for _ in range(k)]
        except Exception as e:  # noqa: BLE001 — degrade per metric
            log(f"[perfgate] metric {name} skipped: {e}")
            out[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        # min is the gate's point estimate: timing noise on a shared
        # host is strictly additive, so min-of-k is far more stable
        # run-to-run than the median; median+MAD still size the noise
        # threshold and ride along for the report
        out[name] = {"min_ms": round(min(xs), 4),
                     "median_ms": round(statistics.median(xs), 4),
                     "mad_ms": round(_mad(xs), 4), "k": k,
                     "samples_ms": [round(x, 4) for x in xs]}
    return out


def _apply_injection(measured: Dict[str, dict], spec: str,
                     log=print) -> None:
    """QUIVER_PERFGATE_INJECT: synthetic slowdown through the real
    compare path ("2.0" = all metrics, "name:2.0" = one)."""
    name = None
    if ":" in spec:
        name, _, spec = spec.partition(":")
    try:
        factor = float(spec)
    except ValueError:
        log(f"[perfgate] bad QUIVER_PERFGATE_INJECT {spec!r}; ignored")
        return
    for m, rec in measured.items():
        if "median_ms" in rec and (name is None or m == name):
            rec["median_ms"] = round(rec["median_ms"] * factor, 4)
            if "min_ms" in rec:
                rec["min_ms"] = round(rec["min_ms"] * factor, 4)
            rec["injected_factor"] = factor


# ---------------------------------------------------------------- baseline
def _load_state(path: str) -> dict:
    try:
        raw = json.load(open(path))
        return raw if isinstance(raw, dict) else {}
    except Exception:
        return {}


def load_baseline(path: str, backend: str) -> Optional[dict]:
    gate = _load_state(path).get("perfgate")
    if isinstance(gate, dict):
        entry = gate.get(backend)
        if isinstance(entry, dict) and isinstance(entry.get("metrics"),
                                                  dict):
            return entry
    return None


def save_baseline(path: str, backend: str, measured: Dict[str, dict],
                  device: bool) -> None:
    """Read-merge-replace under a flock, so two gate runs can't lose
    each other's write."""
    import fcntl

    metrics = {m: {"min_ms": r.get("min_ms", r["median_ms"]),
                   "median_ms": r["median_ms"], "mad_ms": r["mad_ms"],
                   "k": r["k"]}
               for m, r in measured.items() if "median_ms" in r}
    with open(path + ".lock", "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            disk = _load_state(path)
            disk.setdefault("perfgate", {})[backend] = {
                "metrics": metrics, "device": device,
                "source": "live_device" if device else "cpu_rehearsal",
            }
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(disk, fh, indent=1, sort_keys=True)
            os.replace(tmp, path)
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)


# ---------------------------------------------------------------- verdict
def compare(baseline: dict, measured: Dict[str, dict], mad_mult: float,
            rel_floor: float) -> dict:
    metrics = {}
    regressions = []
    for name, base in baseline["metrics"].items():
        cur = measured.get(name)
        if cur is None or "median_ms" not in cur:
            metrics[name] = {"baseline_ms": base["median_ms"],
                             "status": "skipped",
                             "error": (cur or {}).get("error")}
            continue
        b_min = base.get("min_ms", base["median_ms"])
        c_min = cur.get("min_ms", cur["median_ms"])
        sigma = 1.4826 * max(base.get("mad_ms", 0.0), cur["mad_ms"], 1e-6)
        threshold = max(mad_mult * sigma, rel_floor * b_min)
        delta = c_min - b_min
        regressed = delta > threshold
        rec = {
            "baseline_ms": b_min, "current_ms": c_min,
            "delta_ms": round(delta, 4),
            "threshold_ms": round(threshold, 4),
            "rel_change": round(delta / b_min, 4) if b_min else None,
            "status": "regression" if regressed else "pass",
        }
        if "injected_factor" in cur:
            rec["injected_factor"] = cur["injected_factor"]
        metrics[name] = rec
        if regressed:
            regressions.append(name)
    new = sorted(set(m for m, r in measured.items() if "median_ms" in r)
                 - set(baseline["metrics"]))
    return {"metrics": metrics, "regressions": regressions,
            "new_metrics": new}


def run_gate(k: Optional[int] = None, seed: bool = False,
             report_only: bool = False, state_path: str = STATE_PATH,
             out_path: str = OUT_PATH, log=print) -> int:
    from quiver_tpu.config import get_config

    cfg = get_config()
    if k is None:
        k = int(cfg.perfgate_k)
    try:
        import jax

        backend = jax.default_backend()
    except Exception:
        backend = "none"
    device = backend not in ("cpu", "none")

    measured = measure(k, log=log)
    inject = os.environ.get("QUIVER_PERFGATE_INJECT", "").strip()
    if inject:
        _apply_injection(measured, inject, log=log)

    verdict = {
        "backend": backend,
        "device": device,
        "source": "live_device" if device else "cpu_rehearsal",
        "report_only": bool(report_only),
        "k": k,
        "mad_mult": float(cfg.perfgate_mad_mult),
        "rel_floor": float(cfg.perfgate_rel_floor),
        "measured": measured,
    }
    baseline = load_baseline(state_path, backend)
    if seed or baseline is None:
        save_baseline(state_path, backend, measured, device)
        verdict["status"] = "seeded"
        verdict["note"] = ("baseline seeded for backend "
                           f"{backend!r}; commit .bench_state.json")
        code = 0
    else:
        cmp = compare(baseline, measured, float(cfg.perfgate_mad_mult),
                      float(cfg.perfgate_rel_floor))
        verdict.update(cmp)
        verdict["status"] = ("regression" if cmp["regressions"]
                             else "pass")
        code = 1 if cmp["regressions"] else 0

    try:  # in-process visibility for embedders (bench --check, tests);
        # a no-op when telemetry is off
        from quiver_tpu import telemetry

        telemetry.gauge("perfgate_pass_state").set(
            0.0 if verdict.get("regressions") else 1.0)
        telemetry.gauge("perfgate_regressions").set(
            float(len(verdict.get("regressions", ()))))
    except Exception:
        pass
    with open(out_path, "w") as f:
        json.dump(verdict, f, indent=2, sort_keys=True)
    log(f"[perfgate] {verdict['status']} (backend={backend}, "
        f"source={verdict['source']}) -> {out_path}")
    for name in verdict.get("regressions", []):
        m = verdict["metrics"][name]
        log(f"[perfgate]   REGRESSION {name}: {m['baseline_ms']} -> "
            f"{m['current_ms']} ms (threshold +{m['threshold_ms']} ms)")
    if report_only and code:
        log("[perfgate] report-only: regression reported, exit 0")
        return 0
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", action="store_true",
                    help="(re)write the baseline for this backend")
    ap.add_argument("--report-only", action="store_true",
                    help="write the verdict but always exit 0 (CI on "
                         "CPU-only runners)")
    ap.add_argument("--k", type=int, default=None,
                    help="repeats per metric (default config.perfgate_k)")
    ap.add_argument("--state", default=STATE_PATH,
                    help="baseline file (default .bench_state.json)")
    ap.add_argument("--out", default=OUT_PATH,
                    help="verdict file (default PERFGATE.json)")
    args = ap.parse_args(argv)
    return run_gate(k=args.k, seed=args.seed,
                    report_only=args.report_only, state_path=args.state,
                    out_path=args.out)


if __name__ == "__main__":
    sys.exit(main())
