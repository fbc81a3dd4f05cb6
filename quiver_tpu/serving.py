"""GNN serving pipeline — TPU-native batcher / hybrid sampler / server.

Reference parity: ``srcs/python/quiver/serving.py`` —
``RequestBatcher`` (:10-98, workload-aware ``auto_despatch`` routing by
summed per-node ``neighbour_num`` vs a threshold), ``HybridSampler``
(:101-147, CPU sampler workers), ``InferenceServer`` / ``_Debug``
(:150-360, sample→feature→model loops + tp99 accounting).

TPU-first redesign: the reference shards the pipeline over *processes* with
``mp.Manager().Queue``s because CUDA contexts and the GIL force it to.  Here
the single-controller model inverts that: stages are **threads** sharing one
process (the native CPU sampler and XLA release the GIL), queues are
``queue.Queue``, and the device stage uses **bucketed batch shapes** (pad to
the next power of two) so every request size hits a cached jit executable —
the TPU answer to CUDA's any-shape kernel launches.  Routing keeps the same
mechanism: requests whose expected expansion is small run on the CPU
sampler (low latency, no device round-trip), big ones batch onto the TPU.

Fault tolerance (docs/RESILIENCE.md): requests carry absolute deadlines
checked at every stage boundary; the batcher lanes are
:class:`~quiver_tpu.resilience.BoundedLane`s that shed under overload;
each server lane sits behind a :class:`~quiver_tpu.resilience.
CircuitBreaker` and fails over to the other lane (device→CPU via an
inline ``cpu_sampler`` pass, CPU→device via the bucketed forward); and
the named ``chaos.point(...)`` call sites let the chaos suite inject
faults deterministically.  A request is always *answered* — with its
result or a typed resilience error — never silently dropped.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from . import telemetry
from .analysis.staging import no_sync
from .resilience import chaos
from .resilience.breaker import CircuitBreaker
from .resilience.deadline import deadline_for, deadline_scope, \
    shed_if_expired
from .resilience.errors import LaneUnavailable
from .resilience.lanes import BoundedLane, WeightedFairLane
from .resilience.qos import qos_from_config
from .resilience.shutdown import join_and_reap
from .telemetry import flightrec
from .telemetry import timeline as _timeline

__all__ = [
    "RequestBatcher", "HybridSampler", "InferenceServer",
    "InferenceServer_Debug", "ServingRequest", "calibrate_threshold",
]

_STOP = object()

# named fault-injection call sites (no-ops unless a chaos plan is
# installed — one module-global read + None check per fire)
_CHAOS_DEVICE = chaos.point("serving.device_lane")
_CHAOS_CPU = chaos.point("serving.cpu_lane")
_CHAOS_SAMPLER = chaos.point("serving.hybrid_sampler")


@dataclass
class ServingRequest:
    ids: np.ndarray
    client: int
    seq: int
    t_enqueue: float = field(default_factory=time.perf_counter)
    # flight-recorder trace context; None when telemetry is off (every
    # consumer guards, so the None threads through the pipeline for free)
    trace: Optional[object] = None
    # absolute perf_counter deadline; defaults from
    # config.serving_deadline_ms (None = no deadline, checks are free)
    deadline: Optional[float] = None
    # admission-control ordering: under overload the BoundedLanes shed
    # strictly-lower-priority requests first
    priority: int = 0
    # graph version at admission (streaming deployments; None without a
    # StreamingGraph).  The consistency contract is stated against it:
    # the batch serving this request samples a snapshot with
    # version >= graph_version (snapshots only move forward)
    graph_version: Optional[int] = None
    # tenant label as the client sent it (None = untenanted).  QoS
    # admission resolves it through the configured class allowlist and
    # stamps the resolved class on ``tenant_class`` — metrics and fair
    # scheduling only ever see allowlisted class names.
    tenant: Optional[str] = None
    tenant_class: Optional[str] = None

    def __post_init__(self):
        if self.deadline is None:
            self.deadline = deadline_for(self.t_enqueue)
        if self.graph_version is None:
            self.graph_version = flightrec.graph_version()
        if self.trace is None:
            self.trace = flightrec.new_trace()
            if self.trace is not None:
                self.trace.add("enqueue", {"n_ids": int(len(self.ids)),
                                           "client": self.client,
                                           "seq": self.seq})
        if self.trace is not None and self.tenant is not None:
            self.trace.tenant = self.tenant
        if self.trace is not None and _timeline._ON:
            # the admission instant anchors this request's trace_id on
            # the unified timeline; stage slices and the final
            # "request" span (recorder.finish) share it
            _timeline.emit("request.enqueue", cat="serving",
                           attrs={"n_ids": int(len(self.ids)),
                                  "client": self.client},
                           trace=self.trace)

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (now if now is not None else time.perf_counter()) \
            >= self.deadline


def _fail_request(req, exc, lane: str, result_queue) -> None:
    """Shared error answer: retained flight record (reason=error) plus
    the typed ``(req, exc)`` tuple on the result queue when one is in
    scope — a failed request is reported, never swallowed."""
    tr = getattr(req, "trace", None)
    if tr is not None:
        tr.add("error", {"type": type(exc).__name__, "message": str(exc)})
        e2e = max(time.perf_counter() - req.t_enqueue, 0.0)
        flightrec.get_recorder().finish(tr, e2e, status="error", lane=lane)
    if result_queue is not None:
        result_queue.put((req, exc))


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class RequestBatcher:
    """Route per-client request streams to the CPU or TPU lane.

    Args:
      stream_queues: input queues, one per client.
      neighbour_num: ``[N]`` expected expansion per node (from
        :func:`quiver_tpu.generate_neighbour_num`).
      threshold: requests with ``sum(neighbour_num[ids]) <= threshold`` go
        to the CPU lane (mode="Auto"), mirroring ``auto_despatch``
        (serving.py:72-95).
      mode: "Auto" | "CPU" | "Device" | "Preparation" (duplicate to both,
        parity serving.py:60-70).
      result_queue: where shed/rejected requests are *answered*.  When
        given, the two lane queues become
        :class:`~quiver_tpu.resilience.BoundedLane`s
        (``config.serving_queue_depth`` capacity, watermark shedding)
        and expired requests are shed at routing; without it the lanes
        stay unbounded and nothing is shed here (there would be no way
        to answer).
      qos: a :class:`~quiver_tpu.resilience.QoSController`, or None to
        resolve from config (``qos_enabled``).  With QoS active, every
        request passes token-bucket admission here (over-quota tenants
        get a typed :class:`~quiver_tpu.resilience.QuotaExceeded`
        answer) and the bounded lanes become
        :class:`~quiver_tpu.resilience.WeightedFairLane`s scheduling
        tenant classes by weight.
    """

    def __init__(self, stream_queues: List["queue.Queue"],
                 neighbour_num: Optional[np.ndarray] = None,
                 threshold: float = 0.0, mode: str = "Auto",
                 result_queue: Optional["queue.Queue"] = None,
                 qos=None):
        assert mode in ("Auto", "CPU", "Device", "Preparation")
        self.stream_queues = stream_queues
        self.neighbour_num = neighbour_num
        self.threshold = threshold
        self.mode = mode
        self.result_queue = result_queue
        self._qos = qos if qos is not None else qos_from_config()
        if result_queue is not None:
            from .config import get_config

            depth = get_config().serving_queue_depth
        else:
            depth = 0
        if depth > 0 and self._qos is not None:
            weights = self._qos.weights()
            default = self._qos.default
            self.cpu_batched_queue = WeightedFairLane(
                "cpu", weights, default_class=default,
                result_queue=result_queue)
            self.device_batched_queue = WeightedFairLane(
                "device", weights, default_class=default,
                result_queue=result_queue)
        elif depth > 0:
            self.cpu_batched_queue = BoundedLane(
                "cpu", result_queue=result_queue)
            self.device_batched_queue = BoundedLane(
                "device", result_queue=result_queue)
        else:
            self.cpu_batched_queue = queue.Queue()
            self.device_batched_queue = queue.Queue()
        self._threads: List[threading.Thread] = []

    def _route(self, req: ServingRequest):
        if shed_if_expired(req, self.result_queue, "batcher"):
            return
        q = self._qos
        if q is not None and not q.admit(req, self.result_queue):
            return
        if q is not None and q.route_floor_to_cpu and self.mode == "Auto" \
                and req.tenant_class == q.floor:
            # degradation ladder L3: the lowest class rides the CPU
            # lane so the device batch stays clear for paying tiers
            self._put(self.cpu_batched_queue, req, "cpu")
            return
        if self.mode == "CPU":
            self._put(self.cpu_batched_queue, req, "cpu")
        elif self.mode == "Device":
            self._put(self.device_batched_queue, req, "device")
        elif self.mode == "Preparation":
            self._put(self.cpu_batched_queue, req, "both")
            self.device_batched_queue.put(req)
        else:
            load = (
                float(self.neighbour_num[req.ids].sum())
                if self.neighbour_num is not None else float("inf")
            )
            if load <= self.threshold:
                self._put(self.cpu_batched_queue, req, "cpu", load)
            else:
                self._put(self.device_batched_queue, req, "device", load)

    @staticmethod
    def _put(q: "queue.Queue", req: ServingRequest, lane: str,
             load: Optional[float] = None):
        if req.trace is not None:
            attrs = {"lane": lane}
            if load is not None and load != float("inf"):
                attrs["load"] = load
            # quiverlint: ignore[QT008] -- queue handoff orders the
            # accesses: the producer stops touching req.trace once it is
            # enqueued, and q.put/get gives the worker a happens-before
            req.trace.add("route", attrs)
        q.put(req)

    def _worker(self, q: "queue.Queue"):
        while True:
            item = q.get()
            if item is _STOP:
                break
            try:
                if not isinstance(item, ServingRequest):
                    item = ServingRequest(ids=np.asarray(item),
                                          client=-1, seq=-1)
                self._route(item)
            except Exception as e:  # noqa: BLE001 — stream must survive
                # a malformed payload (np.asarray raising, a broken ids
                # dtype) used to kill this stream thread silently; now
                # it is rejected and the thread keeps draining
                self._reject(item, e)

    def _reject(self, item, exc) -> None:
        """Answer + account one unroutable payload: tick
        ``serving_rejected_total``, retain a ``rejected`` flight record,
        and answer on the result queue when the payload got far enough
        to be answerable."""
        req = item if isinstance(item, ServingRequest) else None
        tenant = getattr(req, "tenant_class", None)
        if tenant is not None:  # QoS-admitted: label by class (bounded)
            telemetry.counter("serving_rejected_total", tenant=tenant).inc()
        else:
            telemetry.counter("serving_rejected_total").inc()
        tr = req.trace if req is not None else flightrec.new_trace()
        if tr is not None:
            tr.add("reject", {"type": type(exc).__name__,
                              "message": str(exc),
                              "payload": type(item).__name__})
            t0 = req.t_enqueue if req is not None else tr.t_start
            flightrec.get_recorder().finish(
                tr, max(time.perf_counter() - t0, 0.0),
                status="rejected", lane="batcher")
        if req is not None and self.result_queue is not None:
            self.result_queue.put((req, exc))

    def start(self):
        for q in self.stream_queues:
            t = threading.Thread(target=self._worker, args=(q,), daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self):
        """Drain the stream threads; leaked (wedged) threads are logged
        and ticked on ``serving_thread_leak_total`` instead of being
        silently abandoned."""
        for q in self.stream_queues:
            q.put(_STOP)
        leaked = join_and_reap(self._threads, timeout=5.0,
                               component="batcher")
        self.cpu_batched_queue.put(_STOP)
        self.device_batched_queue.put(_STOP)
        return leaked


class HybridSampler:
    """CPU-lane sampler workers (parity: serving.py:101-147).

    Pulls requests from the batcher's CPU queue, samples with the native
    host sampler, pushes ``(request, SampledBatch, sample_time)`` to
    ``sampled_queue``.

    Requests are padded to the serving buckets BEFORE sampling: the
    native sampler's output shapes are a fixed function of the seed
    count, so bucketing here means the downstream device forward sees
    only |buckets| distinct shapes (per-request shapes would compile a
    fresh executable each — the CUDA reference has no such concern,
    serving.py:132).  ``InferenceServer`` slices results back to the true
    request length.
    """

    def __init__(self, cpu_sampler, cpu_batched_queue: "queue.Queue",
                 num_workers: int = 2, buckets: Optional[Sequence] = None,
                 feature=None,
                 result_queue: Optional["queue.Queue"] = None):
        self.sampler = cpu_sampler
        self.inq = cpu_batched_queue
        # deadline sheds and sampler failures are answered here (None:
        # expired items flow through for the server to shed)
        self.result_queue = result_queue
        self.sampled_queue: "queue.Queue" = queue.Queue()
        self.num_workers = num_workers
        # optional lookahead: stage the sampled batch's feature rows on
        # the prefetch pool while the item waits for the CPU-lane server
        # thread — overlaps H2D with queue time, and the prefetch worker
        # attributes its work to this request's trace
        self.feature = feature
        if buckets is None:
            from .config import get_config

            buckets = tuple(get_config().serving_buckets)
        self.buckets = tuple(buckets)
        self._threads: List[threading.Thread] = []

    def _pad(self, ids: np.ndarray) -> np.ndarray:
        b = _next_bucket(len(ids), self.buckets)
        if len(ids) >= b:
            return ids
        return np.concatenate([ids, np.full(b - len(ids), ids[0] if
                                            len(ids) else 0,
                                            dtype=ids.dtype)])

    def _loop(self):
        while True:
            item = self.inq.get()
            if item is _STOP:
                self.inq.put(_STOP)  # let siblings see it too
                break
            if shed_if_expired(item, self.result_queue, "sampler"):
                continue
            t0 = time.perf_counter()
            try:
                with flightrec.activate(item.trace):
                    _CHAOS_SAMPLER()
                    batch = self.sampler.sample(
                        self._pad(np.asarray(item.ids)))
                    dt = time.perf_counter() - t0
                    if flightrec.tracing():
                        flightrec.event("sample", {
                            "seconds": dt,
                            "n_id": int(batch.n_id.shape[0])})
                    if self.feature is not None:
                        self.feature.prefetch(batch.n_id)
            except Exception as e:  # noqa: BLE001 — worker must survive
                telemetry.counter("serving_requests_total",
                                  lane="cpu", status="error").inc()
                _fail_request(item, e, "sampler", self.result_queue)
                continue
            self.sampled_queue.put((item, batch, dt))

    def start(self):
        for _ in range(self.num_workers):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self):
        self.inq.put(_STOP)
        leaked = join_and_reap(self._threads, timeout=5.0,
                               component="sampler")
        self.sampled_queue.put(_STOP)
        return leaked


class InferenceServer:
    """Device stage: sample (TPU lane) → gather → model → result queue.

    Parity: serving.py:150-296.  One device thread drives the TPU with
    bucketed shapes; CPU-lane pre-sampled batches share the same forward.
    ``apply_fn(params, x, blocks)`` is the jitted model forward.
    """

    # lock discipline (enforced by quiverlint QT003): the fused-executable
    # cache is filled lazily from whichever worker thread first sees a
    # bucket size, so every write must hold ``_lock``
    _guarded_by = {"_fused_fns": "_lock"}

    BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048)

    def __init__(self, tpu_sampler, feature, apply_fn: Callable, params,
                 device_batched_queue: "queue.Queue",
                 cpu_sampled_queue: Optional["queue.Queue"] = None,
                 result_queue: Optional["queue.Queue"] = None,
                 max_coalesce: Optional[int] = None,
                 fused: Optional[bool] = None,
                 cpu_sampler=None, qos=None):
        self.sampler = tpu_sampler
        self.feature = feature
        self.apply_fn = apply_fn
        self.params = params
        self.device_q = device_batched_queue
        self.cpu_q = cpu_sampled_queue
        self.result_queue = result_queue or queue.Queue()
        # continuous batching (QoS only): after the non-blocking drain,
        # hold the coalesced batch open for up to this long while slots
        # remain, admitting late arrivals into the SAME device pass.
        # Executable keying is untouched — the batch still pads to one
        # of the pre-compiled buckets, so steady-state retraces stay 0.
        self._qos = qos if qos is not None else qos_from_config()
        if self._qos is not None:
            from .config import get_config as _gc

            self._admit_window_s = float(_gc().qos_admit_window_ms) / 1e3
        else:
            self._admit_window_s = 0.0
        # failover route for device-lane requests when the device lane
        # fails or its breaker opens: an inline sample on the CPU
        # sampler + the shared presampled forward.  None = no route
        # (failed device requests are answered with the error, the
        # pre-resilience behaviour).
        self.cpu_sampler = cpu_sampler
        # per-lane circuit breakers (config-driven thresholds; tests
        # swap in instances with injected clocks)
        self._breakers = {"device": CircuitBreaker("serving.device"),
                          "cpu": CircuitBreaker("serving.cpu")}
        if max_coalesce is None:
            from .config import get_config

            cfg = get_config()
            max_coalesce = cfg.max_coalesce
            self.BUCKETS = tuple(cfg.serving_buckets)
        self.max_coalesce = max_coalesce
        # fused device lane: sample + gather + forward in ONE jit per
        # bucket — no host hop between stages (the reference pays three
        # kernel launches + a python step between each; TPU pays three
        # dispatches AND a blocking n_id readback unless fused).  Needs
        # the feature fully HBM-resident, like the fused train pipeline.
        if fused is None:
            fused = (getattr(feature, "node_count", 0) > 0
                     and feature.cache_count >= feature.node_count
                     and getattr(tpu_sampler, "mode", "TPU") == "TPU")
        self._fused = fused
        if not fused:
            self._maybe_enable_cold_cache(feature)
        from .recovery.registry import program_cache

        self._fused_fns = program_cache("serving", owner=self)
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self._stopped = threading.Event()

    @staticmethod
    def _maybe_enable_cold_cache(feature):
        """Attach the HBM cold-row overlay to budgeted features in the
        unfused lane: recurring serving requests keep re-touching the
        same cold rows, which otherwise cross the host link every
        request (docs/FEATURE_CACHE.md).  Heuristic sizing via
        ``enable_cold_cache()`` defaults; ``cold_cache_size="off"`` (or
        ``0``/``none``) in config vetoes."""
        if (getattr(feature, "node_count", 0) <= 0
                or feature.cache_count >= feature.node_count
                or getattr(feature, "cold_cache", None) is not None
                or not hasattr(feature, "enable_cold_cache")):
            return
        from .config import get_config

        if str(get_config().cold_cache_size).lower() in ("0", "off",
                                                         "none"):
            return
        feature.enable_cold_cache()

    # -- core per-request paths ---------------------------------------
    # quiverlint: bucketed[every result length is drawn from BUCKETS]
    def _pad_ids(self, ids: np.ndarray) -> np.ndarray:
        b = _next_bucket(len(ids), self.BUCKETS)
        if len(ids) >= b:  # at the top bucket exactly (chunking caps len)
            return ids
        return np.concatenate([ids, np.full(b - len(ids), ids[0] if len(ids)
                                            else 0, dtype=ids.dtype)])

    def _run_bucketed(self, ids: np.ndarray,
                      stages: Optional[dict] = None) -> np.ndarray:
        """One padded device pass per <=top-bucket chunk.

        Requests above the top bucket are CHUNKED into top-bucket pieces so
        every device program is one of the |BUCKETS| pre-compiled shapes —
        an unbounded request size never triggers a fresh compile (the
        reference has no analogue: CUDA kernels take any shape; XLA
        executables don't).

        ``stages``: optional dict accumulating per-stage wall seconds
        (``sample`` / ``gather`` / ``infer``).  The stamps are
        consecutive so the stage intervals partition this call's wall
        time exactly; the final ``np.asarray`` host sync is charged to
        ``infer`` (XLA dispatch is async — per-stage attribution of the
        *device* time needs a profiler, not wall clocks).  Warmup passes
        no dict and so never pollutes request metrics.
        """
        top = self.BUCKETS[-1]
        outs = []
        for off in range(0, max(len(ids), 1), top):  # empty ids: one
            # zero-length chunk, padded to the smallest bucket
            chunk = ids[off: off + top]
            padded = self._pad_ids(chunk)
            if self._fused:
                t0 = time.perf_counter()
                # dispatch must stay async: the readback below is the
                # ONE sanctioned sync point per chunk
                with no_sync("serving device loop"):
                    out = self._fused_forward(padded)
                # quiverlint: sync-ok[response boundary: one transfer per chunk]
                outs.append(np.asarray(out)[: len(chunk)])
                if stages is not None:  # one jit: stages are fused too
                    dt = time.perf_counter() - t0
                    stages["infer"] = stages.get("infer", 0.0) + dt
                    if flightrec.tracing():
                        flightrec.event("infer", {"seconds": dt,
                                                  "fused": True})
            else:
                t0 = time.perf_counter()
                batch = self.sampler.sample(padded)
                t1 = time.perf_counter()
                x = self.feature[np.asarray(batch.n_id)]
                t2 = time.perf_counter()
                out = self.apply_fn(self.params, x, batch.layers)
                outs.append(np.asarray(out)[: len(chunk)])  # sync point
                t3 = time.perf_counter()
                if stages is not None:
                    stages["sample"] = stages.get("sample", 0.0) + t1 - t0
                    stages["gather"] = stages.get("gather", 0.0) + t2 - t1
                    stages["infer"] = stages.get("infer", 0.0) + t3 - t2
                    if flightrec.tracing():
                        flightrec.event("sample", {"seconds": t1 - t0})
                        flightrec.event("gather", {"seconds": t2 - t1})
                        flightrec.event("infer", {"seconds": t3 - t2})
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def _fused_forward(self, padded_ids: np.ndarray):
        """One jit per bucket size: sample -> gather -> model, no host
        round-trips between the stages."""
        import jax
        import jax.numpy as jnp

        from .feature import _lookup_tables
        from .sampler import run_pipeline
        from .utils.rng import make_key

        B = len(padded_ids)
        fn = self._fused_fns.get(B)
        if fn is None:
            s = self.sampler
            sizes = tuple(s.sizes)
            caps = tuple(s.frontier_caps)
            dedup, gm = s.dedup, s.gather_mode
            srng = s.sample_rng
            apply_fn = self.apply_fn

            @jax.jit
            def fn(tables, params, seeds, key):
                indptr, indices, cw, feat_tables = tables
                n_id, _, _, blocks, _, _ = run_pipeline(
                    dedup, indptr, indices, seeds, key, sizes, caps,
                    gather_mode=gm, cum_weights=cw, sample_rng=srng)
                x = _lookup_tables(feat_tables, n_id)
                return apply_fn(params, x, blocks)

            # double-checked: the unlocked .get() above is the fast path;
            # two threads racing a cold bucket both build, setdefault
            # keeps exactly one (compile is lazy, losing a build is cheap)
            with self._lock:
                fn = self._fused_fns.setdefault(B, fn)
        return fn(self._fused_tables(), self.params,
                  jnp.asarray(padded_ids, jnp.int32),
                  make_key(np.random.randint(0, 2**31 - 1)))

    def _fused_tables(self):
        """Graph, weights (weighted samplers stay weighted here) and
        feature tables, handed to every bucket's program as ARGUMENTS:
        captured by the jitted closure they would be baked into EVERY
        bucket's executable as constants — |BUCKETS| more copies of them
        in HBM."""
        s = self.sampler
        indptr, indices = s.csr_topo.to_device(s.device)
        return (indptr, indices, s._cum_weights,
                self.feature._device_tables())

    def warmup(self, example_node: int = 0):
        """Compile every bucket's executable before traffic arrives.

        The reference pays no warmup (CUDA shape-polymorphism); on TPU a
        cold bucket would stall its first request for the ~seconds-long
        compile, wrecking p99 — so serve only after this returns.
        """
        for b in self.BUCKETS:
            ids = np.full(b, example_node, dtype=np.int64)
            self._run_bucketed(ids)
        if hasattr(self.feature, "warm_executables"):
            # mesh-sharded feature stores pre-build their collective
            # gather ladder too — steady-state serving must trace 0
            self.feature.warm_executables()
        return self

    def _infer_device(self, req: ServingRequest):
        ids = np.asarray(req.ids)
        return self._run_bucketed(ids)[: len(ids)]

    def _infer_presampled(self, req: ServingRequest, batch,
                          stages: Optional[dict] = None):
        t0 = time.perf_counter()
        x = self.feature[np.asarray(batch.n_id)]
        t1 = time.perf_counter()
        out = self.apply_fn(self.params, x, batch.layers)
        out = np.asarray(out)[: len(req.ids)]  # sync point
        t2 = time.perf_counter()
        if stages is not None:
            stages["gather"] = stages.get("gather", 0.0) + t1 - t0
            stages["infer"] = stages.get("infer", 0.0) + t2 - t1
            if flightrec.tracing():
                flightrec.event("gather", {"seconds": t1 - t0})
                flightrec.event("infer", {"seconds": t2 - t1})
        return out

    def _drain_coalesce(self, first: ServingRequest):
        """Pull queued requests (non-blocking) to batch one device pass —
        under load many small requests share a single bucketed forward,
        which is where the TPU's throughput lives.

        With QoS active the drain gains a bounded *admit window*
        (``config.qos_admit_window_ms``): once the queue runs dry with
        slots still free, block briefly for late arrivals instead of
        launching a mostly-empty pass — continuous batching.  The
        window never extends past the first member's deadline headroom,
        and a disabled QoS pays exactly one attribute check."""
        reqs = [first]
        budget = self.BUCKETS[-1] - len(first.ids)
        window = self._admit_window_s
        t_close = time.perf_counter() + window if window > 0 else 0.0
        while len(reqs) < self.max_coalesce and budget > 0:
            try:
                item = self.device_q.get_nowait()
            except queue.Empty:
                if window <= 0:
                    break
                left = t_close - time.perf_counter()
                if first.deadline is not None:
                    left = min(left, first.deadline - time.perf_counter())
                if left <= 0:
                    break
                try:
                    item = self.device_q.get(timeout=left)
                except queue.Empty:
                    break
            if item is _STOP:
                self.device_q.put(_STOP)  # re-post for the loop to see
                break
            if len(item.ids) > budget:
                self.device_q.put(item)
                break
            reqs.append(item)
            budget -= len(item.ids)
        return reqs

    def _infer_coalesced(self, reqs, stages: Optional[dict] = None):
        ids = np.concatenate([np.asarray(r.ids) for r in reqs])
        out = self._run_bucketed(ids, stages)
        off = 0
        outs = []
        for r in reqs:
            outs.append(out[off: off + len(r.ids)])
            off += len(r.ids)
        return outs

    # -- loops ---------------------------------------------------------
    # Unlike the reference's bare `while 1` loops (serving.py:198-230 —
    # one bad request kills the worker process), a failed request is
    # reported on the result queue and the lane keeps serving.
    def _device_loop(self):
        while not self._stopped.is_set():
            item = self.device_q.get()
            if item is _STOP:
                break
            reqs = (
                self._drain_coalesce(item) if self.max_coalesce > 1
                else [item]
            )
            # stage-boundary deadline check: requests that aged out on
            # the queue are shed (answered) before burning device time
            reqs = [r for r in reqs
                    if not shed_if_expired(r, self.result_queue, "device")]
            if not reqs:
                continue
            br = self._breakers["device"]
            if not br.allow():
                self._failover(reqs, "device", None)
                continue
            # dequeue stamp AFTER coalescing: queue_wait covers time on
            # the queue plus the drain, so the per-request intervals
            # (queue_wait + stages) still partition end-to-end latency
            t_deq = time.perf_counter()
            stages: dict = {}
            # a coalesced batch activates EVERY member's trace: they all
            # wait for this device pass, so they all own its events
            # (trace is None for all members when telemetry is off, and
            # activate(None) is the shared no-op)
            act = (flightrec.activate([r.trace for r in reqs])
                   if reqs[0].trace is not None else flightrec.activate(None))
            # ambient deadline for callees without a request in hand
            # (dist feature degraded lookups): the batch's tightest one
            dls = [r.deadline for r in reqs if r.deadline is not None]
            scope = deadline_scope(min(dls) if dls else None,
                                   min(r.t_enqueue for r in reqs))
            try:
                with act, scope:
                    if flightrec.tracing():
                        flightrec.event("dequeue",
                                        {"coalesced": len(reqs)})
                    _CHAOS_DEVICE()
                    outs = self._infer_coalesced(reqs, stages)
                br.record_success()
                t_done = time.perf_counter()
                for r, o in zip(reqs, outs):
                    self._finish(r, o, lane="device", stages=stages,
                                 t_dequeue=t_deq, t_done=t_done)
            except Exception as e:  # noqa: BLE001 — lane must survive
                br.record_failure()
                self._failover(reqs, "device", e)

    def _cpu_loop(self):
        while not self._stopped.is_set():
            item = self.cpu_q.get()
            if item is _STOP:
                break
            req, batch, sample_dt = item
            if shed_if_expired(req, self.result_queue, "cpu"):
                continue
            br = self._breakers["cpu"]
            if not br.allow():
                self._failover([req], "cpu", None)
                continue
            stages = {"sample": float(sample_dt)}
            scope = deadline_scope(req.deadline, req.t_enqueue)
            try:
                with flightrec.activate(req.trace), scope:
                    _CHAOS_CPU()
                    out = self._infer_presampled(req, batch, stages)
                br.record_success()
                t_done = time.perf_counter()
                self._finish(req, out, lane="cpu", stages=stages,
                             t_done=t_done)
            except Exception as e:  # noqa: BLE001 — lane must survive
                br.record_failure()
                self._failover([req], "cpu", e)

    # -- failover -------------------------------------------------------
    def _failover(self, reqs, lane: str, error: Optional[Exception]):
        """Reroute requests off a failed (or breaker-open) lane.  Every
        request is ANSWERED: rerouted and finished, or — when no route
        exists / the reroute itself fails — errored on the result queue.
        ``error`` is the primary-lane failure (None when the breaker
        shorted the attempt)."""
        for r in reqs:
            if shed_if_expired(r, self.result_queue, lane):
                continue
            try:
                done = (self._failover_via_cpu(r) if lane == "device"
                        else self._failover_via_device(r))
            except Exception as e:  # noqa: BLE001 — failover can fail too
                self._answer_error(r, e, "failover")
                continue
            if not done:
                self._answer_error(
                    r, error if error is not None else LaneUnavailable(lane),
                    lane)

    def _failover_via_cpu(self, req) -> bool:
        """Serve one device-lane request inline on the CPU sampler lane.
        False when no ``cpu_sampler`` was wired (no route)."""
        if self.cpu_sampler is None:
            return False
        stages: dict = {}
        with flightrec.activate(req.trace):
            if flightrec.tracing():
                flightrec.event("failover", {"from": "device", "to": "cpu"})
            ids = np.asarray(req.ids)
            t0 = time.perf_counter()
            padded = self._pad_ids(ids) if len(ids) <= self.BUCKETS[-1] \
                else ids
            batch = self.cpu_sampler.sample(padded)
            stages["sample"] = time.perf_counter() - t0
            out = self._infer_presampled(req, batch, stages)
        telemetry.counter("serving_failover_total",
                          direction="device_to_cpu").inc()
        self._finish(req, out, lane="failover", stages=stages,
                     t_done=time.perf_counter())
        return True

    def _failover_via_device(self, req) -> bool:
        """Serve one CPU-lane request via the bucketed device forward.
        False when the device breaker refuses it (no route)."""
        if not self._breakers["device"].allow():
            return False
        stages: dict = {}
        with flightrec.activate(req.trace):
            if flightrec.tracing():
                flightrec.event("failover", {"from": "cpu", "to": "device"})
            ids = np.asarray(req.ids)
            out = self._run_bucketed(ids, stages)[: len(ids)]
        telemetry.counter("serving_failover_total",
                          direction="cpu_to_device").inc()
        self._finish(req, out, lane="failover", stages=stages,
                     t_done=time.perf_counter())
        return True

    def _answer_error(self, req, exc, lane: str):
        telemetry.counter("serving_requests_total", lane=lane,
                          status="error").inc()
        self._finish_error(req, exc, lane=lane)
        self.result_queue.put((req, exc))

    def _finish(self, req, out, lane: str = "device",
                stages: Optional[dict] = None,
                t_dequeue: Optional[float] = None,
                t_done: Optional[float] = None):
        self._record_request(req, lane, stages or {}, t_dequeue, t_done)
        self.result_queue.put((req, out))

    def _finish_error(self, req, exc, lane: str):
        """Error-path retention: a failed request is always kept by the
        flight recorder (reason=error), with the exception on its log."""
        tr = getattr(req, "trace", None)
        if tr is None:
            return
        tr.add("error", {"type": type(exc).__name__, "message": str(exc)})
        e2e = max(time.perf_counter() - req.t_enqueue, 0.0)
        flightrec.get_recorder().finish(tr, e2e, status="error", lane=lane)

    def _record_request(self, req, lane, stages, t_dequeue, t_done):
        """Fold one served request into the registry.  Returns
        ``(e2e_seconds, full_stage_dict)`` so the Debug subclass can
        reuse the exact same numbers for its local accounting.

        ``queue_wait`` is the dequeue stamp minus the enqueue stamp when
        the lane observed one (device lane), else the residual of the
        measured stages against end-to-end (CPU lane, whose ``sample``
        happened inside HybridSampler before this server saw the item).
        Either way ``sum(stages) ≈ e2e``.
        """
        now = t_done if t_done is not None else time.perf_counter()
        e2e = max(now - req.t_enqueue, 0.0)
        full = dict(stages)
        if t_dequeue is not None:
            full["queue_wait"] = max(t_dequeue - req.t_enqueue, 0.0)
        else:
            full["queue_wait"] = max(e2e - sum(full.values()), 0.0)
        telemetry.counter("serving_requests_total", lane=lane,
                          status="ok").inc()
        telemetry.histogram("serving_request_seconds", lane=lane).observe(e2e)
        for stage, dt in full.items():
            telemetry.histogram("serving_stage_seconds", lane=lane,
                                stage=stage).observe(dt)
        tr = getattr(req, "trace", None)
        if tr is not None:
            tr.add("finish", {"lane": lane})
            flightrec.get_recorder().finish(tr, e2e, status="ok", lane=lane,
                                            stages=full)
        return e2e, full

    def expose_metrics(self, port: int = 0, host: str = "127.0.0.1"):
        """Start the stdlib HTTP metrics endpoint (/metrics,
        /metrics.json, /trace.json) for this process' registry.  Lazy
        import: serving has no hard dependency on the exporter."""
        from .telemetry.export import start_http_server

        self._metrics_server = start_http_server(port=port, host=host)
        return self._metrics_server

    def start_slo_watchdog(self):
        """Start the process-wide SLO watchdog thread (objectives from
        config).  Explicit by design: a background evaluator should not
        appear as a side effect of constructing a server.  Stopped with
        the server."""
        from .telemetry.slo import get_watchdog

        self._slo_watchdog = get_watchdog().start()
        return self._slo_watchdog

    def start(self):
        t = threading.Thread(target=self._device_loop, daemon=True)
        t.start()
        self._threads.append(t)
        if self.cpu_q is not None:
            t2 = threading.Thread(target=self._cpu_loop, daemon=True)
            t2.start()
            self._threads.append(t2)
        return self

    def stop(self):
        self._stopped.set()
        self.device_q.put(_STOP)
        if self.cpu_q is not None:
            self.cpu_q.put(_STOP)
        leaked = join_and_reap(self._threads, timeout=10.0,
                               component="server")
        srv = getattr(self, "_metrics_server", None)
        if srv is not None:
            srv.close()
            self._metrics_server = None
        wd = getattr(self, "_slo_watchdog", None)
        if wd is not None:
            wd.stop()
            self._slo_watchdog = None
        return leaked


def calibrate_threshold(tpu_sampler, cpu_sampler, feature, apply_fn, params,
                        neighbour_num: np.ndarray, node_count: int,
                        trials: int = 8, sizes=(1, 4, 16, 64),
                        seed: int = 0) -> float:
    """Measure both lanes and return the ``neighbour_num``-sum threshold
    below which the CPU lane is faster.

    This automates what the reference's ``Preparation`` mode collects
    manually (serving.py:60-70 duplicates traffic to both lanes so an
    operator can pick a threshold).  Returns a load value usable directly
    as ``RequestBatcher(threshold=...)``.
    """
    import time as _time

    rng = np.random.default_rng(seed)
    points = []  # (load, cpu_dt, tpu_dt)
    for sz in sizes:
        for _ in range(trials):
            ids = rng.integers(0, node_count, sz)
            load = float(neighbour_num[ids].sum())
            t0 = _time.perf_counter()
            b = cpu_sampler.sample(ids)
            x = feature[np.asarray(b.n_id)]
            np.asarray(apply_fn(params, x, b.layers))
            cpu_dt = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            b = tpu_sampler.sample(ids)
            x = feature[np.asarray(b.n_id)]
            np.asarray(apply_fn(params, x, b.layers))
            tpu_dt = _time.perf_counter() - t0
            points.append((load, cpu_dt, tpu_dt))
    return _fit_crossover(points)


def _fit_crossover(points) -> float:
    """Threshold from timing points ``(load, cpu_dt, device_dt)``.

    Fit the crossover instead of keeping the LAST load where CPU won:
    with noisy timings past the crossover a single lucky CPU sample
    would set the threshold far too high and route heavy requests to
    the slow lane.  The threshold is the midpoint at the best split
    (below: CPU lane, at/above: device lane), the max load if CPU
    always wins, 0 if the device lane always wins.
    """
    points = sorted(points)
    if not points:
        return 0.0
    wins = [cpu_dt <= dev_dt for _, cpu_dt, dev_dt in points]
    # optimal split: the index s maximizing (#CPU wins below s) +
    # (#device wins at/after s).  Works at any sample count (a rolling
    # window degenerates to a global vote when n <= window) and a single
    # outlier on either side moves the optimum only if it outweighs the
    # consistent pattern.
    n = len(points)
    dev_wins_suffix = [0] * (n + 1)
    for i in range(n - 1, -1, -1):
        dev_wins_suffix[i] = dev_wins_suffix[i + 1] + (0 if wins[i] else 1)
    best_s, best_score, cpu_prefix = 0, dev_wins_suffix[0], 0
    for s in range(1, n + 1):
        cpu_prefix += 1 if wins[s - 1] else 0
        score = cpu_prefix + dev_wins_suffix[s]
        if score > best_score:
            best_s, best_score = s, score
    if best_s == 0:
        return 0.0
    if best_s == n:
        return points[-1][0]
    return (points[best_s - 1][0] + points[best_s][0]) / 2.0


class InferenceServer_Debug(InferenceServer):
    """Latency-instrumented server (parity: serving.py:298-360).

    ``stats()`` returns avg / p50 / p99 latency and throughput (the
    reference's tp99 harness) plus ``stage_breakdown_ms`` — per-stage
    (queue_wait / sample / gather / infer) mean and total.  Accounting
    lives on a private fixed-bucket :class:`~quiver_tpu.telemetry.Histogram`
    rather than the old unbounded per-request list: memory is O(buckets)
    under sustained traffic, p50/p99 read from bucket interpolation
    (~13% worst-case with the default ~1.26x grid), and the same numbers
    flow into the process registry via the base class.
    """

    # QT003: latency accounting is written from every worker thread via
    # _record_request; it shares the base class's ``_lock``
    _guarded_by = {"_stage_acc": "_lock", "_count": "_lock",
                   "_t_first": "_lock", "_t_last": "_lock"}

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)  # base creates self._lock
        self._hist = telemetry.Histogram("serving_debug_latency")
        self._stage_acc: dict = {}  # stage -> [count, total_s]
        self._t_first = None
        self._t_last = None
        self._count = 0

    def _record_request(self, req, lane, stages, t_dequeue, t_done):
        e2e, full = super()._record_request(req, lane, stages, t_dequeue,
                                            t_done)
        self._hist.observe(e2e)
        with self._lock:
            self._t_first = self._t_first or req.t_enqueue
            self._t_last = req.t_enqueue + e2e
            self._count += 1
            for stage, dt in full.items():
                acc = self._stage_acc.setdefault(stage, [0, 0.0])
                acc[0] += 1
                acc[1] += dt
        return e2e, full

    def flight_records(self) -> list:
        """Retained flight-recorder records (oldest first) — the tail
        of requests worth debugging: slow, errored, or flagged."""
        return flightrec.get_recorder().records()

    def stats(self) -> dict:
        with self._lock:
            n = self._count
            if n == 0:
                return dict(count=0)
            span = max((self._t_last or 0) - (self._t_first or 0), 1e-9)
            breakdown = {
                stage: dict(mean_ms=float(t / c * 1e3),
                            total_ms=float(t * 1e3))
                for stage, (c, t) in sorted(self._stage_acc.items())
            }
        return dict(
            count=int(n),
            avg_latency_ms=float(self._hist.mean * 1e3),
            p50_latency_ms=float(self._hist.percentile(50) * 1e3),
            p99_latency_ms=float(self._hist.percentile(99) * 1e3),
            throughput_rps=float(n / span),
            stage_breakdown_ms=breakdown,
        )
