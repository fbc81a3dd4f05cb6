"""Unified runtime configuration.

The reference has no config system — build-time env vars, constructor
kwargs, and hardcoded constants (SURVEY §5).  Here one small object holds
the library-wide defaults, overridable by env (``QUIVER_TPU_*``) or
programmatically (``quiver_tpu.config.update(...)``); constructors still
take explicit kwargs which always win.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Tuple

__all__ = ["Config", "get_config", "update"]


def _env(name: str, default, cast=str):
    v = os.environ.get(f"QUIVER_TPU_{name}")
    if v is None:
        return default
    if cast is bool:
        return v not in ("0", "", "false", "False")
    return cast(v)


@dataclass
class Config:
    # feature store
    cache_policy: str = field(
        default_factory=lambda: _env("CACHE_POLICY", "device_replicate")
    )
    # cold-row overlay cache (docs/FEATURE_CACHE.md): "auto" = off until
    # enable_cold_cache() / the serving auto-enable; "off"/"0" = never;
    # an explicit size ("64M", or rows under cache_unit="rows") enables
    # the overlay at feature build time
    cold_cache_size: str = field(
        default_factory=lambda: _env("COLD_CACHE_SIZE", "auto")
    )
    cold_cache_policy: str = field(
        default_factory=lambda: _env("COLD_CACHE_POLICY", "clock")
    )
    cold_cache_admit: int = field(
        default_factory=lambda: _env("COLD_CACHE_ADMIT", 2, int)
    )
    # paged feature store (docs/FEATURE_CACHE.md): "off" (default) keeps
    # the staged three-tier merge byte-identical to PR 9; "on" packs
    # feature rows into fixed-size HBM pages and serves every gather
    # through the ragged Pallas page-gather kernel.  page_rows=0 sizes
    # pages automatically (smallest row count whose page is a multiple
    # of the 512B HBM transaction, >= 4KiB); pool_pages=0 sizes the
    # OVERLAY page pool off the host-page count (docs/FEATURE_CACHE.md).
    feature_paged: str = field(
        default_factory=lambda: _env("FEATURE_PAGED", "off")
    )
    feature_page_rows: int = field(
        default_factory=lambda: _env("FEATURE_PAGE_ROWS", 0, int)
    )
    feature_page_pool: int = field(
        default_factory=lambda: _env("FEATURE_PAGE_POOL", 0, int)
    )
    # serving
    serving_buckets: Tuple[int, ...] = (
        8, 16, 32, 64, 128, 256, 512, 1024, 2048
    )
    max_coalesce: int = field(
        default_factory=lambda: _env("MAX_COALESCE", 8, int)
    )
    # resilience (docs/RESILIENCE.md): per-request deadline budget in ms
    # (0 disables deadlines entirely — checks reduce to one `is None`),
    # bounded-lane capacity + shed watermarks (fractions of capacity,
    # hysteresis: shed above high until drained below low), and the
    # per-lane circuit breaker (consecutive failures to open, seconds
    # until a half-open probe, concurrent probes admitted)
    serving_deadline_ms: float = field(
        default_factory=lambda: _env("SERVING_DEADLINE_MS", 0.0, float)
    )
    serving_queue_depth: int = field(
        default_factory=lambda: _env("SERVING_QUEUE_DEPTH", 1024, int)
    )
    serving_queue_high_watermark: float = field(
        default_factory=lambda: _env(
            "SERVING_QUEUE_HIGH_WATERMARK", 0.9, float)
    )
    serving_queue_low_watermark: float = field(
        default_factory=lambda: _env(
            "SERVING_QUEUE_LOW_WATERMARK", 0.5, float)
    )
    serving_breaker_failures: int = field(
        default_factory=lambda: _env("SERVING_BREAKER_FAILURES", 5, int)
    )
    serving_breaker_reset_s: float = field(
        default_factory=lambda: _env("SERVING_BREAKER_RESET_S", 30.0, float)
    )
    serving_breaker_probes: int = field(
        default_factory=lambda: _env("SERVING_BREAKER_PROBES", 1, int)
    )
    # multi-tenant QoS (docs/RESILIENCE.md): disabled by default — the
    # serving hot path then pays exactly one attribute check.  Tenant
    # classes are declared as "name:rate=R,burst=B,weight=W,priority=P"
    # entries joined by ";" (the class allowlist — it bounds the tenant
    # label cardinality on serving metrics); unlabeled traffic maps to
    # qos_default_tenant.  The admit window is how long the device loop
    # holds an in-flight coalesced batch open for late arrivals
    # (continuous batching); the quantum is the deficit-round-robin
    # refill in ids-per-round per unit weight.  The ladder knobs gate
    # the adaptive degradation ladder: consecutive breaching SLO ticks
    # before stepping down, consecutive healthy ticks before stepping
    # back up, and the fanout fraction applied at ladder level >= 1.
    qos_enabled: bool = field(
        default_factory=lambda: _env("QOS_ENABLED", False, bool)
    )
    qos_tenants: str = field(
        default_factory=lambda: _env(
            "QOS_TENANTS",
            "gold:rate=200,burst=50,weight=8,priority=3;"
            "silver:rate=100,burst=25,weight=4,priority=2;"
            "bronze:rate=50,burst=15,weight=2,priority=1;"
            "ingest:rate=100,burst=50,weight=1,priority=0")
    )
    qos_default_tenant: str = field(
        default_factory=lambda: _env("QOS_DEFAULT_TENANT", "bronze")
    )
    qos_ingest_tenant: str = field(
        default_factory=lambda: _env("QOS_INGEST_TENANT", "ingest")
    )
    qos_admit_window_ms: float = field(
        default_factory=lambda: _env("QOS_ADMIT_WINDOW_MS", 2.0, float)
    )
    qos_quantum: int = field(
        default_factory=lambda: _env("QOS_QUANTUM", 64, int)
    )
    qos_degrade_fanout_frac: float = field(
        default_factory=lambda: _env("QOS_DEGRADE_FANOUT_FRAC", 0.5, float)
    )
    qos_breach_ticks: int = field(
        default_factory=lambda: _env("QOS_BREACH_TICKS", 2, int)
    )
    qos_recover_ticks: int = field(
        default_factory=lambda: _env("QOS_RECOVER_TICKS", 2, int)
    )
    # flight recorder (docs/OBSERVABILITY.md): ring-buffer capacity of
    # retained request records, and the e2e latency above which an
    # otherwise-healthy request counts as "slow" and is retained
    flightrec_capacity: int = field(
        default_factory=lambda: _env("FLIGHTREC_CAPACITY", 256, int)
    )
    flightrec_slow_ms: float = field(
        default_factory=lambda: _env("FLIGHTREC_SLOW_MS", 100.0, float)
    )
    # SLO objectives (telemetry.slo): p99 e2e latency ceiling, error
    # ratio ceiling, coldcache hit-rate floor (0 disables the floor),
    # and the watchdog evaluation interval
    slo_p99_ms: float = field(
        default_factory=lambda: _env("SLO_P99_MS", 250.0, float)
    )
    slo_error_ratio: float = field(
        default_factory=lambda: _env("SLO_ERROR_RATIO", 0.01, float)
    )
    slo_coldcache_hit_floor: float = field(
        default_factory=lambda: _env("SLO_COLDCACHE_HIT_FLOOR", 0.0, float)
    )
    slo_interval_s: float = field(
        default_factory=lambda: _env("SLO_INTERVAL_S", 5.0, float)
    )
    # streaming tier (quiver_tpu.stream): delta-segment capacity before
    # ingestion blocks on compaction, compactor cadence (seconds between
    # periodic folds; the watermark triggers early when the pending
    # fraction of capacity crosses it), and the edge-update ingestion
    # lane (queue depth, its own deadline class — 0 = no deadline — and
    # shed priority relative to query traffic)
    stream_delta_capacity: int = field(
        default_factory=lambda: _env("STREAM_DELTA_CAPACITY", 65536, int)
    )
    stream_compact_interval_s: float = field(
        default_factory=lambda: _env("STREAM_COMPACT_INTERVAL_S", 30.0,
                                     float)
    )
    stream_compact_watermark: float = field(
        default_factory=lambda: _env("STREAM_COMPACT_WATERMARK", 0.75,
                                     float)
    )
    stream_ingest_depth: int = field(
        default_factory=lambda: _env("STREAM_INGEST_DEPTH", 256, int)
    )
    stream_ingest_deadline_ms: float = field(
        default_factory=lambda: _env("STREAM_INGEST_DEADLINE_MS", 0.0,
                                     float)
    )
    stream_ingest_priority: int = field(
        default_factory=lambda: _env("STREAM_INGEST_PRIORITY", 1, int)
    )
    # durability / warm restart (quiver_tpu.recovery): the root the WAL
    # and checkpoints live under ("" = volatile, no durability), the WAL
    # fsync policy ("always" | "batch" | "off") + segment/batch sizing,
    # checkpoint cadence and retention, the replay deadline (0 = none),
    # the post-seal retrace budget per subsystem (-1 = count only,
    # never raise), and the JAX persistent compilation cache directory
    # ("" = off)
    recovery_dir: str = field(
        default_factory=lambda: _env("RECOVERY_DIR", "", str)
    )
    recovery_fsync: str = field(
        default_factory=lambda: _env("RECOVERY_FSYNC", "always", str)
    )
    recovery_segment_bytes: int = field(
        default_factory=lambda: _env("RECOVERY_SEGMENT_BYTES", 4 << 20, int)
    )
    recovery_batch_bytes: int = field(
        default_factory=lambda: _env("RECOVERY_BATCH_BYTES", 1 << 16, int)
    )
    recovery_checkpoint_interval_s: float = field(
        default_factory=lambda: _env("RECOVERY_CHECKPOINT_INTERVAL_S", 60.0,
                                     float)
    )
    recovery_checkpoint_keep: int = field(
        default_factory=lambda: _env("RECOVERY_CHECKPOINT_KEEP", 2, int)
    )
    recovery_deadline_s: float = field(
        default_factory=lambda: _env("RECOVERY_DEADLINE_S", 0.0, float)
    )
    recovery_retrace_budget: int = field(
        default_factory=lambda: _env("RECOVERY_RETRACE_BUDGET", -1, int)
    )
    recovery_cache_dir: str = field(
        default_factory=lambda: _env("RECOVERY_CACHE_DIR", "", str)
    )
    # tracing
    trace: bool = field(default_factory=lambda: _env("TRACE", False, bool))
    # unified timeline (telemetry.timeline): per-thread ring capacity in
    # events — a thread past capacity overwrites its own oldest events
    # (export reports the overwrite count), so a traced soak run is
    # bounded at threads x capacity x ~100B no matter how long it runs
    timeline_ring_capacity: int = field(
        default_factory=lambda: _env("TIMELINE_RING_CAPACITY", 8192, int)
    )
    # perf-regression gate (benchmarks/perfgate.py): repeats per metric
    # (the gate compares medians-of-k), the MAD multiplier above which a
    # slowdown counts as signal, and the relative-change floor below
    # which even a statistically-clear slowdown is ignored as too small
    # to gate on
    perfgate_k: int = field(
        default_factory=lambda: _env("PERFGATE_K", 5, int)
    )
    perfgate_mad_mult: float = field(
        default_factory=lambda: _env("PERFGATE_MAD_MULT", 5.0, float)
    )
    perfgate_rel_floor: float = field(
        default_factory=lambda: _env("PERFGATE_REL_FLOOR", 0.30, float)
    )
    # replicated serving fleet (quiver_tpu/fleet, docs/FLEET.md):
    # shared membership-directory path, placement shape (partitions /
    # virtual nodes on the consistent-hash ring), liveness cadence,
    # router re-dispatch budget, the QoS priority at or above which a
    # tenant routes power-of-two-choices, per-dispatch timeout, WAL
    # shipping poll/holdback cadence, and the staleness bound (in WAL
    # records) above which a follower should not be considered current
    fleet_dir: str = field(
        default_factory=lambda: _env("FLEET_DIR", "", str)
    )
    fleet_partitions: int = field(
        default_factory=lambda: _env("FLEET_PARTITIONS", 8, int)
    )
    fleet_vnodes: int = field(
        default_factory=lambda: _env("FLEET_VNODES", 64, int)
    )
    fleet_heartbeat_s: float = field(
        default_factory=lambda: _env("FLEET_HEARTBEAT_S", 0.5, float)
    )
    fleet_heartbeat_timeout_s: float = field(
        default_factory=lambda: _env("FLEET_HEARTBEAT_TIMEOUT_S", 3.0,
                                     float)
    )
    fleet_route_retries: int = field(
        default_factory=lambda: _env("FLEET_ROUTE_RETRIES", 2, int)
    )
    fleet_hot_priority: int = field(
        default_factory=lambda: _env("FLEET_HOT_PRIORITY", 3, int)
    )
    fleet_request_timeout_s: float = field(
        default_factory=lambda: _env("FLEET_REQUEST_TIMEOUT_S", 1.0,
                                     float)
    )
    fleet_ship_poll_ms: float = field(
        default_factory=lambda: _env("FLEET_SHIP_POLL_MS", 20.0, float)
    )
    fleet_ship_grace_ms: float = field(
        default_factory=lambda: _env("FLEET_SHIP_GRACE_MS", 250.0, float)
    )
    fleet_max_staleness_lsn: int = field(
        default_factory=lambda: _env("FLEET_MAX_STALENESS_LSN", 1024, int)
    )
    # fleet observability plane (quiver_tpu/fleet/federation.py,
    # docs/OBSERVABILITY.md): master switch for cross-process trace
    # propagation + metrics federation (off by default — the request
    # path pays exactly one config check when off), scraper cadence,
    # router hop-record ring capacity, and the eligible-replica floor
    # the fleet SLO watchdog alarms on
    fleet_federation: str = field(
        default_factory=lambda: _env("FLEET_FEDERATION", "off", str)
    )
    fleet_scrape_interval_s: float = field(
        default_factory=lambda: _env("FLEET_SCRAPE_INTERVAL_S", 0.5, float)
    )
    fleet_trace_ring: int = field(
        default_factory=lambda: _env("FLEET_TRACE_RING", 512, int)
    )
    fleet_min_eligible: int = field(
        default_factory=lambda: _env("FLEET_MIN_ELIGIBLE", 1, int)
    )
    # fleet autonomy (quiver_tpu/fleet/{election,walstream,autoscaler},
    # docs/FLEET.md): all three subsystems are OFF by default and the
    # off path is byte-identical — no threads, no metric keys, one
    # config-string check at construction time.
    #   election   — fenced leader auto-failover: followers race to
    #                claim an epoch-stamped leadership record when the
    #                leader's heartbeat expires; the epoch fences every
    #                WAL append / membership write of a deposed leader
    fleet_election: str = field(
        default_factory=lambda: _env("FLEET_ELECTION", "off", str)
    )
    fleet_election_poll_s: float = field(
        default_factory=lambda: _env("FLEET_ELECTION_POLL_S", 0.25, float)
    )
    # per-rank claim stagger: candidate rank r waits r * stagger before
    # claiming, so the most-caught-up follower wins uncontested unless
    # it too is dead (the O_EXCL claim keeps even a tie race safe)
    fleet_election_stagger_s: float = field(
        default_factory=lambda: _env("FLEET_ELECTION_STAGGER_S", 0.5,
                                     float)
    )
    # how often a fenced writer re-reads the claim directory on the
    # append path (0 = every append; tests use 0 for determinism)
    fleet_election_fence_recheck_s: float = field(
        default_factory=lambda: _env("FLEET_ELECTION_FENCE_RECHECK_S",
                                     0.05, float)
    )
    #   walstream  — leader-side socket WAL shipping (JSON-lines frame
    #                stream) so followers need no shared WAL directory
    fleet_walstream: str = field(
        default_factory=lambda: _env("FLEET_WALSTREAM", "off", str)
    )
    fleet_walstream_port: int = field(
        default_factory=lambda: _env("FLEET_WALSTREAM_PORT", 0, int)
    )
    #   autoscaler — federation-driven spawn/drain control loop with a
    #                diurnal-rate predictor, hysteresis and a cooldown
    fleet_autoscaler: str = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER", "off", str)
    )
    fleet_autoscaler_interval_s: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_INTERVAL_S", 1.0,
                                     float)
    )
    fleet_autoscaler_min: int = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_MIN", 1, int)
    )
    fleet_autoscaler_max: int = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_MAX", 8, int)
    )
    fleet_autoscaler_cooldown_s: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_COOLDOWN_S", 30.0,
                                     float)
    )
    # serving capacity one replica is planned at, in requests/second —
    # the unit the diurnal predictor's rate forecast is divided by
    fleet_autoscaler_rps_per_replica: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_RPS_PER_REPLICA",
                                     200.0, float)
    )
    # prediction lead: scale for the rate expected this many seconds
    # ahead (a warm join must complete before the ramp arrives)
    fleet_autoscaler_horizon_s: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_HORIZON_S", 10.0,
                                     float)
    )
    # hysteresis band: scale up when predicted demand exceeds
    # up_ratio * capacity, down only when it falls below down_ratio *
    # capacity-after-drain — the gap is what prevents flapping
    fleet_autoscaler_up_ratio: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_UP_RATIO", 0.8,
                                     float)
    )
    fleet_autoscaler_down_ratio: float = field(
        default_factory=lambda: _env("FLEET_AUTOSCALER_DOWN_RATIO", 0.5,
                                     float)
    )
    # mesh-native sharded serving (quiver_tpu/mesh, docs/SHARDING.md):
    # number of row-range shards one logical replica spans (0 = off; the
    # whole mesh tier is dark and every code path is byte-identical to
    # the unsharded build), the shard-group id this process announces to
    # the fleet directory, this process's shard index within the group,
    # and the per-shard overlay pool size in pages (0 = size to the
    # batch working set at build)
    mesh_shards: int = field(
        default_factory=lambda: _env("MESH_SHARDS", 0, int)
    )
    mesh_group: str = field(
        default_factory=lambda: _env("MESH_GROUP", "", str)
    )
    mesh_shard_index: int = field(
        default_factory=lambda: _env("MESH_SHARD_INDEX", 0, int)
    )
    mesh_pool_pages: int = field(
        default_factory=lambda: _env("MESH_POOL_PAGES", 0, int)
    )


_config: Optional[Config] = None


def _accelerator(backend: Optional[str]) -> bool:
    """Whether ``backend`` (``None``: the one JAX runs on) is not the
    CPU.  The tests and ``__graft_entry__.dryrun_multichip`` pass
    ``"tpu"`` to ask, with no chip attached, what a TPU resolves to."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend != "cpu"


def resolve_gather_mode(gather_mode: str,
                        backend: Optional[str] = None) -> str:
    """The hop's gather path: an explicit ``"xla"`` or ``"blocked"``
    wins, ``"auto"`` is the backend's.

    ``"xla"``: ``jnp.take`` per draw, the CPU's path and the reference
    the tests compare against.  ``"blocked"``: the window fetch of
    ``ops.blockgather`` (two 128-lane rows of ``indices`` per target
    serve its k draws; the ``[B]``-shaped ``indptr`` reads and the
    fallback's draws go through row gather + VPU lane select), the
    accelerator's path.  Measured on one TPU v5e in both cells of
    ``BENCHMARK.json`` against a 512-B row per draw, every draw the same
    to the bit (ledger, PR 31): 21,207 -> 28,517 seeds/s in
    ``papers100m-sage.train-fused`` (the hops 21.0 -> 8.6 ms of the
    step) and 10,486 -> 11,073 in ``mag240m-rgat.train-fused-typed``
    (6.6 -> 1.4 ms).  The paths that lost (a Pallas twin of the window,
    a DMA per draw, a Pallas lane select) are in the history at ab18fb6.
    """
    if gather_mode not in ("auto", "xla", "blocked"):
        raise ValueError(
            f"gather_mode must be auto | xla | blocked, got "
            f"{gather_mode!r}")
    if gather_mode != "auto":
        return gather_mode
    return "blocked" if _accelerator(backend) else "xla"


def resolve_sample_rng(sample_rng: str,
                       backend: Optional[str] = None) -> str:
    """The hop's uniform source: an explicit ``"key"`` or ``"hash"``
    wins, ``"auto"`` is the backend's: ``"hash"`` (counter-hash
    uniforms) on an accelerator, ``"key"`` (``jax.random.uniform``) on
    the CPU, where threefry is fast and tests want reproducible
    streams.  hash against threefry / rbg on the chip at a real graph
    size: not measured (ROADMAP D3) - the default is a choice, not a
    result."""
    if sample_rng not in ("auto", "key", "hash"):
        raise ValueError(f"sample_rng must be auto|key|hash, got "
                         f"{sample_rng!r}")
    if sample_rng != "auto":
        return sample_rng
    return "hash" if _accelerator(backend) else "key"


def resolve_dedup(dedup: str) -> str:
    """The frontier's dedup: an explicit ``"none"`` or ``"hop"`` wins,
    ``"auto"`` is ``"none"`` (the positional-relabel hot path) on every
    backend.  ``"hop"`` against it end to end on the chip: not measured
    (ROADMAP S2 (b)) - a choice, not a result."""
    if dedup not in ("auto", "none", "hop"):
        raise ValueError(f"dedup must be auto|none|hop, got {dedup!r}")
    return "none" if dedup == "auto" else dedup


# config is frozen once per process, so anything read off it is
# process-lifetime-finite: cache keys built from config attributes
# cannot blow up executable cardinality.
# quiverlint: bucketed[config is frozen once per process]
def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config()
        if _config.trace:
            from .utils import trace as _t

            _t.set_enabled(True)
    return _config


def update(**kwargs) -> Config:
    cfg = get_config()
    for k, v in kwargs.items():
        if not hasattr(cfg, k):
            raise AttributeError(f"unknown config field {k!r}")
        setattr(cfg, k, v)
    return cfg
