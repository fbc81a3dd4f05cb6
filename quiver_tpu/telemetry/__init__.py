"""quiver_tpu.telemetry — unified metrics + tracing for the data layer.

One process-wide :class:`MetricsRegistry` (counters / gauges /
fixed-bucket histograms, label support, mergeable snapshots) plus one
:class:`SpanTracer` (nested spans, Chrome trace-event export).  Hot
paths call the module-level helpers::

    from quiver_tpu import telemetry

    telemetry.counter("sampler_batches_total", mode="tpu").inc()
    with telemetry.histogram("feature_gather_seconds", tier="hot").time():
        ...
    with telemetry.span("sampler.sample"):
        ...

Gating: ``QUIVER_TELEMETRY=off`` (or ``0``/``false``/``no``) makes every
helper answer with a shared do-nothing singleton from :mod:`.noop` —
no locks, no clocks, no net allocations.  Default is ON: a counter inc
is sub-µs against the ms-scale batches it instruments.  Span *event
retention* (Chrome traces) stays opt-in via ``QUIVER_TPU_TRACE=1`` or
``get_tracer().set_tracing(True)`` either way.

The HTTP exporter lives in :mod:`.export` and is imported lazily —
see ``docs/OBSERVABILITY.md`` for the metric catalogue and label
conventions.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

from . import noop as _noop
from .device_scopes import device_scopes, register_program
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       DEFAULT_TIME_BUCKETS, metric_key, parse_metric_key,
                       snapshot_delta, summarize_snapshot)
from .spans import Span, SpanTracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span",
    "SpanTracer", "DEFAULT_TIME_BUCKETS", "metric_key", "parse_metric_key",
    "snapshot_delta", "summarize_snapshot",
    "enabled", "set_enabled", "get_registry", "get_tracer",
    "counter", "gauge", "histogram", "span",
    "device_scopes", "register_program",
    "snapshot", "merge", "reset",
]

_ENABLED = os.environ.get("QUIVER_TELEMETRY", "on").strip().lower() not in (
    "off", "0", "false", "no")

_registry = MetricsRegistry()
_tracer = SpanTracer()


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Flip telemetry at runtime (overrides ``QUIVER_TELEMETRY``)."""
    global _ENABLED
    # quiverlint: ignore[QT008] -- single atomic bool rebind; worker
    # readers tolerate one stale observation by design (noop fallback)
    _ENABLED = bool(on)


def get_registry() -> MetricsRegistry:
    return _registry if _ENABLED else _noop.REGISTRY


def get_tracer() -> SpanTracer:
    return _tracer if _ENABLED else _noop.TRACER


def counter(name: str, help: Optional[str] = None, **labels) -> Counter:
    if _ENABLED:
        return _registry.counter(name, help=help, **labels)
    return _noop.METRIC


def gauge(name: str, help: Optional[str] = None, **labels) -> Gauge:
    if _ENABLED:
        return _registry.gauge(name, help=help, **labels)
    return _noop.METRIC


def histogram(name: str, bounds: Optional[Sequence[float]] = None,
              help: Optional[str] = None, **labels) -> Histogram:
    if _ENABLED:
        return _registry.histogram(name, bounds=bounds, help=help, **labels)
    return _noop.METRIC


def span(name: str, block=None):
    return _tracer.span(name, block=block) if _ENABLED else _noop.SPAN


def snapshot() -> dict:
    """Snapshot of the *real* registry (even while disabled, so a
    paused session can still read what was collected)."""
    return _registry.snapshot()


def merge(snap: dict) -> None:
    _registry.merge(snap)


def reset() -> None:
    _registry.reset()
    _tracer.reset()
    # Companion singletons (lazy submodules — never imported just to
    # reset them if nothing ever touched them).
    import sys

    fr = sys.modules.get(__name__ + ".flightrec")
    if fr is not None:
        fr.reset()
    tl = sys.modules.get(__name__ + ".timeline")
    if tl is not None:
        tl.reset()
    slo = sys.modules.get(__name__ + ".slo")
    if slo is not None:
        slo.reset()
    br = sys.modules.get("quiver_tpu.resilience.breaker")
    if br is not None:
        br.reset()
