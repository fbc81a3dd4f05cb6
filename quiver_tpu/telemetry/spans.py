"""Span tracer: nested wall-clock scopes + Chrome trace-event export.

Subsumes the old ``utils.trace.trace_scope`` / ``Timer`` pair.  Two
independent switches:

  * **aggregation** is always on for a live (non-noop) tracer: every
    span folds into ``{name: [count, total_s, max_s]}`` — this is what
    ``summary()`` (né ``trace_summary``) reads and costs one lock, two
    adds and one comparison per span.  The longest single call is what
    shows a stall without a trace: a mean hides one step in a thousand.
  * **event retention** (``set_tracing(True)`` or env
    ``QUIVER_TPU_TRACE=1``) additionally appends one event record per
    span — name, start/duration in µs, pid/tid, nesting depth — which
    ``chrome_trace()`` serializes as Chrome trace-event JSON
    (``{"traceEvents": [...]}``) loadable in Perfetto / chrome://tracing.

Every span also enters a ``jax.profiler.TraceAnnotation("qt." + name)``:
while a ``jax.profiler`` trace is being taken the host spans land on its
``/host:CPU`` plane, on the device trace's clock and under the prefix of
the device scopes (:mod:`.device_scopes`); with no trace running the
annotation costs a few hundred nanoseconds.

A word on async dispatch: like the old ``trace_scope``, a span around a
jitted call measures **dispatch** unless you pass ``block=`` an array
(or list of arrays) to ``block_until_ready`` before the span closes.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from . import timeline as _timeline
from .device_scopes import PREFIX

__all__ = ["SpanTracer", "Span"]

_MAX_EVENTS = 200_000  # retention cap: ~25 MB of events, then drop


def _env_tracing() -> bool:
    return os.environ.get("QUIVER_TPU_TRACE", "").strip().lower() in (
        "1", "true", "on", "yes")


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, looked up on first use: this
    package imports no jax at module level."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class Span:
    """One ``with``-scope.  Created per call (only when telemetry is
    enabled); closing folds into the tracer's aggregate and, when
    tracing, appends an event record."""

    __slots__ = ("_tracer", "name", "_block", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, block=None):
        self._tracer = tracer
        self.name = name
        self._block = block

    def __enter__(self):
        tls = self._tracer._tls
        self._depth = getattr(tls, "depth", 0)
        tls.depth = self._depth + 1
        self._ann = _trace_annotation()(PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        blk = self._block
        if blk is not None:
            for x in (blk if isinstance(blk, (list, tuple)) else (blk,)):
                getattr(x, "block_until_ready", lambda: None)()
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tracer._tls.depth = self._depth
        self._tracer._close(self.name, self._t0, t1, self._depth)
        return False


class SpanTracer:
    """Aggregating tracer with optional Chrome-trace event retention."""

    def __init__(self, tracing: Optional[bool] = None):
        self._lock = threading.Lock()
        # name -> [count, total_s, longest single call in s]
        self._agg: Dict[str, List[float]] = {}
        self._events: List[dict] = []
        self._dropped = 0
        self._tls = threading.local()
        self._epoch = time.perf_counter()
        self._tracing = _env_tracing() if tracing is None else bool(tracing)

    # -- recording --------------------------------------------------------
    def span(self, name: str, block=None) -> Span:
        return Span(self, name, block=block)

    def _close(self, name: str, t0: float, t1: float, depth: int) -> None:
        dt = t1 - t0
        with self._lock:
            s = self._agg.get(name)
            if s is None:
                self._agg[name] = [1, dt, dt]
            else:
                s[0] += 1
                s[1] += dt
                if dt > s[2]:
                    s[2] = dt
            if self._tracing:
                if len(self._events) < _MAX_EVENTS:
                    self._events.append({
                        "name": name,
                        "ts_us": (t0 - self._epoch) * 1e6,
                        "dur_us": dt * 1e6,
                        "pid": os.getpid(),
                        "tid": threading.get_ident(),
                        "depth": depth,
                    })
                else:
                    self._dropped += 1
        if _timeline._ON:  # one global read when the timeline is off
            _timeline.emit(name, cat="span", dur_s=dt, t0=t0,
                           attrs={"depth": depth} if depth else None)

    # -- switches ---------------------------------------------------------
    @property
    def tracing(self) -> bool:
        return self._tracing

    def set_tracing(self, on: bool) -> None:
        self._tracing = bool(on)

    # -- readout ----------------------------------------------------------
    def summary(self) -> Dict[str, dict]:
        """``{name: {count, total_s, mean_ms, max_ms}}`` since the last
        ``reset()`` — the old ``trace_summary()``'s shape, and the longest
        single call beside the mean."""
        with self._lock:
            return {
                name: {
                    "count": int(c),
                    "total_s": t,
                    "mean_ms": (t / c * 1e3) if c else 0.0,
                    "max_ms": m * 1e3,
                }
                for name, (c, t, m) in sorted(self._agg.items())
            }

    def events(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def reset(self) -> None:
        with self._lock:
            self._agg.clear()
            self._events.clear()
            self._dropped = 0
            self._epoch = time.perf_counter()

    # -- Chrome trace-event JSON -----------------------------------------
    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON (complete "X" events, µs units) —
        load via Perfetto (ui.perfetto.dev) or chrome://tracing."""
        with self._lock:
            evs = [
                {
                    "name": e["name"],
                    "ph": "X",
                    "ts": e["ts_us"],
                    "dur": e["dur_us"],
                    "pid": e["pid"],
                    "tid": e["tid"],
                    "args": {"depth": e["depth"]},
                }
                for e in self._events
            ]
            dropped = self._dropped
        out: Dict[str, Any] = {"traceEvents": evs, "displayTimeUnit": "ms"}
        if dropped:
            out["otherData"] = {"dropped_events": dropped}
        return out

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    @staticmethod
    def parse_chrome_trace(data) -> List[dict]:
        """Inverse of :meth:`chrome_trace` for round-trip tests and
        offline analysis: accepts the dict (or its JSON string) and
        returns event records in :meth:`events` form."""
        if isinstance(data, (str, bytes)):
            data = json.loads(data)
        out = []
        for e in data.get("traceEvents", []):
            if e.get("ph") != "X":
                continue
            out.append({
                "name": e["name"],
                "ts_us": e["ts"],
                "dur_us": e["dur"],
                "pid": e["pid"],
                "tid": e["tid"],
                "depth": e.get("args", {}).get("depth", 0),
            })
        return out
