"""Unified AOT program registry (ROADMAP item 5).

Every executable cache in the library — the sampler's per-batch jits
and stream-overlay programs, serving's fused per-bucket forwards, the
feature store's merge/admit grid, hetero's per-batch pipelines — used
to be an anonymous ``{}`` on its owner.  They still live on their
owners (the programs close over owner state, so cross-instance sharing
would be wrong), but each is now a :class:`ProgramCache` handed out by
the one :class:`ProgramRegistry`, which gives the fleet three things
the scattered dicts could not:

  * **one accounting surface** — ``registry_hits_total`` /
    ``registry_misses_total`` / ``registry_builds_total`` counters and
    a ``registry_programs_total`` size gauge, all labelled by
    subsystem;
  * **a retrace-budget gate** — after warmup the registry is
    ``seal()``\\ ed; every post-seal build ticks
    ``registry_retraces_post_seal_total`` and, past the per-subsystem
    budget, raises :class:`RetraceBudgetExceeded`.  A warm boot that
    compiles something cold is a bug this turns into a failure;
  * **persistent compilation** — ``enable_persistent_cache`` points
    JAX's compilation cache at a directory, so the *backend compile*
    (seconds per program on the chip: not measured) is paid once per
    fleet, not once per process.  ``persistent_cache_hits`` counts the
    disk hits via JAX's monitoring events; the warm-restart bench and
    crash-harness acceptance both key off it.

The retrace-guard pytest plugin keeps working unchanged: a
``ProgramCache`` is a real ``dict`` (``len()`` growth is what the
plugin measures), and the build methods it patches still run.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

from .. import telemetry
from ..telemetry import timeline as _timeline
from .errors import RetraceBudgetExceeded

__all__ = ["ProgramCache", "ProgramRegistry", "get_program_registry",
           "program_cache"]


class ProgramCache(dict):
    """A subsystem's executable cache: a dict that reports to the registry.

    The *probes* — ``get`` and ``in`` — tick hit/miss; they are what
    every owner's lookup idiom starts with (``fn = cache.get(B)`` /
    ``if B not in cache``).  ``[]`` reads are deliberately silent:
    they follow a probe in the same logical lookup, and ticking both
    would count one lookup twice and skew the hit-rate dashboards.
    Insertions tick builds and pass through the seal gate.  Locking is
    the owner's concern exactly as before (e.g. serving's
    double-checked ``_lock`` around ``_fused_fns``) — the registry's
    own counters take its internal lock.
    """

    def __init__(self, subsystem: str, registry: "ProgramRegistry"):
        super().__init__()
        self.subsystem = subsystem
        self._registry = registry

    def get(self, key, default=None):
        self._registry._tick(self.subsystem, dict.__contains__(self, key))
        return dict.get(self, key, default)

    def __contains__(self, key) -> bool:
        present = dict.__contains__(self, key)
        self._registry._tick(self.subsystem, present)
        return present

    def __setitem__(self, key, value) -> None:
        fresh = not dict.__contains__(self, key)
        dict.__setitem__(self, key, value)
        if fresh:
            self._registry._built(self.subsystem)

    def setdefault(self, key, default=None):
        if dict.__contains__(self, key):
            return dict.__getitem__(self, key)
        self[key] = default
        return default


def _zero_stats() -> Dict[str, int]:
    return {"hits": 0, "misses": 0, "builds": 0, "post_seal_builds": 0}


class ProgramRegistry:
    """Process-wide ledger over every :class:`ProgramCache`."""

    _guarded_by = {
        "_stats": "_lock", "_caches": "_lock", "_sealed": "_lock",
        "_budgets": "_lock", "_default_budget": "_lock",
        "_pcache_hits": "_lock", "_pcache_dir": "_lock",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, Dict[str, int]] = {}
        self._caches: list = []  # (subsystem, weakref to ProgramCache)
        self._sealed = False
        self._budgets: Dict[str, int] = {}
        self._default_budget: Optional[int] = None
        self._pcache_hits = 0
        self._pcache_dir: Optional[str] = None

    # -- cache hand-out -----------------------------------------------
    def cache(self, subsystem: str, owner=None) -> ProgramCache:
        """A fresh executable cache accounted under ``subsystem``.

        ``owner`` is accepted for call-site documentation only; the
        registry holds the cache by weakref so a dropped owner never
        leaks its programs through the ledger.
        """
        c = ProgramCache(subsystem, self)
        with self._lock:
            self._stats.setdefault(subsystem, _zero_stats())
            self._caches.append((subsystem, weakref.ref(c)))
        return c

    # -- accounting (called by ProgramCache) --------------------------
    def _tick(self, subsystem: str, hit: bool) -> None:
        with self._lock:
            st = self._stats.setdefault(subsystem, _zero_stats())
            st["hits" if hit else "misses"] += 1
        if hit:
            telemetry.counter("registry_hits_total",
                              subsystem=subsystem).inc()
        else:
            telemetry.counter("registry_misses_total",
                              subsystem=subsystem).inc()

    def _built(self, subsystem: str) -> None:
        with self._lock:
            st = self._stats.setdefault(subsystem, _zero_stats())
            st["builds"] += 1
            sealed = self._sealed
            over = False
            if sealed:
                st["post_seal_builds"] += 1
                budget = self._budgets.get(subsystem, self._default_budget)
                over = budget is not None and \
                    st["post_seal_builds"] > budget
        telemetry.counter("registry_builds_total",
                          subsystem=subsystem).inc()
        if _timeline._ON:  # one global read when the timeline is off
            _timeline.emit("registry.build", cat="registry",
                           attrs={"subsystem": subsystem,
                                  "post_seal": bool(sealed)})
        if sealed:
            telemetry.counter("registry_retraces_post_seal_total",
                              subsystem=subsystem).inc()
            if over:
                raise RetraceBudgetExceeded(
                    f"subsystem {subsystem!r} built a program after "
                    f"seal() beyond its retrace budget "
                    f"({self._budgets.get(subsystem, self._default_budget)})"
                    f" — a warm boot compiled something cold")

    # -- the retrace-budget gate --------------------------------------
    def seal(self, budget: Optional[int] = None,
             per_subsystem: Optional[Dict[str, int]] = None) -> None:
        """Close the warmup window: post-seal builds are counted and,
        beyond the budget, fatal.  ``budget`` is the default allowance
        per subsystem (``None`` reads ``config.recovery_retrace_budget``;
        a negative value there means count-only, never raise)."""
        if budget is None:
            from ..config import get_config

            cfg_budget = int(get_config().recovery_retrace_budget)
            budget = None if cfg_budget < 0 else cfg_budget
        with self._lock:
            self._sealed = True
            self._default_budget = budget
            self._budgets = dict(per_subsystem or {})
            for st in self._stats.values():
                st["post_seal_builds"] = 0
        telemetry.gauge("registry_sealed_state").set(1.0)

    def unseal(self) -> None:
        with self._lock:
            self._sealed = False
        telemetry.gauge("registry_sealed_state").set(0.0)

    @property
    def sealed(self) -> bool:
        with self._lock:
            return self._sealed

    # -- introspection / metrics --------------------------------------
    def stats(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            out = {k: dict(v) for k, v in self._stats.items()}
            live = [(sub, ref()) for sub, ref in self._caches]
        for sub, c in live:
            if c is not None:
                out.setdefault(sub, _zero_stats())
                out[sub]["size"] = out[sub].get("size", 0) + len(c)
        for st in out.values():
            st.setdefault("size", 0)
        return out

    def export_metrics(self) -> Dict[str, Dict[str, int]]:
        """Publish per-subsystem sizes as gauges; returns the stats."""
        stats = self.stats()
        for sub, st in stats.items():
            telemetry.gauge("registry_programs_total", subsystem=sub).set(
                float(st["size"]))
        return stats

    # -- persistent compilation cache ---------------------------------
    def enable_persistent_cache(self, cache_dir: str) -> bool:
        """Point JAX's compilation cache at ``cache_dir`` (created if
        missing) and start counting disk hits.  When the process was
        started with ``JAX_COMPILATION_CACHE_DIR`` the cache stays where
        that variable put it and ``cache_dir`` is not used
        (``utils/compile_cache.py``).  Returns False — with the reason
        logged — when this JAX build refuses, so boot proceeds merely
        cold, not dead."""
        import logging
        import os

        from ..utils import compile_cache

        log = logging.getLogger("quiver_tpu.recovery")
        placed = os.environ.get(compile_cache.ENV)
        try:
            import jax

            if placed:
                cache_dir = placed
            else:
                os.makedirs(cache_dir, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir",
                                  str(cache_dir))
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            self._install_hit_listener()
        except Exception as e:
            log.warning("persistent compilation cache unavailable: %s", e)
            return False
        with self._lock:
            self._pcache_dir = str(cache_dir)
        return True

    def _install_hit_listener(self) -> None:
        global _HIT_LISTENER_INSTALLED
        with _LISTENER_LOCK:
            if _HIT_LISTENER_INSTALLED:
                return
            from jax import monitoring

            def _on_event(event, **kwargs):
                if "cache_hit" in event or "cache_hits" in event:
                    reg = get_program_registry()
                    with reg._lock:
                        reg._pcache_hits += 1
                    telemetry.counter(
                        "registry_persistent_cache_hits_total").inc()

            monitoring.register_event_listener(_on_event)
            _HIT_LISTENER_INSTALLED = True

    @property
    def persistent_cache_hits(self) -> int:
        with self._lock:
            return self._pcache_hits

    @property
    def persistent_cache_dir(self) -> Optional[str]:
        with self._lock:
            return self._pcache_dir


_REGISTRY: Optional[ProgramRegistry] = None
_REGISTRY_LOCK = threading.Lock()
_LISTENER_LOCK = threading.Lock()
_HIT_LISTENER_INSTALLED = False


def get_program_registry() -> ProgramRegistry:
    global _REGISTRY
    with _REGISTRY_LOCK:
        if _REGISTRY is None:
            _REGISTRY = ProgramRegistry()
        return _REGISTRY


def program_cache(subsystem: str, owner=None) -> ProgramCache:
    """The constructor the executable-cache owners call in place of
    ``{}`` — e.g. ``self._jitted = program_cache("sampler", owner=self)``."""
    return get_program_registry().cache(subsystem, owner=owner)
