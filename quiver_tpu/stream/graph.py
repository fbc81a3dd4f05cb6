"""StreamingGraph — a mutable graph view over a frozen base CSR.

The streaming tier's contract (docs/STREAMING.md):

  * the **base** :class:`~quiver_tpu.utils.topology.CSRTopo` never
    mutates in place — deletions of base edges set bits in a
    **tombstone bitmap** indexed by base edge position, insertions go to
    the :class:`~quiver_tpu.stream.delta.DeltaStore` append segment;
  * samplers consume immutable :class:`DeltaSnapshot`\\ s — one set of
    device arrays per graph version, built lazily and cached until the
    next mutation.  The delta segment is re-CSR'd per snapshot and
    padded to a **pow2 fanout bucket** so the jitted overlay pipeline's
    executable keys stay additive (coldcache discipline: executables key
    on the bucket, not the pending count);
  * the **compactor** (``stream.compactor``) folds tombstones + live
    delta edges into a fresh base CSR and swaps it in atomically under
    ``_lock`` — in-flight snapshots keep sampling the old arrays (jax
    arrays are immutable), the next ``snapshot()`` sees the new base;
  * every mutation bumps ``version``; the flight recorder stamps the
    version current at each request's admission
    (``flightrec.set_version_provider``), so traces pin the topology
    they sampled.

Invalidation wiring: row listeners registered via
:meth:`register_invalidation` / :meth:`attach_feature` run after every
mutation with the union of touched endpoints — that drops stale rows
from the coldcache overlay / per-host DistFeature overlay.  Listeners
run OUTSIDE ``_lock`` (they take their own store locks; holding both
would order ``_lock`` before ``Feature._plock`` here and invite the
reverse order elsewhere).
"""

from __future__ import annotations

import threading
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from .. import telemetry
from ..telemetry import flightrec
from ..utils.topology import CSRTopo, coo_to_csr
from .delta import DeltaStore

__all__ = ["StreamingGraph", "DeltaSnapshot"]


def _pad128(a: np.ndarray) -> np.ndarray:
    """Zero-pad to a multiple of 128, never empty (element-gather shape
    contract, same as ``CSRTopo.to_device``)."""
    target = max(((len(a) + 127) // 128) * 128, 128)
    if target != len(a):
        a = np.concatenate([a, np.zeros(target - len(a), a.dtype)])
    return a


def _fanout_bucket(n: int) -> int:
    """Smallest pow2 >= n, floored at 128 — the static length the delta
    indices/ts tables pad to, so executables key on O(log capacity)
    buckets instead of every pending count."""
    b = 128
    while b < n:
        b *= 2
    return b


class DeltaSnapshot(NamedTuple):
    """Immutable device view of one graph version.

    All arrays are device-resident jax arrays; ``d_indices`` / ``d_ts``
    are padded to ``delta_bucket`` and ``tomb`` / ``base_ts`` to the
    base table pad, so an executable built for
    ``(epad, delta_bucket, has_ts)`` serves every later snapshot with
    the same key.
    """

    indptr: object         # [Npad] int32 base CSR row pointers
    indices: object        # [epad] int32 base CSR columns
    tomb: object           # [epad] int32, nonzero = base edge deleted
    d_indptr: object       # [Npad] int32 delta CSR row pointers
    d_indices: object      # [delta_bucket] int32 delta columns
    base_ts: Optional[object]  # [epad] int32 or None
    d_ts: Optional[object]     # [delta_bucket] int32 or None
    version: int
    epad: int
    delta_bucket: int
    has_ts: bool
    pending: int           # live delta edges in this snapshot


class StreamingGraph:
    """Thread-safe mutable graph: base CSR + tombstones + delta segment.

    Args:
      csr_topo: the initial base :class:`CSRTopo` (frozen from here on).
      edge_ts: optional ``[E]`` int32 per-edge timestamps aligned with
        ``csr_topo.indices`` order; providing them enables the samplers'
        temporal window filter (and makes ``add_edges`` require ``ts``).
      delta_capacity: pending-edge ceiling
        (default ``config.stream_delta_capacity``).
      device: jax device the snapshots place arrays on.
    """

    _guarded_by = {
        "_tomb": "_lock", "_delta": "_lock", "_version": "_lock",
        "_snap": "_lock", "_base": "_lock", "_base_ts": "_lock",
        "_tombstones": "_lock", "_listeners": "_lock",
    }

    def __init__(self, csr_topo: CSRTopo, edge_ts=None,
                 delta_capacity: Optional[int] = None, device=None):
        from ..config import get_config

        cfg = get_config()
        self._lock = threading.RLock()
        self._base = csr_topo
        self.has_ts = edge_ts is not None
        if self.has_ts:
            edge_ts = np.asarray(edge_ts, dtype=np.int32)
            if edge_ts.shape[0] != csr_topo.edge_count:
                raise ValueError(
                    f"edge_ts length {edge_ts.shape[0]} != edge_count "
                    f"{csr_topo.edge_count}")
        self._base_ts = edge_ts
        self._tomb = np.zeros(csr_topo.edge_count, dtype=bool)
        self._tombstones = 0  # live tombstone count (folds reset it)
        cap = int(delta_capacity if delta_capacity is not None
                  else cfg.stream_delta_capacity)
        self._delta = DeltaStore(cap, has_ts=self.has_ts)
        self._version = 0
        self._snap: Optional[DeltaSnapshot] = None
        self.device = device
        self._listeners: List[Callable] = []
        # flight records stamp the version current at their admission
        flightrec.set_version_provider(self._read_version)

    # -- read side -----------------------------------------------------
    @property
    def base(self) -> CSRTopo:
        return self._base

    @property
    def node_count(self) -> int:
        return self._base.node_count

    @property
    def version(self) -> int:
        return self._read_version()

    def _read_version(self) -> int:
        # int read is atomic under the GIL; used by the flightrec
        # provider on every trace admission, so it must stay lock-free
        return self._version

    @property
    def pending_deltas(self) -> int:
        with self._lock:
            return self._delta.live

    @property
    def tombstone_count(self) -> int:
        with self._lock:
            return self._tombstones

    # -- invalidation wiring -------------------------------------------
    def register_invalidation(self, fn: Callable) -> None:
        """``fn(rows: np.ndarray)`` runs after every mutation with the
        touched node ids (edge endpoints).  Exceptions propagate to the
        mutator — a listener that cannot invalidate must not fail
        silently, or the caches serve stale rows."""
        with self._lock:
            self._listeners.append(fn)

    def attach_feature(self, feature) -> None:
        """Wire a ``Feature`` / ``DistFeature``'s ``invalidate_rows``."""
        self.register_invalidation(feature.invalidate_rows)

    def close(self) -> None:
        """Unhook the flightrec version provider (tests / teardown)."""
        flightrec.set_version_provider(None)

    def _notify(self, rows: np.ndarray) -> None:
        if rows.size == 0:
            return
        # snapshot under the lock, call listeners outside it: a listener
        # (Feature.invalidate_rows) takes Feature._plock, and holding
        # _lock across that call would pin the _lock -> _plock edge into
        # every notification (see the class docstring's ordering note)
        with self._lock:
            listeners = list(self._listeners)
        if not listeners:
            return
        rows = np.unique(rows.astype(np.int64))
        for fn in listeners:
            fn(rows)

    # -- mutation side -------------------------------------------------
    def add_edges(self, src, dst, ts=None) -> int:
        """Append edges to the delta segment; returns the count applied.

        ``BufferError`` (segment full) propagates — callers treat it as
        backpressure (the ingest worker compacts and retries).
        """
        src = np.atleast_1d(np.asarray(src, dtype=np.int32))
        dst = np.atleast_1d(np.asarray(dst, dtype=np.int32))
        n = self._base.node_count
        if src.size and (int(src.max()) >= n or int(dst.max()) >= n
                         or int(src.min()) < 0 or int(dst.min()) < 0):
            raise ValueError(
                f"edge endpoints must be in [0, {n}) — node additions "
                "are not part of the streaming tier")
        with self._lock:
            m = self._delta.add(src, dst, ts)
            self._version += 1
            self._snap = None
            pending = self._delta.live
        telemetry.counter("stream_edges_applied_total", op="add").inc(m)
        telemetry.gauge("stream_graph_version_total").set(self._version)
        telemetry.gauge("stream_overlay_bytes").set(
            float(pending) * (12.0 if self.has_ts else 8.0))
        self._notify(np.concatenate([src, dst]))
        return m

    def remove_edges(self, src, dst) -> int:
        """Delete edges: tombstone a live base occurrence, else kill a
        live pending delta edge.  Returns the count actually deleted
        (absent edges are ignored)."""
        src = np.atleast_1d(np.asarray(src, dtype=np.int64))
        dst = np.atleast_1d(np.asarray(dst, dtype=np.int64))
        if src.shape != dst.shape:
            raise ValueError("src/dst length mismatch")
        removed = tombed = 0
        touched = []
        with self._lock:
            indptr, indices = self._base.indptr, self._base.indices
            for u, v in zip(src, dst):
                u, v = int(u), int(v)
                lo, hi = int(indptr[u]), int(indptr[u + 1])
                row = indices[lo:hi]
                hit = np.nonzero((row == v) & ~self._tomb[lo:hi])[0]
                if len(hit):
                    self._tomb[lo + hit[0]] = True
                    self._tombstones += 1
                    tombed += 1
                elif not self._delta.kill(u, v):
                    continue  # edge absent: no-op
                removed += 1
                touched.append((u, v))
            if removed:
                self._version += 1
                self._snap = None
                pending = self._delta.live
        if removed:
            if tombed:
                telemetry.counter("stream_tombstones_total").inc(tombed)
            telemetry.counter("stream_edges_applied_total",
                              op="remove").inc(removed)
            telemetry.gauge("stream_graph_version_total").set(self._version)
            telemetry.gauge("stream_overlay_bytes").set(
                float(pending) * (12.0 if self.has_ts else 8.0))
            self._notify(np.asarray(touched, dtype=np.int64).reshape(-1))
        return removed

    # -- snapshot side -------------------------------------------------
    def snapshot(self, device=None) -> DeltaSnapshot:
        """Device view of the current version (cached until a mutation).

        The delta segment's live edges are re-CSR'd over the node space
        (stable order: a row's delta neighbors keep append order — the
        same order a fold preserves, which is what makes post-compaction
        sampling bitwise-reproducible) and padded to the pow2 fanout
        bucket.
        """
        import jax
        import jax.numpy as jnp

        device = device if device is not None else self.device
        with self._lock:
            snap = self._snap
            if snap is not None:
                return snap
            n = self._base.node_count
            indptr, indices = self._base.to_device(device)
            epad = int(indices.shape[0])
            tomb = _pad128(self._tomb.astype(np.int32))
            if len(tomb) != epad:  # epad floor is 128 even for tiny E
                tomb = np.concatenate(
                    [tomb, np.zeros(epad - len(tomb), np.int32)])
            d_src, d_dst, d_ts = self._delta.live_edges()
            d_indptr64, d_indices, _ = coo_to_csr(d_src, d_dst, n)
            bucket = _fanout_bucket(len(d_indices))
            d_ind = np.zeros(bucket, dtype=np.int32)
            d_ind[:len(d_indices)] = d_indices
            d_ts_pad = None
            base_ts_pad = None
            if self.has_ts:
                order = np.argsort(d_src, kind="stable")
                d_ts_pad = np.zeros(bucket, dtype=np.int32)
                d_ts_pad[:len(d_indices)] = d_ts[order]
                base_ts_pad = _pad128(self._base_ts)
                if len(base_ts_pad) != epad:
                    base_ts_pad = np.concatenate(
                        [base_ts_pad,
                         np.zeros(epad - len(base_ts_pad), np.int32)])
            put = (lambda a: jax.device_put(jnp.asarray(a), device)
                   if device is not None else jnp.asarray(a))
            snap = DeltaSnapshot(
                indptr=indptr, indices=indices,
                tomb=put(tomb),
                d_indptr=put(_pad128(d_indptr64.astype(np.int32))),
                d_indices=put(d_ind),
                base_ts=None if base_ts_pad is None else put(base_ts_pad),
                d_ts=None if d_ts_pad is None else put(d_ts_pad),
                version=self._version, epad=epad, delta_bucket=bucket,
                has_ts=self.has_ts, pending=len(d_indices),
            )
            self._snap = snap
            return snap

    def __repr__(self):
        return (f"StreamingGraph(base={self._base!r}, "
                f"pending={self.pending_deltas}, "
                f"tombstones={self.tombstone_count}, "
                f"version={self.version})")
