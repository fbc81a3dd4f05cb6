"""Row-range-sharded feature store: one logical table over a mesh.

The fleet's replicas each hold the WHOLE feature table; this store
holds ``1/n_shards`` of it per device and serves a batch gather as a
**sharded gather with a halo exchange expressed as a collective** —
the ``shard_map`` formulation of what the dist tier hand-rolls as a
host-planned all-to-all (``dist/feature.py``), and the TPU shape of
torch-quiver's ``quiver_partition_feature`` clique sharding.

Layout (docs/SHARDING.md):

  * Rows are split into contiguous ranges of ``rows_per_shard``
    (ownership is ``id // rows_per_shard`` — a shift, not a lookup).
  * Each shard owns a :class:`~quiver_tpu.ops.paged.PagedStore` over
    ITS range only: the frame pool and page table are sharded by row
    range, and a page fault touches exactly one shard's pool — faults
    stay shard-local, the single-device fault path
    (``PagedStore._fault_pages``: one whole-page H2D, CLOCK eviction,
    the ``feature_page_*`` metrics) is reused verbatim.
  * The mesh-wide views the collective reads — frames
    ``[S, F, R, D]`` and the page->frame table ``[S, P]`` — carry
    ``NamedSharding(P("shard"))``; they are restacked only after a
    fault dirtied a shard, so the steady state moves zero bytes.

The gather itself runs ONE executable per pow2-padded batch size
(key ``("gather", B_pad, n_shards)`` in the ``mesh_feature`` program
cache): each shard gathers the rows it owns from its local frames and
contributes a dtype-minimum sentinel elsewhere; an all-reduce ``pmax``
over the ``shard`` axis is the halo exchange that assembles the full
``[B, D]`` batch on every shard.  ``pmax`` (not ``psum``) keeps the
combine bit-exact: the owner's row wins unchanged — no ``-0.0 + 0.0``
renormalization — so the result is bit-identical to the single-device
staged path (``tests/test_mesh.py`` pins it; the one documented hole
is a feature value equal to the sentinel itself, i.e. ``-inf``).

Overflow honesty: a batch whose page working set exceeds a shard's
overlay pool falls back to an exact host-table gather for the WHOLE
batch (``feature_page_fallback_total`` ticks) — correctness first,
the counter makes the mis-sizing visible, same contract as the
single-device paged store.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from .. import telemetry
from ..ops.paged import PageTable, PagedStore, default_page_rows
from ..recovery.registry import program_cache
from .topology import (SHARD_AXIS, build_mesh, row_shard, shard_devices,
                       shard_ranges, stack_shards)

__all__ = ["MeshFeature"]


class _ShardFaultFns:
    """The ``_feature`` surface each shard's ``PagedStore`` expects from
    its owner (``ops/paged.py`` fault contract): a per-``k_pad`` cached
    scatter.  All shards share one pool geometry, so every shard
    resolves to the SAME executables in the owner's program cache."""

    def __init__(self, owner: "MeshFeature"):
        self._owner = owner

    def _paged_fault_fn(self, k_pad: int):
        return self._owner._fault_fn(k_pad)


class MeshFeature:
    """One logical feature table served by ``n_shards`` devices."""

    _guarded_by = {"_dirty": "_lock", "_frames_g": "_lock",
                   "_lookup_g": "_lock", "restacks": "_lock",
                   "fallbacks": "_lock"}

    def __init__(self, table: np.ndarray, n_shards: Optional[int] = None,
                 mesh=None, page_rows: int = 0,
                 pool_pages: Optional[int] = None):
        import jax

        from ..config import get_config

        cfg = get_config()
        if n_shards is None:
            n_shards = cfg.mesh_shards
        self.n_shards = int(n_shards)
        if self.n_shards < 1:
            raise ValueError(
                f"MeshFeature needs n_shards >= 1 (config.mesh_shards "
                f"is off); got {self.n_shards}")
        table = np.ascontiguousarray(table)
        self.node_count, self.dim = table.shape
        self.dtype = table.dtype
        self.cache_count = 0      # no replicated hot prefix: rows shard
        self.mesh = mesh if mesh is not None else build_mesh(self.n_shards)
        self.axis = SHARD_AXIS
        self.rows_per_shard, self.ranges = shard_ranges(
            self.node_count, self.n_shards)
        row_bytes = self.dim * self.dtype.itemsize
        self.page_rows = int(page_rows) or default_page_rows(row_bytes)
        self._pages_per_shard = -(-self.rows_per_shard // self.page_rows)
        if pool_pages is None:
            pool_pages = int(cfg.mesh_pool_pages)
        # pool=0 sizes each shard's pool to hold its whole range — the
        # memory win over replication is the 1/n_shards split itself;
        # smaller pools trade faults for HBM and are an explicit choice
        self.pool_pages = int(pool_pages) or self._pages_per_shard
        self._table_np = table
        self._fns = _ShardFaultFns(self)
        self._stores = []
        self._devices = shard_devices(self.mesh)[0]
        for (lo, hi), dev in zip(self.ranges, self._devices):
            rows = np.zeros((self.rows_per_shard, self.dim),
                            dtype=self.dtype)
            rows[: hi - lo] = table[lo:hi]
            pt = PageTable(n_rows=self.rows_per_shard, cache_count=0,
                           page_rows=self.page_rows,
                           pool_pages=self.pool_pages)
            with jax.default_device(dev):
                store = PagedStore(pt, rows, cache_count=0, dim=self.dim,
                                   dtype=self.dtype)
            # COMMIT the pool to its shard's device: faults then scatter
            # into it there, and the mesh-wide view is assembled from
            # the pools in place
            store.frames = jax.device_put(store.frames, dev)
            store._feature = self._fns
            self._stores.append(store)
        self.pool_pages = self._stores[0].table.pool_pages  # post-clamp
        if np.issubdtype(self.dtype, np.floating):
            self._sentinel = np.array(-np.inf, dtype=self.dtype)
        else:
            self._sentinel = np.array(np.iinfo(self.dtype).min,
                                      dtype=self.dtype)
        self._frames_sharding = row_shard(self.mesh)
        self._cache = program_cache("mesh_feature", owner=self)
        self._lock = threading.Lock()
        self._frames_g = None
        self._lookup_g = None
        self._dirty = True
        self.restacks = 0
        self.fallbacks = 0
        from . import _set_active_feature

        _set_active_feature(self)

    # -- executables ---------------------------------------------------
    def _fault_fn(self, k_pad: int):
        """Shared-across-shards scatter of a pow2-padded fault batch
        into a shard's frame pool (pad slot = ``n_frames``, dropped) —
        the mesh twin of ``Feature._paged_fault_fn``."""
        import jax

        fn = self._cache.get(("pgfault", k_pad))
        if fn is None:

            @jax.jit
            def fn(frames, slots, pages):
                return frames.at[slots].set(pages, mode="drop")

            # quiverlint: ignore[QT014] -- k_pad is pow2-padded at the
            # fault site (ops/paged._fault); the edge runs through the
            # duck-typed PagedStore._feature -> _ShardFaultFns shim,
            # which the resolver cannot follow.
            self._cache[("pgfault", k_pad)] = fn
        return fn

    def _gather_fn(self, b_pad: int):
        """The sharded gather + halo-exchange collective for one padded
        batch size: ONE executable per ``(B_pad, n_shards)``."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        key = ("gather", b_pad, self.n_shards)
        fn = self._cache.get(key)
        if fn is None:
            axis = self.axis
            rps = self.rows_per_shard
            page_rows = self.page_rows
            n_frames = self._stores[0].table.n_frames
            sentinel = jnp.asarray(self._sentinel)

            def _local(frames, lookup, ids):
                # blocks: frames [1, F, R, D], lookup [1, P]; ids [Bp]
                s = jax.lax.axis_index(axis)
                local = ids - s * rps
                own = (local >= 0) & (local < rps)
                lid = jnp.clip(local, 0, rps - 1)
                frame = lookup[0, lid // page_rows]
                ok = own & (frame >= 0)
                rows = frames[0][jnp.clip(frame, 0, n_frames - 1),
                                 lid % page_rows]
                part = jnp.where(ok[:, None], rows, sentinel)
                # the halo exchange: owners broadcast their rows, the
                # sentinel loses everywhere — bit-exact all-reduce
                return jax.lax.pmax(part, axis)

            fn = jax.jit(shard_map(
                _local, mesh=self.mesh,
                in_specs=(P(axis), P(axis), P()), out_specs=P()))
            self._cache[key] = fn
        return fn

    # -- faulting / restack (host-side planning) -----------------------
    def _fault_shards(self, ids: np.ndarray,
                      owner: np.ndarray) -> Optional[bool]:
        """Fault every shard's touched pages (shard-local, one H2D per
        shard).  Returns None when some shard's pool cannot hold this
        batch's working set (caller falls back), else whether any page
        actually faulted (caller marks the views dirty).  Call with
        ``_lock`` held."""
        import jax
        import jax.numpy as jnp

        dirtied = False
        for s, store in enumerate(self._stores):
            local = ids[owner == s] - s * self.rows_per_shard
            if local.size == 0:
                continue
            pages = np.unique(local // self.page_rows)
            resident = store.frame_of_pages()[pages] >= 0
            if resident.all():
                continue
            with jax.default_device(self._devices[s]):  # H2D lands there
                faulted = store._fault_pages(pages, jnp, telemetry)
            if faulted is None:
                store.fallbacks += 1
                return None
            dirtied = True
        return dirtied

    def _stacked_views(self):
        """Fresh mesh-wide sharded views (frames ``[S,F,R,D]``, lookup
        ``[S,P]``) from the shards' current pools; only reached after a
        fault dirtied a shard — the steady state moves zero bytes.
        Call with ``_lock`` held."""
        import jax

        lookup = np.stack([s.frame_of_pages() for s in self._stores])
        return (stack_shards(self.mesh, [s.frames for s in self._stores]),
                jax.device_put(lookup, self._frames_sharding))

    # -- the batch gather ----------------------------------------------
    def __getitem__(self, node_idx):
        import jax.numpy as jnp

        from ..feature import _pow2_bucket

        ids = np.asarray(node_idx, dtype=np.int64).reshape(-1)
        B = len(ids)
        if B == 0:
            return jnp.zeros((0, self.dim), dtype=self.dtype)
        with telemetry.histogram("mesh_shard_gather_seconds").time():
            owner = ids // self.rows_per_shard
            with self._lock:
                faulted = self._fault_shards(ids, owner)
                if faulted is None:
                    # pool overflow on some shard: exact host gather —
                    # answered, never dropped (single-device contract)
                    self.fallbacks += 1
                    telemetry.counter("feature_page_fallback_total").inc()
                    return jnp.asarray(self._table_np[ids])
                if faulted:
                    self._dirty = True
                if self._dirty:
                    self._frames_g, self._lookup_g = self._stacked_views()
                    self._dirty = False
                    self.restacks += 1
                frames_g, lookup_g = self._frames_g, self._lookup_g
            b_pad = _pow2_bucket(B)
            ids_pad = np.full(b_pad, -1, dtype=np.int32)
            ids_pad[:B] = ids
            out = self._gather_fn(b_pad)(frames_g, lookup_g,
                                         jnp.asarray(ids_pad))[:B]
        # logical halo volume of the replicated combine: every owned row
        # crosses to the other (n-1) shards.  Analytic on rehearsal —
        # transport counters need real interconnect telemetry.
        halo = float(B * self.dim * self.dtype.itemsize
                     * (self.n_shards - 1))
        telemetry.counter("mesh_halo_bytes_total", direction="send").inc(
            halo)
        telemetry.counter("mesh_halo_bytes_total", direction="recv").inc(
            halo)
        return out

    # -- warmup / introspection ----------------------------------------
    def warm_executables(self, buckets: Optional[Sequence[int]] = None
                         ) -> int:
        """Pre-build the gather collective for a pow2 ladder of batch
        sizes (serving calls this from ``warmup()`` so a fresh frontier
        size never stalls a request on a compile).  Returns the number
        of executables built."""
        if buckets is None:
            from ..feature import _pow2_bucket

            top = _pow2_bucket(min(self.node_count, 1 << 13))
            buckets, b = [], 1
            while b <= top:
                buckets.append(b)
                b <<= 1
        before = len(self._cache)
        for b in buckets:
            self._gather_fn(int(b))
        return len(self._cache) - before

    def stats(self) -> dict:
        with self._lock:
            per_shard = [dict(range=list(r),
                              resident_pages=s.table.resident_pages(),
                              fallbacks=s.fallbacks,
                              # where the shard's frame pool lives
                              device=str(next(iter(s.frames.devices()))),
                              bytes=int(s.frames.nbytes))
                         for r, s in zip(self.ranges, self._stores)]
            return dict(
                n_shards=self.n_shards, rows_per_shard=self.rows_per_shard,
                page_rows=self.page_rows, pool_pages=self.pool_pages,
                pages_per_shard=self._pages_per_shard,
                executables=len(self._cache),
                restacks=self.restacks, fallbacks=self.fallbacks,
                shards=per_shard)

    def size(self, dim: int) -> int:
        return (self.node_count, self.dim)[dim]

    def __repr__(self):
        return (f"MeshFeature(nodes={self.node_count}, dim={self.dim}, "
                f"shards={self.n_shards}, page_rows={self.page_rows}, "
                f"pool_pages={self.pool_pages})")
