"""Cached feature store — TPU-native ``quiver.Feature``.

Reference parity: ``srcs/python/quiver/feature.py:17-459`` (Feature,
DeviceConfig) and the ShardTensor machinery it sits on
(``shard_tensor.py:51-213``, ``quiver_feature.cu:57-376``).

TPU-first redesign of the three storage tiers:

  reference                      | quiver_tpu
  -------------------------------+------------------------------------------
  local-GPU HBM hot cache        | HBM-resident ``jax.Array`` hot prefix
  peer-GPU HBM over NVLink/P2P   | hot prefix **sharded over the ICI mesh**
    (p2p_clique_replicate)       |   (``cache_policy="ici_shard"``); XLA
                                 |   inserts the all-gather/all-to-all that
                                 |   the quiver_tensor_gather kernel did by
                                 |   dereferencing peer pointers
  pinned-host zero-copy (UVA)    | host cold tail (numpy / np.memmap),
                                 |   gathered on host and shipped per batch
  cudaIpc handle sharing         | unnecessary (single-controller jax);
                                 |   ``share_ipc`` keeps API parity

The degree-ordered hot/cold split (``reindex_feature``) and the byte-budget
parsing are identical in spirit to the reference; what changes is the
mechanism of remote access.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Union

import numpy as np

from .telemetry.device_scopes import FEATURE_GATHER, HOST_GETITEM
from .utils.topology import CSRTopo, parse_size, reindex_feature

__all__ = ["Feature", "DeviceConfig"]


def _pow2_bucket(n: int) -> int:
    """Pad a row count to the power-of-two executable bucket (0 stays 0)."""
    return 0 if n == 0 else max(16, 1 << int(n - 1).bit_length())


def _fresh_bucket(n: int) -> int:
    """Quarter-octave bucket for the overlay's fresh-row H2D payload.

    Power-of-two padding can double the shipped bytes, erasing the
    overlay's transfer saving at moderate hit rates; four buckets per
    octave cap the pad waste at ~12.5% while the executable count stays
    bounded (~4 log2 B distinct shapes).  Device-side-only buckets keep
    plain pow2 — their padding costs HBM reads, not host-link bytes."""
    if n == 0:
        return 0
    if n <= 16:
        return 16
    p = 1 << int(n - 1).bit_length()   # next pow2 >= n
    h, q = p >> 1, p >> 3              # previous pow2, eighth of p
    for cand in (h + q, h + 2 * q, h + 3 * q):
        if n <= cand:
            return cand
    return p


# rows between the rows two neighbouring dead slots ask for: each in a
# (16, 128) tile of its own.  Measured: 11.54 ms for the SAGE cell's
# frontier against 11.70 at a stride of 1 and 15.18 when all ask for row 0
_DEAD_STRIDE = 16


def _dead_rows(slots: int, rows: int, dtype):
    """A row of its own for each of ``slots`` slots whose row nobody
    reads, out of a table of ``rows``: slot ``i`` asks for row ``i *
    _DEAD_STRIDE % rows``, always in range.  One row asked for by every
    such slot is the dearest there is (PERF.md, PR 33).  Used by the
    masked gather here and by ``dist.exchange``'s unpacking."""
    import jax.numpy as jnp

    return (jnp.arange(slots, dtype=jnp.uint32) * _DEAD_STRIDE
            % rows).astype(dtype)


def _lookup_tables(tables, idx, mask=None):
    """``Feature.lookup_device`` over explicit ``(hot, order)`` arrays
    (from ``Feature._device_tables``), so a jitted caller can take the
    tables as arguments.

    ``mask`` is the sampler's word on its own ids (``n_mask`` beside
    ``n_id``), and the gather acts on the two things it says.  Every id
    is in range by construction, so ``jnp.take``'s out-of-range pass (a
    select over every gathered row) goes.  And a slot where it is False
    is dead: every consumer multiplies its row by a zero mask, so it may
    read ANY row.  The sampler writes id 0 there, and one row asked for
    650 K times a step is the dearest there is: 16.2 ns a slot on a v5e
    where rows all over the table cost 10.7 (PERF.md, PR 33).  So a dead
    slot asks for a row of its own (:func:`_dead_rows`).  Live slots read
    the table's rows exactly; dead ones some row of the table.  Without a mask (a caller's own ids) the semantics
    are ``jnp.take``'s to the letter."""
    import jax
    import jax.numpy as jnp

    hot, order = tables
    with jax.named_scope(FEATURE_GATHER):
        if order is not None:
            idx = jnp.take(order, idx, mode="clip")
        if mask is None:
            return jnp.take(hot, idx, axis=0)
        own = _dead_rows(idx.shape[0], hot.shape[0], idx.dtype)
        return hot.at[jnp.where(mask, idx, own)].get(
            mode="promise_in_bounds")


@dataclass
class DeviceConfig:
    """Pre-partitioned placement (parity: ``feature.py:17-24``)."""

    device_ids: List[int]
    device_paths: List[str]  # .npy per device shard
    host_path: Optional[str] = None  # cold tail on disk (mmap)


class Feature:
    """Hot/cold cached node-feature store.

    Lock discipline (quiverlint QT003): ``_plock`` guards the staging
    state shared between the prefetch pool worker and the gather path —
    the ``_pending`` staging map, the reusable per-bucket staging
    buffers (``_stage_bufs``), and the overlay device table
    (``_overlay``, whose value must stay consistent with the
    ``cold_cache`` slot metadata mutated under the same lock).

    Args:
      rank: local device index (parity arg; single-controller jax mostly
        ignores it).
      device_list: devices participating in the cache (defaults to all).
      device_cache_size: per-device byte budget, e.g. ``"200M"`` (parsed by
        :func:`parse_size`), or rows if ``cache_unit="rows"``.
      cache_policy: ``"device_replicate"`` (hot prefix replicated) or
        ``"ici_shard"`` (hot prefix sharded over the mesh; alias
        ``"p2p_clique_replicate"`` accepted for reference compat).
      csr_topo: optional :class:`CSRTopo`; enables degree-ordered caching
        (``reindex_feature``) so high-degree rows land in the hot tier.
      cold_cache_size: budget for the HBM cold-row overlay cache
        (``docs/FEATURE_CACHE.md``) — same units as ``device_cache_size``
        (``parse_size`` bytes, or rows under ``cache_unit="rows"``).
        ``None`` defers to ``config.cold_cache_size``; ``"auto"`` leaves
        the overlay off until :meth:`enable_cold_cache` (the serving
        pipeline enables it for budgeted features); ``0`` disables.
      cold_cache_policy: overlay eviction policy, ``"clock"`` or
        ``"minfreq"`` (defaults to ``config.cold_cache_policy``).
    """

    _guarded_by = {"_pending": "_plock", "_stage_bufs": "_plock",
                   "_overlay": "_plock", "paged": "_plock",
                   # published table state: writes swap atomically under
                   # _plock; reads are lock-free (double-checked-read
                   # contract shared with QT003/QT008)
                   "hot": "_plock", "cold": "_plock",
                   "feature_order": "_plock", "cache_count": "_plock",
                   "node_count": "_plock", "dim": "_plock"}

    def __init__(self, rank: int = 0, device_list: Optional[Sequence] = None,
                 device_cache_size: Union[int, str] = 0,
                 cache_policy: str = "device_replicate",
                 csr_topo: Optional[CSRTopo] = None,
                 mesh=None, dtype=None, cache_unit: str = "bytes",
                 cold_cache_size: Union[int, str, None] = None,
                 cold_cache_policy: Optional[str] = None):
        assert cache_unit in ("bytes", "rows"), cache_unit
        self.cache_unit = cache_unit
        if cache_policy == "p2p_clique_replicate":
            cache_policy = "ici_shard"
        assert cache_policy in ("device_replicate", "ici_shard"), cache_policy
        self.rank = rank
        self.device_list = device_list
        self.device_cache_size = device_cache_size
        self.cache_policy = cache_policy
        self.csr_topo = csr_topo
        self.mesh = mesh
        self.dtype = dtype
        self.cold_cache_size = cold_cache_size
        self.cold_cache_policy = cold_cache_policy
        self.feature_order = None       # old id -> cached row
        self.hot = None                 # jax.Array [H, D]
        self.cold = None                # numpy/memmap [N-H, D]
        self.cache_count = 0
        self.node_count = 0
        self.dim = 0
        self.cold_cache = None          # ColdRowCache slot metadata
        self._overlay = None            # jax.Array [C, D] overlay table
        self.paged = None               # PagedStore (ops/paged.py)
        self._lazy_state = None
        from .recovery.registry import program_cache

        self._merge_cache = program_cache(
            "feature", owner=self)      # (B, bucket) -> jitted merge
        self._pending = {}              # prefetch staging (ids hash -> parts)
        self._stage_bufs = {}           # bucket -> reusable staging ndarray
        self._inflight = None           # deque of outstanding stage futures
        self._plock = threading.Lock()  # staging lock (see _guarded_by)
        self._pool = None               # lazy ThreadPoolExecutor

    # ------------------------------------------------------------------
    def _budget_rows(self, row_bytes: int, n_devices: int) -> int:
        budget = parse_size(self.device_cache_size)
        if self.cache_unit == "rows":
            rows = budget
        else:
            rows = budget // max(row_bytes, 1)
        if self.cache_policy == "ici_shard":
            rows *= n_devices  # each device holds 1/n of the hot set
        return int(rows)

    def _n_devices(self) -> int:
        import jax

        if self.mesh is not None:
            return int(np.prod(list(self.mesh.shape.values())))
        if self.device_list is not None:
            return len(self.device_list)
        return jax.local_device_count()

    def from_cpu_tensor(self, tensor, prob=None) -> "Feature":
        """Split ``tensor`` into HBM hot prefix + host cold tail.

        Parity: ``feature.py:194-281``.  With ``csr_topo`` set, rows are
        first permuted into degree-descending order (shuffled hot slice) and
        ``feature_order`` records old->new ids; ``csr_topo.feature_order``
        is set as a side effect, as in the reference.  ``prob`` (a per-node
        access-probability vector, e.g. from ``sample_prob``) overrides the
        degree heuristic — the reference's papers100M policy
        (``set_local_order``, feature.py:283).
        """
        import jax
        import jax.numpy as jnp

        tensor = np.asarray(tensor)
        node_count, dim = tensor.shape
        with self._plock:
            self.node_count, self.dim = node_count, dim
        dt = self.dtype or tensor.dtype
        row_bytes = int(np.dtype(dt).itemsize) * dim
        nd = self._n_devices()
        cache_count = min(self._budget_rows(row_bytes, nd), node_count)

        new_order = None
        topo_order = False
        if prob is not None and cache_count > 0:
            order = np.argsort(-np.asarray(prob), kind="stable")
            new_order = np.empty(node_count, dtype=np.int64)
            new_order[order] = np.arange(node_count)
            tensor = tensor[order]
        elif self.csr_topo is not None and cache_count > 0:
            ratio = cache_count / node_count
            tensor, new_order = reindex_feature(self.csr_topo, tensor, ratio)
            topo_order = True

        hot_np = np.ascontiguousarray(tensor[:cache_count], dtype=dt)
        cold_np = np.ascontiguousarray(tensor[cache_count:], dtype=dt)
        hot = self._place_hot(hot_np, dt)
        # Publish the table swap as one atomic step: gather-path readers
        # are lock-free by policy (QT003/QT008 double-checked-read
        # contract), so the swap must never be observable half-done.
        # _maybe_enable_cold_cache stays OUTSIDE the lock — it
        # re-acquires _plock (QT009 flags the nested self-acquire).
        with self._plock:
            if new_order is not None:
                self.feature_order = new_order
                if topo_order:
                    self.csr_topo.feature_order = new_order
            self.cache_count = cache_count
            self.cold = cold_np
            self.hot = hot
        self._maybe_enable_cold_cache()
        self._maybe_enable_paging()
        return self

    def _place_hot(self, hot_np, dt):
        """Put the hot tier in HBM — replicated, or sharded over the mesh
        (``ici_shard``, the p2p-clique equivalent)."""
        import jax
        import jax.numpy as jnp

        if hot_np.shape[0] == 0:
            return jnp.zeros((0, self.dim), dtype=dt)
        if self.cache_policy == "ici_shard" and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = self.mesh.axis_names[0]
            pad = (-hot_np.shape[0]) % np.prod(self.mesh.devices.shape)
            if pad:
                hot_np = np.concatenate(
                    [hot_np, np.zeros((pad, self.dim), dtype=dt)]
                )
            return jax.device_put(
                hot_np, NamedSharding(self.mesh, P(axis, None))
            )
        return jnp.asarray(hot_np)

    @classmethod
    def from_mmap(cls, path_or_array, device_config: DeviceConfig = None,
                  **kwargs) -> "Feature":
        """Disk-backed features (parity: ``feature.py:84-192``).

        ``path_or_array`` may be a ``.npy`` path (opened as ``np.memmap``)
        or an ndarray; the cold tier then reads through the mmap so features
        larger than host RAM still serve.
        """
        self = cls(**kwargs)
        if isinstance(path_or_array, str):
            arr = np.load(path_or_array, mmap_mode="r")
        else:
            arr = path_or_array
        if device_config is not None and device_config.device_paths:
            import jax.numpy as jnp

            shards = [np.load(p, mmap_mode="r")
                      for p in device_config.device_paths]
            hot_np = np.concatenate([np.asarray(s) for s in shards])
            self.cache_count = hot_np.shape[0]
            self.cold = arr
            self.node_count = self.cache_count + arr.shape[0]
            self.dim = arr.shape[1]
            self.hot = self._place_hot(hot_np, hot_np.dtype)
            self._maybe_enable_cold_cache()
            self._maybe_enable_paging()
            return self
        # budgeted split over the mmap
        self.node_count, self.dim = arr.shape
        row_bytes = int(arr.dtype.itemsize) * self.dim
        cache_count = min(
            self._budget_rows(row_bytes, self._n_devices()), self.node_count
        )
        self.cache_count = cache_count
        self.hot = self._place_hot(
            np.ascontiguousarray(arr[:cache_count]), arr.dtype
        )
        self.cold = arr[cache_count:]
        self._maybe_enable_cold_cache()
        self._maybe_enable_paging()
        return self

    # ------------------------------------------------------------------
    def set_local_order(self, local_order):
        """Parity: ``feature.py:283-294`` — externally computed cache order."""
        local_order = np.asarray(local_order)
        new_order = np.empty(self.node_count, dtype=np.int64)
        new_order[local_order] = np.arange(self.node_count)
        with self._plock:
            self.feature_order = new_order

    # -- cold-row overlay cache (docs/FEATURE_CACHE.md) ----------------
    def _maybe_enable_cold_cache(self):
        """Config-driven overlay enable at build time.  ``"auto"`` (the
        default) leaves the overlay opt-in — ``enable_cold_cache`` for
        training loops, or the serving pipeline's budgeted-feature
        auto-enable; an explicit size turns it on here."""
        size = self.cold_cache_size
        if size is None:
            from .config import get_config

            size = get_config().cold_cache_size
        if size in (None, "auto", "off"):
            return
        budget = parse_size(size)
        if self.cache_unit == "rows":
            rows = int(budget)
        else:
            row_bytes = int(np.dtype(self._hot_dtype()).itemsize) * self.dim
            rows = int(budget) // max(row_bytes, 1)
        if rows > 0:
            self.enable_cold_cache(rows=rows)

    def enable_cold_cache(self, rows: Optional[int] = None,
                          policy: Optional[str] = None,
                          admit_threshold: Optional[int] = None) -> "Feature":
        """Attach the fixed-capacity HBM overlay cache over the cold tail.

        The overlay is a second device-resident tier between the static
        hot prefix and the host cold tail: recurring cold rows are
        admitted on their ``admit_threshold``-th miss and then served
        from HBM instead of crossing the host link (three-tier lookup —
        see ``docs/FEATURE_CACHE.md``).  Requires a built feature; no-op
        when the feature is fully hot.

        Args:
          rows: overlay capacity in rows.  Default: a quarter of the hot
            prefix (min 1024), capped at the cold-tail size — small
            enough to never compete with the hot tier for HBM, big
            enough to absorb a zipf tail's recurring rows.
          policy: ``"clock"`` | ``"minfreq"`` (default from config).
          admit_threshold: admit on the N-th miss (default from config).
        """
        import jax.numpy as jnp

        from .config import get_config

        assert self.node_count > 0, (
            "enable_cold_cache needs a built feature "
            "(from_cpu_tensor / from_mmap first)"
        )
        n_cold = self.node_count - self.cache_count
        if n_cold <= 0:
            return self  # fully HBM-resident: nothing to overlay
        cfg = get_config()
        if rows is None:
            rows = max(1024, self.cache_count // 4)
        rows = int(min(rows, n_cold))
        if rows <= 0:
            return self
        from .ops.coldcache import ColdRowCache

        policy = policy or self.cold_cache_policy or cfg.cold_cache_policy
        admit = (admit_threshold if admit_threshold is not None
                 else cfg.cold_cache_admit)
        with self._plock:
            self.cold_cache = ColdRowCache(rows, n_cold, policy=policy,
                                           admit_threshold=admit)
            self._overlay = jnp.zeros((rows, self.dim),
                                      dtype=self._hot_dtype())
        return self

    # -- paged feature store (docs/FEATURE_CACHE.md) -------------------
    def _maybe_enable_paging(self):
        """Config-driven paged-store enable at build time
        (``feature_paged=on``).  Off by default: the staged three-tier
        merge stays byte-identical — same metric keys, same executable
        keys — until paging is opted into."""
        from .config import get_config

        cfg = get_config()
        if cfg.feature_paged != "on":
            return
        if self.cache_count >= self.node_count:
            return  # fully hot: pure-device gather, nothing to page
        self.enable_paging(
            page_rows=cfg.feature_page_rows or None,
            pool_pages=cfg.feature_page_pool or None)

    def enable_paging(self, page_rows: Optional[int] = None,
                      pool_pages: Optional[int] = None,
                      policy: Optional[str] = None) -> "Feature":
        """Attach the paged store: pack the table into fixed-size HBM
        pages and serve every budgeted gather through the ragged
        page-gather kernel (``ops/paged.py``).

        The three tiers become page residency states — the hot prefix
        is the pinned DEVICE pages, the overlay is the OVERLAY frame
        pool, the host tail is HOST pages faulted in whole.  The staged
        merge stays attached underneath as the correctness fallback for
        batches whose page working set exceeds the pool.

        Args:
          page_rows: rows per page.  Default: smallest row count whose
            page is a multiple of the 512B HBM transaction and at least
            4KiB (``default_page_rows``).
          pool_pages: OVERLAY pool capacity in pages.  Default: a
            quarter of the host-page count (min 8), capped at the
            host-page count.
          policy: page-table eviction policy, ``"clock"`` | ``"minfreq"``
            (default from config ``cold_cache_policy``).
        """
        from .config import get_config
        from .ops.paged import PagedStore, PageTable, default_page_rows

        assert self.node_count > 0, (
            "enable_paging needs a built feature "
            "(from_cpu_tensor / from_mmap first)")
        n_cold = self.node_count - self.cache_count
        if n_cold <= 0:
            return self  # fully HBM-resident: nothing to page
        import jax

        if jax.default_backend() == "tpu":
            # the page-gather kernel compiles with Mosaic there: refuse
            # now, by name, what its compiler would refuse at first use
            from .ops.pallas import check_lane_width

            check_lane_width("feature_paged (page_gather)", self.dim)
        dt = np.dtype(self._hot_dtype())
        row_bytes = dt.itemsize * self.dim
        R = int(page_rows) if page_rows else default_page_rows(row_bytes)
        n_pages = -(-self.node_count // R)
        hot_pages = -(-self.cache_count // R) if self.cache_count else 0
        n_host_pages = n_pages - min(hot_pages, n_pages)
        if pool_pages is None:
            pool_pages = max(8, n_host_pages // 4)
        pool_pages = min(int(pool_pages), n_host_pages)
        policy = policy or self.cold_cache_policy \
            or get_config().cold_cache_policy
        table = PageTable(self.node_count, self.cache_count, R,
                          pool_pages, policy=policy)
        # quiverlint: sync-ok[one-time hot-set migration at paging enablement]
        # (never on the lookup path)
        hot_np = (np.asarray(self.hot) if self.cache_count else None)
        store = PagedStore(table, self.cold, self.cache_count, self.dim,
                           dt, hot_host=hot_np)
        store._feature = self
        with self._plock:
            self.paged = store
        return self

    def invalidate_rows(self, node_ids) -> int:
        """Drop mutated rows (OLD node ids) from the cold-row overlay.

        The streaming tier calls this for every edge mutation's touched
        endpoints (``StreamingGraph.attach_feature``): a resident
        overlay slot would otherwise keep serving the pre-mutation
        value.  Rows in the static hot prefix are untouched — that tier
        is a partition of the table, not a cache, so staleness there is
        a feature-*update* problem, not an invalidation one.  Touch
        counts reset too: a mutated row re-earns admission from scratch
        (miss on next touch, re-admit on the one after, under the
        default second-touch policy).  Returns overlay slots dropped.
        """
        from . import telemetry

        if self.cold_cache is None and self.paged is None:
            return 0
        ids = np.atleast_1d(np.asarray(node_ids, dtype=np.int64))
        if self.feature_order is not None:
            ids = ids[(ids >= 0) & (ids < len(self.feature_order))]
            ids = np.asarray(self.feature_order)[ids]
        cold_ids = ids - self.cache_count
        cold_ids = cold_ids[cold_ids >= 0]
        with self._plock:
            cache = self.cold_cache
            dropped = (cache.invalidate_rows(cold_ids)
                       if cache is not None else 0)
            if self.paged is not None:
                # whole OVERLAY pages drop: one stale row poisons its page
                self.paged.invalidate_rows(cold_ids)
        if dropped:
            telemetry.counter("coldcache_invalidated_rows_total").inc(
                dropped)
        return dropped

    def export_coldcache_state(self) -> Optional[dict]:
        """Device-cache residency state for a recovery checkpoint
        (``None`` when neither overlay nor paged store is attached).
        Only metadata is exported — the row *values* live in the host
        cold tier and are re-gathered from it on restore.  With paging
        on, the page-table residency is exported instead (tagged
        ``kind="paged"``; the arrays ride the same pinned-dtype
        serialization as the overlay's)."""
        with self._plock:
            if self.paged is not None:
                return self.paged.export_state()
            cache = self.cold_cache
            return cache.export_state() if cache is not None else None

    def restore_coldcache_state(self, state: Optional[dict]) -> int:
        """Re-warm the overlay (or page table) from a checkpointed state.

        Restores the slot metadata, then refills the device table from
        the host cold tier for every resident slot — restoring the map
        without the values would serve zeros for "cached" rows.  The
        geometry must match (``ValueError`` otherwise — the caller
        starts cold).  Kind mismatches degrade cleanly: a paged
        snapshot restored into a ``feature_paged=off`` build (or vice
        versa) starts cold instead of refusing boot.  Returns the
        number of rows re-warmed.
        """
        import jax.numpy as jnp

        if state is None:
            return 0
        if state.get("kind") == "paged":
            if self.paged is None:
                return 0  # paging off now: degrade to a cold start
            with self._plock:
                return self.paged.restore_state(state)
        if self.paged is not None and self.cold_cache is None:
            return 0  # staged snapshot, paged-only build: start cold
        if self.cold_cache is None:
            self.enable_cold_cache(rows=int(state["capacity"]))
        if self.cold_cache is None:
            return 0  # fully hot: nothing to overlay
        with self._plock:
            cache = self.cold_cache
            cache.restore_state(state)
            slots = np.nonzero(cache.node_of >= 0)[0]
            if slots.size:
                rel = cache.node_of[slots]
                rows = np.ascontiguousarray(self.cold[rel],
                                            dtype=self._hot_dtype())
                self._overlay = self._overlay.at[jnp.asarray(slots)].set(
                    jnp.asarray(rows))
        return int(slots.size)

    # ------------------------------------------------------------------
    def __getitem__(self, node_idx):
        """Gather rows by (old) node id; returns a device array.

        Hot rows come from HBM (one fused XLA gather — sharded arrays make
        XLA emit the cross-chip collective); cold rows are gathered on host
        and shipped once per batch, then merged on device.  Parity:
        ``feature.py:296-333`` + ``shard_tensor.py:154-180``.

        Fully-cached features take a pure-device path: jax-array ids never
        round-trip through the host (the reference pays a cudaMemcpy here
        only when ids arrive on CPU; same idea).
        """
        import jax
        import jax.numpy as jnp

        from . import telemetry

        self.lazy_init_from_ipc_handle()
        tier = ("hot" if self.cache_count >= self.node_count else
                ("cold" if self.cache_count == 0 else "mixed"))
        with telemetry.span(HOST_GETITEM), telemetry.histogram(
                "feature_gather_seconds", tier=tier).time():
            out = self._getitem_impl(node_idx, jax, jnp, telemetry)
        telemetry.counter("feature_gather_batches_total", tier=tier).inc()
        return out

    def _getitem_impl(self, node_idx, jax, jnp, telemetry):
        if self.cache_count >= self.node_count:
            if isinstance(node_idx, jax.Array):
                return self.lookup_device(node_idx)
            idx = np.asarray(node_idx)
            if self.feature_order is not None:
                idx = self.feature_order[idx]
            return jnp.take(self.hot, jnp.asarray(idx), axis=0)
        idx = np.asarray(node_idx)
        staged = self._take_staged(idx.tobytes())
        if self._pool is not None:
            telemetry.counter(
                "feature_prefetch_total",
                result="hit" if staged is not None else "miss").inc()
        if staged is None:
            staged = self._stage(idx)
        if staged[0] == "pg":
            # paged path: ONE ragged-kernel program per batch size (the
            # inverse-permutation take fuses into it) — the entire
            # (B, bucket) x ("z"/"patch", bc/bh) grid collapses here
            return self.paged.finish(staged, self)
        if staged[0] == "ov":
            # additive program structure: base two-way merge keyed by
            # the fresh bucket, then a separate overlay patch keyed by
            # the hit bucket — |bc| + |bh| executables, never |bc|x|bh|
            # combos (hit counts fluctuate batch to batch; a fused
            # three-way program would compile per combination)
            (_, hot_idx, bc, cold_pos_d, cold_rows_d,
             bh, ov_slot_d, ov_pos_d, ov_table) = staged
            B = len(idx)
            if hot_idx is None:
                if bc == 0:
                    out = self._merge_fn(B, ("z", 0), jax, jnp)()
                else:
                    out = self._merge_fn(B, ("z", bc), jax, jnp)(
                        cold_rows_d, cold_pos_d)
            else:
                out = self._merge_fn(B, bc, jax, jnp)(
                    self.hot, hot_idx, cold_rows_d, cold_pos_d)
            if bh:
                out = self._merge_fn(B, ("patch", bh), jax, jnp)(
                    out, ov_table, ov_slot_d, ov_pos_d)
            return out
        _, hot_idx, bucket, cold_pos_d, cold_rows_d = staged
        return self._merge_fn(len(idx), bucket, jax, jnp)(
            self.hot, hot_idx, cold_rows_d, cold_pos_d
        )

    def _take_staged(self, key):
        """Claim a prefetched stage for ``key``, waiting on in-flight
        prefetch work if needed (single FIFO worker: futures complete in
        submit order, so draining the oldest either surfaces our entry or
        proves it was never prefetched — never a duplicated gather)."""
        if self._pool is None:
            return None
        with self._plock:
            staged = self._pending.pop(key, None)
        while staged is None and self._inflight:
            try:
                fut = self._inflight.popleft()
            except IndexError:
                break
            fut.result()
            with self._plock:
                staged = self._pending.pop(key, None)
        return staged

    def _stage(self, idx):
        """Host side of a budgeted gather: translate ids, probe the
        overlay cache (if enabled), fetch ONLY the fresh cold rows from
        the host tier, start their H2D copy.

        The cold-row count is padded to a power-of-two bucket so the device
        merge compiles once per (batch, bucket) instead of per batch — and
        only ``~n_cold`` rows cross PCIe, not the full batch width (the
        round-1 path gathered full-size hot AND cold then ``where``-merged:
        2x traffic).  With the overlay enabled, the
        recurring part of those cold rows stops crossing at all — it is
        served from the HBM overlay table (``_stage_overlay``).
        """
        import jax
        import jax.numpy as jnp

        from . import telemetry

        if self.feature_order is not None:
            idx = self.feature_order[idx]
        idx = idx.astype(np.int64)
        if self.paged is not None and len(idx):
            with self._plock:
                st = self.paged.stage(idx, jnp, telemetry)
            if st is not None:
                return st
            # pool overflow: this batch's page working set doesn't fit
            # the OVERLAY pool — the staged merge below is the fallback
        if self.cold_cache is not None:
            return self._stage_overlay(idx, jax, jnp, telemetry)
        if self.cache_count == 0:
            n = len(idx)
            telemetry.counter("feature_rows_total", tier="cold").inc(
                float(n))
            return ("m", None, -1, None,
                    self._upload_cold(idx, n, n, jnp, telemetry))
        hot_mask = idx < self.cache_count
        cold_pos = np.nonzero(~hot_mask)[0].astype(np.int32)
        n_cold = len(cold_pos)
        # cache-hit accounting for the budgeted tier: a "hot" row is a
        # cache hit served from HBM, a "cold" row crosses the host link
        telemetry.counter("feature_rows_total", tier="hot").inc(
            float(len(idx) - n_cold))
        from .telemetry import flightrec

        if flightrec.tracing():
            flightrec.event("feature.stage", {
                "rows_hot": int(len(idx) - n_cold), "rows_cold": int(n_cold)})
        if n_cold:
            telemetry.counter("feature_rows_total", tier="cold").inc(
                float(n_cold))
        hot_idx = jnp.asarray(np.where(hot_mask, idx, 0).astype(np.int32))
        if n_cold == 0:
            return ("m", hot_idx, 0, None, None)
        bucket = _pow2_bucket(n_cold)
        # the bucket must cover every real row — padded lanes beyond
        # n_cold read only the zero-filled staging tail, never past the
        # buffer, including when B lands exactly on a bucket edge
        assert 0 < n_cold <= bucket, (n_cold, bucket)
        rows_d = self._upload_cold(idx[cold_pos] - self.cache_count,
                                   n_cold, bucket, jnp, telemetry)
        # pad positions with the out-of-range sentinel len(idx) == B;
        # the device scatter drops them (mode="drop")
        pos = np.full(bucket, len(idx), dtype=np.int32)
        pos[:n_cold] = cold_pos
        assert (pos[n_cold:] >= len(idx)).all(), \
            "padding sentinel must stay out of range of the output"
        return ("m", hot_idx, bucket, jnp.asarray(pos), rows_d)

    def _upload_cold(self, rel_ids, n_rows, bucket, jnp, telemetry):
        """Gather ``rel_ids`` from the host cold tier into the reusable
        per-bucket staging buffer and start its H2D copy.

        One long-lived buffer per bucket size instead of a fresh
        ``np.zeros((bucket, dim))`` per batch; ``jnp.array`` (copy
        semantics — never ``jnp.asarray``, which may alias host memory
        on the CPU backend) detaches the device copy before the buffer
        can be reused.  The shipped payload lands on
        ``feature_h2d_bytes_total``."""
        dt = np.dtype(self._hot_dtype())
        with self._plock:
            buf = self._stage_bufs.get(bucket)
            if buf is None or buf.shape != (bucket, self.dim) \
                    or buf.dtype != dt:
                buf = np.zeros((bucket, self.dim), dtype=dt)
                self._stage_bufs[bucket] = buf
            buf[:n_rows] = self.cold[rel_ids]
            rows_d = jnp.array(buf)
        telemetry.counter("feature_h2d_bytes_total").inc(float(buf.nbytes))
        from .telemetry import flightrec

        if flightrec.tracing():
            flightrec.event("feature.h2d", {"bytes": int(buf.nbytes),
                                            "rows": int(n_rows)})
        return rows_d

    def _stage_overlay(self, idx, jax, jnp, telemetry):
        """Three-tier staging: hot-prefix split, overlay probe, host
        fetch for the remaining fresh rows, then overlay admission.

        Probe + admission + the device-table update run under ``_plock``
        as one atomic step, and the staged tuple captures the overlay
        *value* current at probe time: a concurrent stage (sync gather
        racing the prefetch worker) that admits-and-evicts can never
        retarget slots under an already-staged merge, because jax arrays
        are immutable — the captured value keeps serving exactly the
        rows its metadata promised.
        """
        B = len(idx)
        cc = self.cache_count
        if cc > 0:
            hot_mask = idx < cc
            cold_pos_all = np.nonzero(~hot_mask)[0].astype(np.int32)
            hot_idx = jnp.asarray(
                np.where(hot_mask, idx, 0).astype(np.int32))
            telemetry.counter("feature_rows_total", tier="hot").inc(
                float(B - len(cold_pos_all)))
        else:
            cold_pos_all = np.arange(B, dtype=np.int32)
            hot_idx = None
        n_cold = len(cold_pos_all)
        if n_cold == 0:
            return ("m", hot_idx, 0, None, None)
        telemetry.counter("feature_rows_total", tier="cold").inc(
            float(n_cold))
        rel = idx[cold_pos_all] - cc
        dt = np.dtype(self._hot_dtype())
        h2d_bytes = 0
        n_evicted = 0
        with self._plock:
            cache = self.cold_cache
            hit_mask, slots = cache.probe(rel)
            n_hit = int(hit_mask.sum())
            n_fresh = n_cold - n_hit
            ov_table = self._overlay  # value consistent with the probe
            bh = _pow2_bucket(n_hit)
            ov_slot_d = ov_pos_d = None
            # bucket-edge discipline (regression-tested): every bucket
            # covers its real rows, padded lanes carry the out-of-range
            # sentinel B and zero-filled buffer tails only
            assert n_hit <= bh, (n_hit, bh)
            if bh:
                ov_slot = np.zeros(bh, dtype=np.int32)
                ov_slot[:n_hit] = slots[hit_mask]
                ov_pos = np.full(bh, B, dtype=np.int32)
                ov_pos[:n_hit] = cold_pos_all[hit_mask]
                ov_slot_d = jnp.asarray(ov_slot)
                ov_pos_d = jnp.asarray(ov_pos)
            bc = _fresh_bucket(n_fresh)
            rows_d = cold_pos_d = None
            assert n_fresh <= bc, (n_fresh, bc)
            if bc:
                fresh_rel = rel[~hit_mask]
                buf = self._stage_bufs.get(bc)
                if buf is None or buf.shape != (bc, self.dim) \
                        or buf.dtype != dt:
                    buf = np.zeros((bc, self.dim), dtype=dt)
                    self._stage_bufs[bc] = buf
                buf[:n_fresh] = self.cold[fresh_rel]
                rows_d = jnp.array(buf)  # copy: the buffer is reusable
                h2d_bytes = buf.nbytes
                pos = np.full(bc, B, dtype=np.int32)
                pos[:n_fresh] = cold_pos_all[~hit_mask]
                cold_pos_d = jnp.asarray(pos)
                adm, n_evicted = cache.admit(fresh_rel)
                if (adm >= 0).any():
                    # scatter the admitted subset of the freshly shipped
                    # rows into the overlay, in the same (already paid)
                    # H2D payload; non-admitted rows pad to slot C (drop)
                    adm_slot = np.full(bc, cache.capacity, dtype=np.int32)
                    adm_slot[:n_fresh] = np.where(adm >= 0, adm,
                                                  cache.capacity)
                    self._overlay = self._admit_fn(bc, jax, jnp)(
                        self._overlay, jnp.asarray(adm_slot), rows_d)
        telemetry.counter("feature_coldcache_rows_total",
                          result="hit").inc(float(n_hit))
        telemetry.counter("feature_coldcache_rows_total",
                          result="miss").inc(float(n_fresh))
        if n_evicted:
            telemetry.counter("feature_coldcache_evictions_total").inc(
                float(n_evicted))
        if h2d_bytes:
            telemetry.counter("feature_h2d_bytes_total").inc(
                float(h2d_bytes))
        from .telemetry import flightrec

        if flightrec.tracing():
            # per-request attribution of the aggregate coldcache
            # counters above — which requests are paying the host link
            flightrec.event("feature.coldcache", {
                "hit": int(n_hit), "miss": int(n_fresh),
                "evicted": int(n_evicted), "h2d_bytes": int(h2d_bytes)})
        return ("ov", hot_idx, bc, cold_pos_d, rows_d,
                bh, ov_slot_d, ov_pos_d, ov_table)

    def _hot_dtype(self):
        return self.hot.dtype if self.hot is not None else (
            self.dtype or np.float32
        )

    def _merge_fn(self, B, bucket, jax, jnp):
        """One cached executable per (batch size, cold bucket)."""
        fn = self._merge_cache.get((B, bucket))
        if fn is None:
            if isinstance(bucket, tuple):  # ("z", bc) | ("patch", bh)
                fn = self._build_overlay_fn(B, bucket, jax, jnp)
            elif bucket < 0:    # pure cold tier: rows arrive ready
                fn = lambda hot, hi, rows, pos: rows
            elif bucket == 0:   # all-hot batch

                @jax.jit
                def fn(hot, hot_idx, cold_rows, cold_pos):
                    return jnp.take(hot, hot_idx, axis=0)
            else:

                @jax.jit
                def fn(hot, hot_idx, cold_rows, cold_pos):
                    out = jnp.take(hot, hot_idx, axis=0)
                    return out.at[cold_pos].set(cold_rows, mode="drop")
            # quiverlint: ignore[QT014] -- B is one-executable-per-batch-
            # size by design (serving pads upstream via _pad_ids); the
            # bucket component is always produced by _pow2_bucket /
            # _fresh_bucket in _stage/_stage_overlay, but rides through
            # the prefetch dict as an opaque staged tuple, which is
            # where the symbolic trace loses it.
            self._merge_cache[(B, bucket)] = fn
        return fn

    def _build_overlay_fn(self, B, key, jax, jnp):
        """Overlay companion programs for the base two-way merge:

        * ``("z", bc)`` — pure-cold base (no hot prefix): zeros, with
          the fresh rows scattered in (``bc == 0``: just the zeros).
        * ``("patch", bh)`` — scatter ``bh`` overlay hits (gathered from
          the HBM table) over the base merge's output.

        Pad positions are ``B`` and pad slots ``capacity``; both fall
        off via ``mode="drop"``."""
        kind = key[0]
        dim = self.dim
        dt = self._hot_dtype()
        if kind == "z":
            if key[1] == 0:

                @jax.jit
                def fn():
                    return jnp.zeros((B, dim), dtype=dt)
            else:

                @jax.jit
                def fn(cold_rows, cold_pos):
                    out = jnp.zeros((B, dim), dtype=dt)
                    return out.at[cold_pos].set(cold_rows, mode="drop")
        else:  # "patch"

            @jax.jit
            def fn(out, table, ov_slot, ov_pos):
                rows = jnp.take(table, ov_slot, axis=0)
                return out.at[ov_pos].set(rows, mode="drop")

        return fn

    def _admit_fn(self, bucket, jax, jnp):
        """Cached scatter-update program writing admitted rows into the
        overlay table (pad slot = capacity, dropped).  Keyed in
        ``_merge_cache`` so ``retrace_guard`` counts its builds too.  No
        buffer donation: staged merges may still hold the old table
        value (see ``_stage_overlay``)."""
        fn = self._merge_cache.get(("admit", bucket))
        if fn is None:

            @jax.jit
            def fn(table, slots, rows):
                return table.at[slots].set(rows, mode="drop")

            self._merge_cache[("admit", bucket)] = fn
        return fn

    def _paged_fn(self, B):
        """ONE cached executable per batch size on the paged path: the
        ragged page-gather kernel plus the inverse-permutation take that
        undoes the planner's sort-by-frame.  Keyed ``("paged", B)`` in
        ``_merge_cache`` — the whole additive bucket grid of the staged
        path collapses to this single entry (plus the fault scatter's
        pow2 warmup, ``_paged_fault_fn``)."""
        import jax
        import jax.numpy as jnp

        fn = self._merge_cache.get(("paged", B))
        if fn is None:
            from .ops.pallas.page_gather_kernel import page_gather

            store = self.paged
            page_rows = store.table.page_rows
            block, ppb = store.block, store.ppb
            interpret = store._interpret

            @jax.jit
            def fn(frames, blk_pages, blk_np, row_lp, row_off, rank):
                out = page_gather(
                    frames, blk_pages, blk_np, row_lp, row_off,
                    page_rows=page_rows, block=block, ppb=ppb,
                    interpret=interpret)
                return jnp.take(out, rank, axis=0)

            # quiverlint: ignore[QT014] -- one executable per batch size
            # is this path's contract (the whole (B, bucket) grid
            # collapses to it); B arrives inside the planner's staged
            # tuple through the duck-typed PagedStore.finish edge, which
            # the symbolic trace cannot follow.
            self._merge_cache[("paged", B)] = fn
        return fn

    def _paged_fault_fn(self, k_pad):
        """Cached scatter writing a pow2-padded batch of faulted pages
        into the frame pool (pad slot = ``n_frames``, dropped).  The
        paged analogue of ``_admit_fn`` — no buffer donation: staged
        plans may still hold the old frames value."""
        import jax

        fn = self._merge_cache.get(("pgfault", k_pad))
        if fn is None:

            @jax.jit
            def fn(frames, slots, pages):
                return frames.at[slots].set(pages, mode="drop")

            # quiverlint: ignore[QT014] -- k_pad is pow2-padded at the
            # fault site (ops/paged._fault: _pow2_bucket over the miss
            # count); the call reaches here through the duck-typed
            # PagedStore._feature receiver, which hides the edge from
            # the resolver.
            self._merge_cache[("pgfault", k_pad)] = fn
        return fn

    # -- async cold-tier prefetch --------------------------------------
    def prefetch(self, node_idx):
        """Begin the host-side cold gather + H2D copy for ``node_idx`` on a
        worker thread; the matching ``feature[node_idx]`` call consumes it.

        TPU answer to the reference's in-kernel zero-copy host reads
        (``shard_tensor.cu.hpp:19-61``): there the device pulls host rows on
        demand inside the gather kernel; here the host pushes the (few) cold
        rows toward the device while the previous step computes, so the
        merge sees them already in flight.  ``SeedLoader`` calls this one
        batch ahead automatically.
        """
        if self.cache_count >= self.node_count:
            return  # nothing host-side to hide
        if self._pool is None:
            import atexit
            import collections
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="feature-prefetch"
            )
            # cancel queued stages at interpreter exit: a straggler
            # worker touching jax arrays during runtime teardown aborts
            # the process (C++ terminate)
            atexit.register(self._pool.shutdown, wait=False,
                            cancel_futures=True)
            self._inflight = collections.deque()

        from .telemetry import flightrec

        # capture the caller's trace contexts at submit time: the pool
        # worker does not inherit contextvars, and re-activating inside
        # work() attributes the staged gather (coldcache probes, H2D) to
        # the originating request instead of to an anonymous thread
        ctxs = flightrec.active()

        def work():
            # materialize here (may block on the device sample that
            # produced node_idx) so the CALLER never does
            with flightrec.activate(ctxs):
                idx = np.asarray(node_idx)
                if flightrec.tracing():
                    flightrec.event("feature.prefetch",
                                    {"rows": int(len(idx))})
                staged = self._stage(idx)
            with self._plock:
                self._pending[idx.tobytes()] = staged
                while len(self._pending) > 8:  # drop oldest unclaimed
                    self._pending.pop(next(iter(self._pending)))

        self._inflight.append(self._pool.submit(work))
        # age out only FINISHED futures: dropping a pending one would break
        # _take_staged's FIFO-drain (its key could never be waited for,
        # forcing a duplicate synchronous gather)
        while len(self._inflight) > 8 and self._inflight[0].done():
            self._inflight.popleft()

    def _device_tables(self):
        """``(hot, order)``: the device arrays :meth:`lookup_device`
        reads (``order`` is None without a cache reorder).  Jitted
        pipelines pass these as ARGUMENTS to :func:`_lookup_tables` — a
        table captured by the closure is baked into the executable as a
        constant, one more copy of it in HBM per program."""
        import jax.numpy as jnp

        self.lazy_init_from_ipc_handle()
        assert 0 < self.node_count <= self.cache_count, (
            "lookup_device needs a (built) fully HBM-resident feature"
        )
        if (self.feature_order is not None
                and getattr(self, "_order_dev", None) is None):
            self._order_dev = jnp.asarray(
                self.feature_order.astype(np.int32)
            )
        return self.hot, getattr(self, "_order_dev", None)

    def lookup_device(self, idx, mask=None):
        """Pure-device gather for jit pipelines (requires full HBM cache).
        Applies ``feature_order`` on device; safe to call under jit.
        ``mask`` (a sampler's ``n_id_mask`` beside its ``n_id``): see
        :func:`_lookup_tables`; called eagerly with one, the slots asked
        for and the live ones among them are counted, so that a
        frontier's live share is a reading."""
        import jax

        if mask is not None and not isinstance(mask, jax.core.Tracer):
            from . import telemetry

            telemetry.counter("feature_gather_slots_total").inc(
                float(mask.shape[0]))
            # one read of the mask, on the eager path only
            telemetry.counter("feature_gather_live_slots_total").inc(
                float(np.asarray(mask).sum()))
        return _lookup_tables(self._device_tables(), idx, mask)

    # ------------------------------------------------------------------
    def size(self, dim: int) -> int:
        return (self.node_count, self.dim)[dim]

    @property
    def shape(self):
        return (self.node_count, self.dim)

    def dim_(self):
        return self.dim

    # ------------------------------------------------------------------
    # IPC-parity API: single-controller jax needs no cudaIpc; we pack the
    # construction recipe so reference-style mp code keeps working.
    # (feature.py:383-458)
    def share_ipc(self):
        return (
            dict(rank=self.rank, device_cache_size=self.device_cache_size,
                 cache_policy=self.cache_policy),
            self.hot, self.cold, self.feature_order,
            self.cache_count, self.node_count, self.dim,
        )

    @classmethod
    def new_from_ipc_handle(cls, rank, ipc_handle):
        cfg, hot, cold, order, cc, nc, dim = ipc_handle
        cfg = dict(cfg)
        cfg["rank"] = rank
        self = cls(**cfg)
        self.hot, self.cold, self.feature_order = hot, cold, order
        self.cache_count, self.node_count, self.dim = cc, nc, dim
        return self

    @classmethod
    def lazy_from_ipc_handle(cls, ipc_handle):
        self = cls(rank=0)
        self._lazy_state = ipc_handle
        return self

    def __repr__(self):
        return (
            f"Feature(nodes={self.node_count}, dim={self.dim}, "
            f"hot={self.cache_count}, policy={self.cache_policy!r})"
        )

    def lazy_init_from_ipc_handle(self):
        if self._lazy_state is None:
            return
        cfg, hot, cold, order, cc, nc, dim = self._lazy_state
        with self._plock:
            self.hot, self.cold, self.feature_order = hot, cold, order
            self.cache_count, self.node_count, self.dim = cc, nc, dim
        self._lazy_state = None
