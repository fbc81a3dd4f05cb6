"""Paged feature store: page table, residency states, fault planner.

ROADMAP item 2: the Ragged Paged Attention design (PAPERS.md, arxiv
2604.15464) applied to the data layer.  Feature rows are packed into
fixed-size HBM pages (``page_rows`` x row-bytes, a multiple of the 512B
HBM transaction) and the three storage tiers of the staged merge — hot
prefix, coldcache overlay, host tail — collapse into **page residency
states** over one frame pool:

  * ``DEVICE`` — pages of the degree-ordered hot prefix; pinned
    resident at frames ``[0, hot_pages)``, never evicted.
  * ``OVERLAY`` — host pages currently faulted into the overlay pool
    (frames ``[hot_pages, hot_pages + pool)``); CLOCK-evicted.
  * ``HOST`` — pages resident only in the host tail; a gather touching
    one faults the whole page in as part of the batch's single H2D
    transfer.

One ragged Pallas kernel (``ops/pallas/page_gather_kernel.py``) then
gathers any frontier by walking ``(page, offset)`` pairs with
page-granularity DMA — no pow2 padding, no quarter-octave
``_fresh_bucket`` machinery, and ONE executable per batch size instead
of the staged path's additive ``(B, bucket)`` x ``("z"/"patch", bc/bh)``
grid.

Division of labor (mirrors ``ops/coldcache.py``):

  * **this module** — host-side planning: id -> (frame, offset)
    translation, fault detection, page-table bookkeeping (a
    :class:`~quiver_tpu.ops.coldcache.ColdRowCache` over host-*page*
    space, so CLOCK eviction, invalidation, and checkpoint
    export/restore are shared code), and the sorted block plan the
    kernel prefetches.
  * **feature.py** — orchestration: the staged-tuple plumbing
    (prefetch pool, ``_pending`` claims) and the per-``B`` program
    cache (``_paged_fn``; counted by ``retrace_guard`` and sealed by
    the recovery registry like every other executable cache).

Thread-safety: externally synchronized — the owning ``Feature`` holds
``_plock`` across :meth:`PagedStore.stage`, same contract as
``ColdRowCache``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..analysis.staging import no_sync
from .coldcache import ColdRowCache

__all__ = ["PagedStore", "PageTable", "default_page_rows",
           "DEVICE", "OVERLAY", "HOST", "PAGE_STATES"]

# page residency states (docs/FEATURE_CACHE.md)
DEVICE, OVERLAY, HOST = 0, 1, 2
PAGE_STATES = {"DEVICE": DEVICE, "OVERLAY": OVERLAY, "HOST": HOST}

_TXN_BYTES = 512          # HBM transaction granularity
_TARGET_PAGE_BYTES = 4096  # auto-sizing floor: 8 transactions per page
_VMEM_BUDGET = 2 << 20     # kernel scratch budget for the page window


def default_page_rows(row_bytes: int,
                      target_bytes: int = _TARGET_PAGE_BYTES) -> int:
    """Smallest row count whose page is a 512B-transaction multiple and
    at least ``target_bytes`` (the gather then moves whole transactions,
    never partial ones).  Falls back to a plain ``target_bytes`` fill
    when no multiple exists within 4096 rows (odd row widths)."""
    row_bytes = max(int(row_bytes), 1)
    fill = max(1, -(-target_bytes // row_bytes))
    for r in range(fill, fill + 4096):
        if (r * row_bytes) % _TXN_BYTES == 0:
            return r
    return fill


def _plan_geometry(page_rows: int, dim: int, itemsize: int
                   ) -> Tuple[int, int]:
    """(block, ppb) for the kernel: output rows per grid program and the
    worst-case distinct pages per block, fit to the VMEM scratch budget
    (every row of a block could touch its own page)."""
    page_bytes = max(page_rows * dim * itemsize, 1)
    block = max(8, min(128, _VMEM_BUDGET // page_bytes))
    # round down to a multiple of 8 so padded lengths stay lane-friendly
    block = max(8, (block // 8) * 8)
    return block, block


class PageTable:
    """Residency bookkeeping over the page space of one feature table.

    Pages partition the row space ``[0, N)``: page ``p`` covers rows
    ``[p*R, (p+1)*R)``.  The hot prefix is rounded UP to whole pages
    (``hot_pages``) — boundary rows past ``cache_count`` are filled
    from the host tail at build, so the padding is real data, not
    zeros, and the paged gather stays bit-identical to the staged
    merge.  Host pages are tracked by a :class:`ColdRowCache` whose
    "rows" are pages (``admit_threshold=1``: a touched HOST page must
    fault in to be served at all).
    """

    def __init__(self, n_rows: int, cache_count: int, page_rows: int,
                 pool_pages: int, policy: str = "clock"):
        assert page_rows > 0, page_rows
        self.page_rows = int(page_rows)
        self.n_rows = int(n_rows)
        self.n_pages = -(-self.n_rows // self.page_rows)
        self.hot_pages = (-(-int(cache_count) // self.page_rows)
                          if cache_count > 0 else 0)
        self.hot_pages = min(self.hot_pages, self.n_pages)
        self.n_host_pages = self.n_pages - self.hot_pages
        pool_pages = int(min(pool_pages, self.n_host_pages))
        self.pool_pages = max(pool_pages, 0)
        # page residency map: ColdRowCache over host-page ids — CLOCK
        # eviction, invalidation, and export/restore_state all reused
        self.cache = (ColdRowCache(self.pool_pages, self.n_host_pages,
                                   policy=policy, admit_threshold=1)
                      if self.pool_pages > 0 and self.n_host_pages > 0
                      else None)

    def state_of(self, page: int) -> int:
        """Residency state of one logical page (telemetry / tests)."""
        if page < self.hot_pages:
            return DEVICE
        if (self.cache is not None
                and self.cache.slot_of[page - self.hot_pages] >= 0):
            return OVERLAY
        return HOST

    @property
    def n_frames(self) -> int:
        return self.hot_pages + self.pool_pages

    def resident_pages(self) -> int:
        return self.hot_pages + (self.cache.resident
                                 if self.cache is not None else 0)


class PagedStore:
    """Device frame pool + fault planner behind ``Feature``'s paged path.

    Built by :meth:`Feature.enable_paging`.  Owns the ``[F, R, D]``
    frames array (DEVICE pages written once at build, OVERLAY pool
    faulted on demand), the reusable locked staging buffers for
    whole-page H2D fault transfers, and the block plan handed to the
    ragged kernel.  All mutation happens under the owning feature's
    ``_plock`` (**externally synchronized**, same contract as
    ``ColdRowCache`` — no lock of its own); the staged tuple captures
    the frames *value* at plan time, so a concurrent fault/evict can
    never retarget pages under an already-planned gather (jax arrays
    are immutable — the same capture discipline as ``_stage_overlay``).
    """

    def __init__(self, table: PageTable, host_rows, cache_count: int,
                 dim: int, dtype, hot_host=None):
        import jax
        import jax.numpy as jnp

        self.table = table
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self._feature = None            # owning Feature (set on attach)
        self._host = host_rows          # host tail [N - cache_count, D]
        self._cc = int(cache_count)
        R = table.page_rows
        self.page_bytes = R * self.dim * self.dtype.itemsize
        self.block, self.ppb = _plan_geometry(R, self.dim,
                                             self.dtype.itemsize)
        # frame pool: hot pages first (boundary page filled from the
        # host tail so its rows are real data), then the overlay pool
        frames_np = np.zeros((table.n_frames, R, self.dim),
                             dtype=self.dtype)
        hot_rows = min(table.hot_pages * R, table.n_rows)
        if hot_rows:
            flat = frames_np[:table.hot_pages].reshape(-1, self.dim)
            n_dev = min(self._cc, hot_rows)
            if n_dev:
                flat[:n_dev] = np.asarray(hot_host)[:n_dev]
            if hot_rows > n_dev:     # boundary page tail: host rows
                flat[n_dev:hot_rows] = np.asarray(
                    host_rows[:hot_rows - n_dev])
        self.frames = jnp.asarray(frames_np)
        self._page_bufs = {}            # k_pad -> [k_pad, R, D] staging
        self._interpret = jax.default_backend() != "tpu"
        self.fallbacks = 0              # batches the pool couldn't hold

    # ------------------------------------------------------------------
    def _fault_pages(self, host_pages: np.ndarray, jnp, telemetry
                    ) -> Optional[int]:
        """Fault the given (unique) HOST pages into the overlay pool as
        ONE whole-page H2D transfer.  Returns the number of pages
        faulted, or None when the pool cannot hold this batch's working
        set (the caller falls back to the staged path — correctness
        first, the counter makes the mis-sizing visible)."""
        cache = self.table.cache
        if cache is None:
            return None
        hit, _ = cache.probe(host_pages)
        fault = host_pages[~hit]
        if fault.size == 0:
            telemetry.counter("feature_page_hits_total").inc(
                float(host_pages.size))
            return 0
        # the batch's hit pages must survive the admission sweep: they
        # are about to be read by this very gather
        protect = cache.slot_of[host_pages[hit]]
        if fault.size + hit.sum() > cache.capacity:
            return None  # working set exceeds the pool: stage instead
        slots, n_evicted = cache.admit(fault, protect_slots=protect)
        if (slots < 0).any():
            return None  # admission couldn't place every fault
        R = self.table.page_rows
        k = int(fault.size)
        from ..feature import _pow2_bucket

        k_pad = _pow2_bucket(k)
        buf = self._page_bufs.get(k_pad)
        if buf is None or buf.shape != (k_pad, R, self.dim) \
                or buf.dtype != self.dtype:
            buf = np.zeros((k_pad, R, self.dim), dtype=self.dtype)
            self._page_bufs[k_pad] = buf
        base0 = self.table.hot_pages * R - self._cc  # host offset of page0
        for j, hp in enumerate(fault):
            lo = base0 + int(hp) * R
            hi = min(lo + R, len(self._host))
            rows = hi - lo
            buf[j, :rows] = self._host[lo:hi]
            if rows < R:               # partial tail page: zero pad
                buf[j, rows:] = 0
        pad_slot = np.full(k_pad, self.table.n_frames, dtype=np.int32)
        pad_slot[:k] = self.table.hot_pages + slots
        h2d_bytes = buf.nbytes         # whole padded transfer, host math
        rows_d = jnp.array(buf)        # copy: the buffer is reusable
        self.frames = self._feature._paged_fault_fn(k_pad)(
            self.frames, jnp.asarray(pad_slot), rows_d)
        telemetry.counter("feature_page_faults_total").inc(float(k))
        telemetry.counter("feature_page_hits_total").inc(
            float(int(hit.sum())))
        telemetry.counter("feature_h2d_bytes_total").inc(float(h2d_bytes))
        if n_evicted:
            telemetry.counter("feature_page_evictions_total").inc(
                float(n_evicted))
        telemetry.gauge("feature_page_resident_bytes").set(
            float(self.table.resident_pages() * self.page_bytes))
        from ..telemetry import flightrec, timeline

        if flightrec.tracing():
            # forwards to the unified timeline too, trace-correlated
            flightrec.event("feature.page_fault", {
                "pages": k, "evicted": int(n_evicted),
                "h2d_bytes": int(h2d_bytes)})
        elif timeline._ON:
            # faults from untraced gathers (warmup, loader prefetch)
            # still belong on the timeline
            timeline.emit("feature.page_fault", cat="paged", attrs={
                "pages": k, "evicted": int(n_evicted),
                "h2d_bytes": int(h2d_bytes)})
        return k

    # ------------------------------------------------------------------
    def stage(self, idx: np.ndarray, jnp, telemetry):
        """Translate (already feature-order-mapped) ids into the block
        plan the ragged kernel walks, faulting HOST pages first.

        Returns the staged tuple ``("pg", frames, blk_pages, blk_np,
        row_lp, row_off, rank, B)`` or ``None`` when the batch's page
        working set exceeds the overlay pool (caller stages instead).
        Caller holds the owning feature's ``_plock``.
        """
        R = self.table.page_rows
        t = self.table
        idx = idx.astype(np.int64)
        B = len(idx)
        page = idx // R
        is_host_space = page >= t.hot_pages
        if is_host_space.any():
            host_pages = np.unique(page[is_host_space] - t.hot_pages)
            if self._fault_pages(host_pages, jnp, telemetry) is None:
                self.fallbacks += 1
                telemetry.counter("feature_page_fallback_total").inc()
                return None
            slot = t.cache.slot_of[page[is_host_space] - t.hot_pages]
            assert (slot >= 0).all(), "fault left a HOST page unmapped"
        frame = page.astype(np.int32)
        if is_host_space.any():
            frame[is_host_space] = (t.hot_pages + slot).astype(np.int32)
        off = (idx % R).astype(np.int32)
        n_dev_rows = B - int(is_host_space.sum())
        telemetry.counter("feature_rows_total", tier="hot").inc(
            float(n_dev_rows))
        telemetry.counter("feature_rows_total", tier="cold").inc(
            float(B - n_dev_rows))
        # ---- sorted block plan (ragged: linear pad to `block`, not pow2)
        order = np.argsort(frame, kind="stable")
        sf, so = frame[order], off[order]
        blk = self.block
        Bpad = -(-B // blk) * blk
        nb = Bpad // blk
        row_lp = np.zeros(Bpad, dtype=np.int32)
        row_off = np.zeros(Bpad, dtype=np.int32)
        row_off[:B] = so
        blk_pages = np.zeros(nb * self.ppb, dtype=np.int32)
        blk_np = np.zeros(nb, dtype=np.int32)
        for b in range(nb):
            lo, hi = b * blk, min((b + 1) * blk, B)
            if lo >= B:
                break
            seg = sf[lo:hi]
            # distinct frames in first-appearance order: seg is sorted,
            # so np.unique's sorted order IS first-appearance order
            uniq, inv = np.unique(seg, return_inverse=True)
            blk_pages[b * self.ppb: b * self.ppb + len(uniq)] = uniq
            blk_np[b] = len(uniq)
            row_lp[lo:hi] = inv.astype(np.int32)
        rank = np.empty(B, dtype=np.int32)
        rank[order] = np.arange(B, dtype=np.int32)
        return ("pg", self.frames, jnp.asarray(blk_pages),
                jnp.asarray(blk_np), jnp.asarray(row_lp),
                jnp.asarray(row_off), jnp.asarray(rank), B)

    def frame_of_pages(self) -> np.ndarray:
        """Logical page -> resident frame map (``-1`` = HOST), the flat
        view the mesh tier stacks into its sharded page table — hot
        pages are pinned at their own index, overlay residents read
        from the CLOCK cache's slot map.  Caller holds ``_plock``."""
        t = self.table
        out = np.full(t.n_pages, -1, dtype=np.int32)
        out[:t.hot_pages] = np.arange(t.hot_pages, dtype=np.int32)
        if t.cache is not None:
            slot = t.cache.slot_of
            resident = slot >= 0
            out[t.hot_pages:][resident] = (
                t.hot_pages + slot[resident]).astype(np.int32)
        return out

    def finish(self, staged, feature):
        """Run the (cached) paged gather program over a staged plan."""
        (_, frames, blk_pages, blk_np, row_lp, row_off, rank, B) = staged
        fn = feature._paged_fn(B)
        # the gather itself must dispatch without blocking: callers
        # decide when (whether) to materialize the result
        with no_sync("paged gather"):
            return fn(frames, blk_pages, blk_np, row_lp, row_off, rank)

    # ------------------------------------------------------------------
    def invalidate_rows(self, rel_ids: np.ndarray) -> int:
        """Drop OVERLAY pages containing the given host-tail-relative
        row ids (stream mutations); DEVICE pages are a partition, not a
        cache — same contract as ``ColdRowCache.invalidate_rows``.
        Caller holds ``_plock``.  Returns pages dropped."""
        t = self.table
        if t.cache is None or rel_ids.size == 0:
            return 0
        R = t.page_rows
        pages = np.unique((rel_ids + self._cc) // R) - t.hot_pages
        dropped = t.cache.invalidate_rows(pages[pages >= 0])
        if dropped:
            from .. import telemetry

            telemetry.gauge("feature_page_resident_bytes").set(
                float(t.resident_pages() * self.page_bytes))
        return dropped

    # -- recovery (docs/RECOVERY.md) -----------------------------------
    def export_state(self) -> dict:
        """Page-table residency for a recovery checkpoint.  Flat dict:
        the page cache's arrays ride the existing ``_CC_PINNED``
        serialization; ``kind``/``page_rows`` are scalars in the
        checkpoint header, so a pre-paged build simply ignores them."""
        st = (self.table.cache.export_state()
              if self.table.cache is not None else {})
        st["kind"] = "paged"
        st["page_rows"] = self.table.page_rows
        return st

    def restore_state(self, state: dict) -> int:
        """Re-warm the overlay pool from a checkpointed page table:
        restore the residency map, then re-fault every resident page
        from the host tail (restoring the map without the page values
        would serve zeros).  Geometry mismatches raise ``ValueError``
        (the caller starts cold).  Returns rows re-warmed.  Caller
        holds ``_plock``."""
        import jax.numpy as jnp

        if int(state.get("page_rows", -1)) != self.table.page_rows:
            raise ValueError(
                f"page geometry changed: snapshot has page_rows="
                f"{state.get('page_rows')}, this store has "
                f"{self.table.page_rows}")
        cache = self.table.cache
        if cache is None:
            return 0
        cache.restore_state(state)
        slots = np.nonzero(cache.node_of >= 0)[0]
        if slots.size == 0:
            return 0
        R = self.table.page_rows
        base0 = self.table.hot_pages * R - self._cc
        pages_np = np.zeros((len(slots), R, self.dim), dtype=self.dtype)
        for j, s in enumerate(slots):
            lo = base0 + int(cache.node_of[s]) * R
            hi = min(lo + R, len(self._host))
            pages_np[j, :hi - lo] = self._host[lo:hi]
        frame_ids = (self.table.hot_pages + slots).astype(np.int32)
        self.frames = self.frames.at[jnp.asarray(frame_ids)].set(
            jnp.asarray(pages_np))
        from .. import telemetry

        telemetry.gauge("feature_page_resident_bytes").set(
            float(self.table.resident_pages() * self.page_bytes))
        return int(slots.size) * R

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        t = self.table
        return dict(
            page_rows=t.page_rows, page_bytes=self.page_bytes,
            n_pages=t.n_pages, hot_pages=t.hot_pages,
            pool_pages=t.pool_pages,
            resident_pages=t.resident_pages(),
            fallbacks=self.fallbacks,
            block=self.block, ppb=self.ppb,
            cache=(t.cache.stats() if t.cache is not None else None),
        )

    def __repr__(self):
        t = self.table
        return (f"PagedStore(pages={t.n_pages}, hot={t.hot_pages}, "
                f"pool={t.pool_pages}, page_rows={t.page_rows}, "
                f"page_bytes={self.page_bytes})")
