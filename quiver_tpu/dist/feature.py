"""Cross-host partitioned feature store — TPU-native ``DistFeature``.

Reference parity: ``PartitionInfo`` (``feature.py:461-526``) and
``DistFeature`` (``feature.py:529-567``) + the NCCL ``exchange``
(``comm.py:127-182``).

TPU-first redesign: the whole request/response dance — dispatch ids by
owner, send id lists, remote gather, send features back, scatter merge — is
ONE jitted ``shard_map`` body with two ``all_to_all``s a round.  Ragged
per-host request counts become buckets of a static length with validity
masks (the static-shape discipline): at the default sized for an owner's
share of the batch and shipped in as many rounds as the fullest bucket asks
for (one where the ids are spread, ``n`` where one host owns them all;
nothing dropped), under a caller's ``request_cap`` one round that drops and
counts what overflows (``dist/exchange.py``).

Layout: the partitioned feature lives as a single ``jax.Array`` of shape
``[n_parts * max_local, D]`` sharded over the mesh axis, so "host p's
shard" is rows ``[p*max_local, (p+1)*max_local)`` — device-local on p.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..feature import _lookup_tables
from ..parallel.train import replicate
from ..resilience import chaos
from ..resilience.deadline import check_ambient
from ..resilience.errors import PeerTimeout
from .. import telemetry
from ..telemetry.device_scopes import (FEATURE_GATHER, HOST_LOOKUP, LAUNCH,
                                       PLACE, exchange as exchange_scope,
                                       register_program)
from .exchange import (bucket_len, exchange, put_row_blocks,
                       record_exchange, shard_len)

__all__ = ["PartitionInfo", "DistFeature", "lookup_program"]

# fault-injection site for the cross-host exchange (no-op unless a
# chaos plan is installed)
_CHAOS_EXCHANGE = chaos.point("dist.feature.exchange")


class PartitionInfo:
    """Node -> (owner, local slot) maps (parity: ``feature.py:461-526``).

    Args:
      device: this rank (kept for parity).
      host: host index of this rank.
      hosts: number of hosts (partitions).
      global2host: ``[N]`` int array, owner host per node.
      replicate: optional id array of nodes replicated on every host.
    """

    def __init__(self, device=0, host: int = 0, hosts: int = 1,
                 global2host=None, replicate=None):
        self.device = device
        self.host = host
        self.hosts = hosts
        self.global2host = np.asarray(global2host, dtype=np.int32)
        n = self.global2host.shape[0]
        self.replicate_mask = np.zeros(n, dtype=bool)
        if replicate is not None:
            self.replicate_mask[np.asarray(replicate)] = True
        # local slot of each node on its owner (replicated nodes get a slot
        # on EVERY host: they're appended after the owned block).
        owner = self.global2host.copy()
        self.global2local = np.zeros(n, dtype=np.int32)
        owned_counts = np.zeros(hosts, dtype=np.int64)
        order = np.argsort(owner, kind="stable")
        for h in range(hosts):
            ids = order[owner[order] == h]
            ids = ids[~self.replicate_mask[ids]]
            self.global2local[ids] = np.arange(len(ids), dtype=np.int32)
            owned_counts[h] = len(ids)
        self.owned_counts = owned_counts
        rep_ids = np.nonzero(self.replicate_mask)[0]
        self.rep_ids = rep_ids
        # replicated nodes: slot = owned_count(host) + rank in rep list —
        # assigned at build time per host (see DistFeature.build_shards).
        self.max_local = int(owned_counts.max() + len(rep_ids))

    @classmethod
    def from_partition_book(cls, book, device=0, host: int = 0,
                            hosts: Optional[int] = None, replicate=None):
        """Build from a ``feature_partition_book`` (node -> partition id),
        the artifact written by :func:`quiver_tpu.quiver_partition_feature`
        (parity: the loader flow at partition.py:252-283)."""
        book = np.asarray(book)
        return cls(device=device, host=host,
                   hosts=hosts if hosts is not None else int(book.max()) + 1,
                   global2host=book, replicate=replicate)

    def dispatch(self, ids: np.ndarray):
        """Parity helper (``feature.py:510-526``): bucket ids per host.

        Returns (list of id arrays per host, list of position arrays).
        Served from DistFeature's jitted path in production; kept for tests
        and API compat.
        """
        ids = np.asarray(ids)
        owner = np.where(self.replicate_mask[ids], self.host,
                         self.global2host[ids])
        out_ids, out_pos = [], []
        for h in range(self.hosts):
            m = owner == h
            out_ids.append(ids[m])
            out_pos.append(np.nonzero(m)[0])
        return out_ids, out_pos


def lookup_program(mesh: Mesh, axis: str, cap, ranged: bool):
    """The jitted lookup over a sharded table, ``jit_qt_dist_lookup``:
    ``(shards [n, m, D], tables, ids [n, B], valid [n, B]) -> (rows [n, B,
    D], dropped [n], live [n], rounds [n])``.  ``cap``: a request bucket's
    slots for one round that drops what overflows, or None for the exact
    exchange in rounds (``dist.exchange.exchange``).  ``tables`` is the
    partition's own, replicated: ``{"row_starts"}`` when ``ranged``, else a
    global2host partition's five maps.  It holds no table: all arrive as
    arguments."""
    n = int(mesh.shape[axis])

    def body(shard, tables, ids, valid):
        # shard: [1, m, D]; ids, valid: [1, B] — this rank's query batch.
        shard = shard[0]
        ids, valid = ids[0], valid[0]
        me = jax.lax.axis_index(axis)
        with exchange_scope(FEATURE_GATHER):
            if ranged:
                starts = tables["row_starts"]
                # an id outside the table is nobody's: no request
                valid = valid & (ids >= 0) & (ids < starts[n])
                owner = (jnp.searchsorted(starts, ids, side="right")
                         - 1).astype(jnp.int32)
            else:
                owner = jnp.where(tables["rep_mask"][ids], me,
                                  tables["g2h"][ids])

        def fetch(rids, rvalid, r):
            with exchange_scope(FEATURE_GATHER):
                if ranged:
                    lslot = rids - starts[me]
                else:
                    rid = jnp.where(rvalid, rids, 0)
                    lslot = jnp.where(
                        tables["rep_mask"][rid],
                        tables["owned_counts"][me] + tables["rep_rank"][rid],
                        tables["g2l"][rid])
            # the owner's fetch, told which received slots are empty: an
            # empty slot asks for a row of its own (PERF.md, PR 33) and
            # its answer is never unpacked
            return _lookup_tables((shard, None), lslot, rvalid)

        # ship request ids to owners, features back to requesters
        out, counts = exchange(
            FEATURE_GATHER, axis, n, cap, ids, owner, valid, fetch,
            jax.ShapeDtypeStruct(shard.shape[1:], shard.dtype))
        return (out[None],) + tuple(c[None] for c in counts)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, None), P(), P(axis, None),
                  P(axis, None)),
        out_specs=(P(axis, None, None), P(axis), P(axis), P(axis)),
    )

    def qt_dist_lookup(shards, tables, ids, valid):
        return f(shards, tables, ids, valid)

    return jax.jit(qt_dist_lookup)


class DistFeature:
    """Sharded feature with all-to-all remote lookup.

    Build with :meth:`from_row_ranges` (contiguous row ranges, the
    sampler's: each device's rows are put from a slice of the host table
    and an owner is found by a search over the ``n + 1`` range starts) or
    :meth:`from_global_feature` (any node -> host map, replication; the
    table is laid out into shards on the host first), then index with
    ``dist_feature[ids]`` where ``ids`` is ``[n_hosts, B]`` (one query batch
    per host shard) or ``[B]`` (this host's batch, parity mode).

    The lookup program (``jit_qt_dist_lookup``) is handed the shards and
    the partition's tables as ARGUMENTS: a device array captured by a
    jitted closure is baked into the executable as a constant.

    :meth:`enable_cold_cache` attaches a per-host HBM overlay in front
    of the all-to-all: this host's recurring remote rows are served from
    a local device table instead of round-tripping the collective
    (``docs/FEATURE_CACHE.md``); the overlay state is guarded by
    ``_ov_lock`` (quiverlint QT003).
    """

    _guarded_by = {"_overlay": "_ov_lock"}

    def __init__(self, mesh: Mesh, info: Optional[PartitionInfo],
                 axis: str = "data", request_cap: Optional[int] = None):
        self.mesh = mesh
        self.info = info         # None for a partition by row ranges
        self.axis = axis
        self.n = int(mesh.shape[axis])
        assert info is None or self.n == info.hosts, (self.n, info.hosts)
        self.request_cap = request_cap
        self.shards = None       # [n, max_local, D] sharded over ``axis``
        self.row_starts_host = None  # [n+1] int64, row-range partitions
        # what the program is handed beside the shards, replicated over
        # the mesh once (an array on one device would be copied to the
        # others at every call): the range starts, or a global2host
        # partition's maps
        self.tables = None
        self._fn = {}
        self._host_source = None  # numpy global feature (overlay admission)
        self.cold_cache = None    # ColdRowCache over global-id space
        self._overlay = None      # jax.Array [C, D] per-host overlay table
        self._ov_lock = threading.Lock()
        # degrade telemetry: True when the most recent lookup fell back
        # to locally resolvable rows on a peer-shard timeout
        self.last_degraded = False
        self.last_degraded_mask = None

    @classmethod
    def from_global_feature(cls, feature: np.ndarray, mesh: Mesh,
                            info: PartitionInfo, axis: str = "data",
                            request_cap: Optional[int] = None):
        self = cls(mesh, info, axis, request_cap)
        n, d = feature.shape
        m = info.max_local
        shards = np.zeros((info.hosts, m, d), dtype=feature.dtype)
        g2l = info.global2local.copy()
        for h in range(info.hosts):
            owned = np.nonzero(
                (info.global2host == h) & ~info.replicate_mask
            )[0]
            shards[h, g2l[owned]] = feature[owned]
            base = info.owned_counts[h]
            if len(info.rep_ids):
                shards[h, base: base + len(info.rep_ids)] = (
                    feature[info.rep_ids]
                )
        # replicated nodes resolve to the local copy on every host; their
        # slot depends on the host's owned_count, so store per-host offset
        # and fold at lookup (slot = owned_count[host] + rep_rank).
        rep_rank = np.zeros(n, dtype=np.int32)
        rep_rank[info.rep_ids] = np.arange(len(info.rep_ids), dtype=np.int32)
        self._host_source = np.asarray(feature)  # overlay admission source
        sharding = NamedSharding(mesh, P(axis, None, None))
        self.shards = jax.device_put(shards, sharding)
        self.tables = replicate(mesh, {
            "g2l": g2l, "g2h": info.global2host,
            "rep_mask": info.replicate_mask, "rep_rank": rep_rank,
            "owned_counts": info.owned_counts.astype(np.int32)})
        return self

    @classmethod
    def from_row_ranges(cls, feature: np.ndarray, mesh: Mesh, row_starts,
                        axis: str = "data",
                        request_cap: Optional[int] = None, dtype=None,
                        overlay: bool = False,
                        shard_rows: Optional[int] = None):
        """Partition by contiguous row ranges: device ``p`` holds rows
        ``[row_starts[p], row_starts[p+1])`` (``row_starts``: ``[n+1]``,
        from 0 to the row count; the sampler's ranges,
        :func:`~quiver_tpu.dist.sampler.plan_row_shards`, make one
        partition of graph and table).

        Each device's rows go up from a SLICE of ``feature``: no second
        host copy of the table, and nothing ``[N]``-long on the device
        (owner = ``searchsorted(row_starts, id, "right") - 1``, local row =
        ``id - row_starts[owner]``).  ``dtype`` stores the rows narrower
        (converted block by block).  Every shard is as long as the largest
        range, rounded up to the tile; ``shard_rows`` states a larger
        length (:func:`~quiver_tpu.dist.exchange.shard_len`; the
        sampler's ``shard_rows`` keeps both tables' programs at one
        shape).  ``overlay=True`` is for a caller who goes on to
        :meth:`enable_cold_cache` or wants the degraded lookup: it keeps
        what they need, the host table and a :class:`PartitionInfo` of the
        ranges (an ``[N]`` map on the HOST)."""
        row_starts = np.asarray(row_starts, dtype=np.int64)
        n_rows, d = feature.shape
        self = cls(mesh, None, axis, request_cap)
        if (len(row_starts) != self.n + 1 or row_starts[0] != 0
                or row_starts[-1] != n_rows
                or (np.diff(row_starts) < 0).any()):
            raise ValueError(
                f"row_starts {row_starts.tolist()} are not {self.n} "
                f"contiguous ranges over {n_rows} rows")
        m = shard_len(np.diff(row_starts).max(), shard_rows)
        store = np.dtype(feature.dtype if dtype is None else dtype)

        def block(p):
            lo = int(row_starts[p])
            if lo + m <= n_rows:
                # a view: the rows past the range's end are the next
                # range's, here as padding that no local row reaches
                rows = feature[lo:lo + m]
            else:
                rows = np.zeros((m, d), feature.dtype)
                rows[:n_rows - lo] = feature[lo:]
            return rows.astype(store, copy=False)

        self.shards = put_row_blocks(mesh, axis, (m, d), block)
        self.row_starts_host = row_starts
        self.tables = replicate(
            mesh, {"row_starts": row_starts.astype(np.int32)})
        if overlay:
            self.info = PartitionInfo(
                hosts=self.n, global2host=np.repeat(
                    np.arange(self.n, dtype=np.int32), np.diff(row_starts)))
            self._host_source = np.asarray(feature)
        return self

    # -- per-host cold-row overlay (docs/FEATURE_CACHE.md) -------------
    def enable_cold_cache(self, rows: Optional[int] = None,
                          policy: Optional[str] = None,
                          admit_threshold: Optional[int] = None
                          ) -> "DistFeature":
        """Attach a per-host HBM overlay over the remote-row space.

        This host's recurring remote (non-replicated, other-owner) rows
        are admitted into a local ``[rows, D]`` device table; overlay
        hits drop out of the all-to-all entirely — their valid bit
        clears (freeing request-bucket capacity) and the rows come back
        as a device-side patch after the collective.
        """
        assert self._host_source is not None, (
            "enable_cold_cache needs from_global_feature or "
            "from_row_ranges(overlay=True) (the host-side source copy "
            "feeds admission)"
        )
        from ..config import get_config
        from ..ops.coldcache import ColdRowCache

        cfg = get_config()
        n, d = self._host_source.shape
        if rows is None:
            rows = max(1024, self.info.max_local // 4)
        rows = int(min(rows, n))
        policy = policy or cfg.cold_cache_policy
        admit = (admit_threshold if admit_threshold is not None
                 else cfg.cold_cache_admit)
        with self._ov_lock:
            self.cold_cache = ColdRowCache(rows, n, policy=policy,
                                           admit_threshold=admit)
            self._overlay = jnp.zeros(
                (rows, d), dtype=self._host_source.dtype)
        return self

    def invalidate_rows(self, global_ids) -> int:
        """Drop mutated rows (GLOBAL node ids) from this host's overlay.

        Streaming mutations call this on every host (the overlay caches
        remote rows, so the mutating host cannot know who holds a stale
        copy — ``StreamingGraph.attach_feature`` wires the local store;
        multi-host deployments broadcast the touched ids alongside the
        edge updates themselves).  Same contract as
        ``Feature.invalidate_rows``: resident slots drop, admission
        evidence resets.  Returns overlay slots dropped.
        """
        if self.cold_cache is None:
            return 0
        ids = np.atleast_1d(np.asarray(global_ids, dtype=np.int64))
        with self._ov_lock:
            cache = self.cold_cache
            dropped = (cache.invalidate_rows(ids)
                       if cache is not None else 0)
        if dropped:
            telemetry.counter("coldcache_invalidated_rows_total").inc(
                dropped)
        return dropped

    def _ov_patch_fn(self, B, bucket, me):
        """Cached per-(B, bucket) patch program: scatter overlay hits
        into this host's output row (pad pos = B, dropped)."""
        key = ("ov_patch", B, bucket)
        fn = self._fn.get(key)
        if fn is None:

            @jax.jit
            def fn(out, table, slot, pos):
                rows = jnp.take(table, slot, axis=0)
                return out.at[me, pos].set(rows, mode="drop")

            self._fn[key] = fn
        return fn

    def _ov_admit_fn(self, bucket):
        """Cached per-bucket overlay scatter-update (pad slot =
        capacity, dropped).  No donation: an earlier patch closure may
        still hold the previous table value."""
        key = ("ov_admit", bucket)
        fn = self._fn.get(key)
        if fn is None:

            @jax.jit
            def fn(table, slots, rows):
                return table.at[slots].set(rows, mode="drop")

            self._fn[key] = fn
        return fn

    def _overlay_probe(self, ids, valid):
        """Host-side overlay step for this host's query row.

        Probes the remote non-replicated ids, clears the valid bit of
        hits (they skip the all-to-all), admits recurring misses from
        the host source copy, and returns a patch closure applying the
        hits to the collective's output — or None when nothing hit.
        Mirrors ``Feature._stage_overlay``'s atomicity: probe + admit +
        table update + table-value capture all under ``_ov_lock``.
        """
        from ..feature import _pow2_bucket

        me = self.info.host
        B = ids.shape[1]
        row = ids[me]
        cand = (valid[me] & ~self.info.replicate_mask[row]
                & (self.info.global2host[row] != me))
        pos_all = np.nonzero(cand)[0].astype(np.int32)
        if not len(pos_all):
            return None
        gids = row[pos_all].astype(np.int64)
        n_evicted = 0
        with self._ov_lock:
            cache = self.cold_cache
            hit_mask, slots = cache.probe(gids)
            n_hit = int(hit_mask.sum())
            table = self._overlay  # value consistent with the probe
            miss_ids = gids[~hit_mask]
            if len(miss_ids):
                adm, n_evicted = cache.admit(miss_ids)
                amask = adm >= 0
                if amask.any():
                    ba = _pow2_bucket(int(amask.sum()))
                    adm_slot = np.full(ba, cache.capacity, dtype=np.int32)
                    adm_slot[: int(amask.sum())] = adm[amask]
                    rows = np.zeros((ba, self._host_source.shape[1]),
                                    dtype=self._host_source.dtype)
                    rows[: int(amask.sum())] = (
                        self._host_source[miss_ids[amask]]
                    )
                    self._overlay = self._ov_admit_fn(ba)(
                        self._overlay, jnp.asarray(adm_slot),
                        jnp.asarray(rows))
            row_bytes = (self._host_source.shape[1]
                         * self._host_source.dtype.itemsize)
            resident_bytes = cache.resident_bytes(row_bytes)
        telemetry.gauge("dist_feature_overlay_resident_bytes").set(
            float(resident_bytes))
        telemetry.counter("dist_feature_coldcache_rows_total",
                          result="hit").inc(float(n_hit))
        telemetry.counter("dist_feature_coldcache_rows_total",
                          result="miss").inc(float(len(gids) - n_hit))
        if n_evicted:
            telemetry.counter(
                "dist_feature_coldcache_evictions_total").inc(
                float(n_evicted))
        from ..telemetry import flightrec

        if flightrec.tracing():
            flightrec.event("dist.exchange", {
                "probe_hit": int(n_hit),
                "probe_miss": int(len(gids) - n_hit),
                "evicted": int(n_evicted)})
        if n_hit == 0:
            return None
        hit_pos = pos_all[hit_mask]
        valid[me, hit_pos] = False  # hits skip the all-to-all
        bh = _pow2_bucket(n_hit)
        # bucket-edge discipline (see Feature._stage): the bucket covers
        # every real hit; padded lanes carry the out-of-range sentinel B
        assert n_hit <= bh, (n_hit, bh)
        ov_slot = np.zeros(bh, dtype=np.int32)
        ov_slot[:n_hit] = slots[hit_mask]
        ov_pos = np.full(bh, B, dtype=np.int32)
        ov_pos[:n_hit] = hit_pos
        fn = self._ov_patch_fn(B, bh, me)
        slot_d, pos_d = jnp.asarray(ov_slot), jnp.asarray(ov_pos)
        return lambda out: fn(out, table, slot_d, pos_d)

    def lookup(self, ids, valid=None):
        """``ids``: [n_hosts, B] int32 (one batch per host).  Returns
        [n_hosts, B, D] with each host's features resolved.

        After each call ``self.last_overflow`` holds a ``[n_hosts]`` device
        array counting queries that overflowed their destination bucket and
        got ZERO feature rows.  Always zero when ``request_cap`` is None
        (the exact exchange: buckets sized for an owner's share of the
        batch, in as many rounds as the counts ask for,
        ``self.last_rounds``); check :meth:`overflow_stats` when running
        with a cap of your own, which is ONE round of buckets that long —
        training on silently zeroed features is the failure mode this
        guards against.

        Telemetry: each call folds into the ``feature.lookup`` span, with
        two parts inside it: ``feature.lookup.place`` (the deadline check,
        the overlay's probe, ids and mask put onto the mesh: everything
        before the program is called) and ``feature.lookup.launch`` (the
        call of ``jit_qt_dist_lookup`` until it returns to Python).  All
        three time how long the CALLER's thread is held, not the device:
        the rows are still being fetched when the call has returned."""
        with telemetry.span(HOST_LOOKUP):
            return self._lookup_impl(ids, valid)

    def _lookup_impl(self, ids, valid):
        with telemetry.span(HOST_LOOKUP + PLACE):
            check_ambient("dist_feature")
            ov_patch = None
            if self.cold_cache is not None and not isinstance(ids,
                                                              jax.Array):
                # host-side overlay probe needs host ids; device ids would
                # force a sync here, so they bypass the overlay entirely
                ids = np.asarray(ids, dtype=np.int32)
                valid = (np.ones(ids.shape, dtype=bool) if valid is None
                         else np.array(valid, dtype=bool))  # copy: bits clear
                ov_patch = self._overlay_probe(ids, valid)
            ids = jnp.asarray(ids, jnp.int32)
            nh, B = ids.shape
            if valid is None:
                valid = jnp.ones((nh, B), bool)
            cap = self.request_cap or None      # None: exact, in rounds
            key = (B, cap)
            sharding = NamedSharding(self.mesh, P(self.axis, None))
            ids = jax.device_put(ids, sharding)
            valid = jax.device_put(valid, sharding)
            args = (self.shards, self.tables, ids, valid)
            if key not in self._fn:
                self._fn[key] = lookup_program(
                    self.mesh, self.axis, cap,
                    self.row_starts_host is not None)
                register_program(self._fn[key], args)
        try:
            with telemetry.span(HOST_LOOKUP + LAUNCH):
                _CHAOS_EXCHANGE()
                out, overflow, live, rounds = self._fn[key](*args)
        except (PeerTimeout, TimeoutError):
            # peer shard timed out: degrade to the rows resolvable
            # WITHOUT the collective (owned / replicated / overlay-hit),
            # zeros elsewhere, flagged via last_degraded — stale-local
            # beats stalling the whole serving pipeline on one peer
            return self._degraded_lookup(np.asarray(ids),
                                         np.asarray(valid))
        self.last_degraded = False
        self.last_overflow = overflow
        self._overflow_recorded = False
        self.last_rounds = rounds
        self._last_exchange = (self.n * bucket_len(B, self.n, cap), rounds,
                               live)
        self._exchange_recorded = False
        if ov_patch is not None:
            out = ov_patch(out)
        from ..telemetry import flightrec

        if flightrec.tracing():
            flightrec.event("dist.lookup", {
                "hosts": int(nh), "batch": int(B),
                "overlay_patched": ov_patch is not None})
        return out

    def _degraded_lookup(self, ids: np.ndarray, valid: np.ndarray):
        """Peer-timeout fallback: each host row keeps the rows its own
        shard can answer (owned by it, replicated everywhere, or — for
        this host — sitting in the cold-row overlay); everything else
        comes back zero.  ``last_degraded`` flags the result and
        ``last_degraded_mask`` says which rows are real."""
        # the request's deadline likely burned while the peer timed out:
        # shed HERE, before the local-rows gather, not after — the
        # serving loop installed the batch deadline as ambient scope
        check_ambient("dist_feature")
        from ..telemetry import flightrec

        info = self.info
        src = self._host_source
        assert src is not None, (
            "degraded lookup needs from_global_feature or "
            "from_row_ranges(overlay=True) (the host-side source copy is "
            "the hot tier it serves from)")
        nh, B = ids.shape
        owner = info.global2host[ids]
        local = valid & (info.replicate_mask[ids]
                         | (owner == np.arange(nh)[:, None]))
        if self.cold_cache is not None:
            me = info.host
            pos = np.nonzero(valid[me] & ~local[me])[0]
            if len(pos):
                with self._ov_lock:
                    hit, _ = self.cold_cache.probe(
                        ids[me, pos].astype(np.int64))
                local[me, pos[hit]] = True
        out = np.zeros((nh, B, src.shape[1]), dtype=src.dtype)
        out[local] = src[ids[local]]
        self.last_degraded = True
        self.last_degraded_mask = local
        self.last_overflow = np.zeros((nh,), np.int32)
        self.last_rounds = np.zeros((nh,), np.int32)    # no exchange ran
        self._overflow_recorded = True
        telemetry.counter("dist_feature_degraded_total").inc()
        if flightrec.tracing():
            flightrec.event("dist.lookup", {
                "degraded": True, "hosts": int(nh), "batch": int(B),
                "served": int(local.sum()),
                "dropped": int((valid & ~local).sum())})
        return out

    def overflow_stats(self):
        """Per-host dropped-query counts from the most recent lookup as a
        host int array (None before any call).  Materializing here also
        feeds ``dist_feature_overflow_total`` — at query time, never in
        the lookup hot path (that would force a device sync)."""
        if getattr(self, "last_overflow", None) is None:
            return None
        arr = np.asarray(self.last_overflow)
        if not getattr(self, "_overflow_recorded", True):
            self._overflow_recorded = True
            total = float(arr.sum())
            if total:
                telemetry.counter("dist_feature_overflow_total").inc(total)
        return arr

    def exchange_stats(self):
        """``(slots, live_slots)`` of the most recent lookup's request
        exchange, summed over the ranks: slots shipped to the owners (rounds
        x ranks x bucket; each comes back carrying a row) and those that
        held a request; None before any call.  Read at query time like
        :meth:`overflow_stats`, and feeds ``dist_exchange_slots_total`` /
        ``dist_exchange_live_slots_total`` /
        ``dist_exchange_rounds_total{layer="feature"}`` once a call."""
        return record_exchange(self, "feature")

    def __getitem__(self, ids):
        ids = np.asarray(ids)
        if ids.ndim == 1:  # parity mode: same batch replicated per host
            if not getattr(self, "_warned_1d", False):
                import warnings

                warnings.warn(
                    "DistFeature[1-D ids] broadcasts the batch to every "
                    "host shard (n_hosts x bandwidth) — a parity shim for "
                    "the reference's per-rank __getitem__.  Pass "
                    "[n_hosts, B] ids to lookup() for the efficient path.",
                    stacklevel=2,
                )
                self._warned_1d = True
            out = self.lookup(np.tile(ids[None], (self.n, 1)))
            return out[self.info.host]
        return self.lookup(ids)
