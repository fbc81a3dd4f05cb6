"""Distributed neighbor sampling over a row-sharded graph.

The reference handles graphs bigger than device memory with UVA: the CSR
stays in pinned host memory and CUDA kernels read it over PCIe
(``quiver.cu.hpp:16-26``, mode ``ZERO_COPY``).  The TPU equivalent is to
**shard the edge array over the mesh** and let ICI play the role of PCIe —
each device owns a contiguous row range (so ``indptr`` stays local and
dense), seeds are routed to their owner through the exchange
:class:`quiver_tpu.dist.DistFeature` shares (``dist/exchange.py``: buckets
sized for an owner's share, as many rounds of two all-to-alls as the
counts ask for), sampled neighbor blocks ride back on the second.

The blocks are built positionally, as ``dedup="none"`` builds them on one
chip, and say so (``LayerBlock.layout = POSITIONAL``, with a leading shard
axis on every leaf): a model handed a rank's blocks reads a target's
sources as a slice of its frontier rows (``models.layers.sources``), no
gather forward and no scatter-add backward.

papers100M at int32 is ~6.5 GB of indices — over a v5e-8 that is <1 GB per
chip, leaving HBM for features.  Single-chip sampling of a sharded graph is
the degenerate n=1 case (no collectives emitted).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..resilience import chaos
from ..resilience.errors import PeerTimeout
from ..resilience.retry import Backoff, retry_call
from ..utils.topology import CSRTopo
from ..ops.sample import sample_neighbors
from ..parallel.train import replicate
from ..sampler import POSITIONAL, LayerBlock, SampledBatch
from .. import telemetry
from ..telemetry.device_scopes import (exchange as exchange_scope,
                                       register_program, sampler_hop,
                                       HOST_SAMPLE, LAUNCH, PLACE, SAMPLER)
from .exchange import (bucket_len, exchange, put_row_blocks,
                       record_exchange, shard_len)

__all__ = ["DistGraphSampler", "shard_csr_by_rows", "plan_row_shards",
           "sample_program"]

# fault-injection site for the per-hop all-to-all exchange (no-op
# unless a chaos plan is installed)
_CHAOS_EXCHANGE = chaos.point("dist.sampler.exchange")


def plan_row_shards(indptr, n_shards: int,
                    max_local_edges: int = 2**31 - 1):
    """Plan contiguous, edge-balanced row ranges from ``indptr`` alone.

    Returns ``row_starts`` ([n_shards+1] int64).  Raises if any shard's
    local edge count would overflow the int32 positions the on-device
    rebased indptr uses (same guard class as ``uva.py``'s hot tier) —
    this is the check the papers100M regime (>2^31 total edges,
    reference benchmarks/ogbn-papers100M/train_quiver_multi_node.py)
    rests on.  Needs no materialized edge array, so it is testable at
    any scale.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    indptr = np.asarray(indptr)
    n = len(indptr) - 1
    total = int(indptr[-1])
    target = total / n_shards
    row_starts = [0]
    for s in range(1, n_shards):
        row_starts.append(int(np.searchsorted(indptr, target * s)))
    row_starts.append(n)
    row_starts = np.asarray(row_starts, dtype=np.int64)
    local_edges = indptr[row_starts[1:]] - indptr[row_starts[:-1]]
    worst = int(local_edges.max())
    if worst > max_local_edges:
        need = -(-total // max_local_edges)
        raise ValueError(
            f"a row shard holds {worst:,} edges > int32 limit "
            f"{max_local_edges:,}; use at least ~{need} shards "
            f"(got {n_shards}) or a smaller graph partition"
        )
    if n > max_local_edges:
        raise ValueError(
            f"{n:,} nodes overflow the int32 row_starts/frontier ids"
        )
    return row_starts


def shard_csr_by_rows(topo: CSRTopo, n_shards: int):
    """Split a CSR into ``n_shards`` contiguous row ranges, balanced by
    edge count.  Returns (row_starts [n+1], local indptr list, local
    indices list) — local indptr is rebased to each shard's edge offset.

    The plain statement of what a shard holds, as host copies: the tests
    hold :class:`DistGraphSampler`'s device shards to it.  The sampler
    itself puts each shard from a slice (:func:`plan_row_shards` +
    ``exchange.put_row_blocks``) and makes no such copies."""
    indptr = topo.indptr
    row_starts = plan_row_shards(indptr, n_shards)
    local_indptr, local_indices = [], []
    for s in range(n_shards):
        lo, hi = row_starts[s], row_starts[s + 1]
        ip = indptr[lo: hi + 1] - indptr[lo]
        local_indptr.append(ip.astype(np.int64))
        local_indices.append(
            topo.indices[indptr[lo]: indptr[hi]].astype(np.int32)
        )
    return row_starts, local_indptr, local_indices


def _cap(F: int, frac: float, n: int):
    """A caller's request bucket for a frontier of ``F``: None at ``frac``
    1.0, the exact exchange, which sizes its own and drops nothing."""
    if frac >= 1.0:
        return None
    return min(max(int(np.ceil(F * frac / n)) * 2, 8), F)


def _hop(axis: str, n: int, gm: str, srng: str, hop: int, k: int, cap):
    layer = sampler_hop(hop)

    def body(ip, ix, row_starts, ids, valid, key):
        # ip: [max_ip]; ix: [max_id]; ids/valid: [F]
        me = jax.lax.axis_index(axis)
        with exchange_scope(layer):
            owner = (
                jnp.searchsorted(row_starts, ids, side="right") - 1
            ).astype(jnp.int32)

        def draw(rids, rvalid, r):
            with jax.named_scope(layer):
                # rebase to local rows and sample from the local shard;
                # a round draws from a counter stream of its own
                local = jnp.clip(rids - row_starts[me], 0, ip.shape[0] - 2)
                sub = jax.random.fold_in(jax.random.fold_in(key, me), r)
                out = sample_neighbors(ip, ix, local, k, sub,
                                       seed_mask=rvalid,
                                       gather_mode=gm, sample_rng=srng)
            with exchange_scope(layer):
                # ship [n * bucket, k] neighbor ids (+1, 0=invalid) back
                return jnp.where(out.mask, out.nbrs + 1, 0)

        got, counts = exchange(layer, axis, n, cap, ids, owner, valid, draw,
                               jax.ShapeDtypeStruct((k,), jnp.int32))
        with exchange_scope(layer):
            nbrs = got - 1      # no request sent: 0, so -1
            mask = nbrs >= 0
        return nbrs, mask, counts

    return body


def sample_program(mesh: Mesh, axis: str, sizes, request_cap_frac: float,
                   gather_mode: str, sample_rng: str):
    """The jitted k-hop program over a row-sharded CSR,
    ``jit_qt_dist_sample``: ``(indptr_sh, indices_sh, row_starts, seeds
    [n, B], valid [n, B], key) -> (n_id, n_mask, num, blocks, dropped [n,
    L], live [n, L], rounds [n, L])``.  It holds no table: all three arrive
    as arguments.

    Every block is ``layout=POSITIONAL`` (:class:`~quiver_tpu.sampler.
    LayerBlock`): hop ``l`` grows a rank's frontier of ``F`` slots to
    ``F (1 + k)`` by appending target ``b``'s ``k`` draws at ``F + b*k ..
    F + b*k + k - 1``, and ``nbr_local`` is that position wherever ``mask``.
    The marker is static structure (no leaf): it crosses this ``jit``, a
    caller's ``tree_map(lambda l: l[0], blocks)`` and
    ``make_train_step(mesh=)``'s ``vmap`` as the Python value it is.  A
    caller that trims, reorders or deduplicates a frontier before the model
    hands it ``block._replace(layout=None)``; ``sources`` otherwise raises
    on the length at trace time."""
    from ..utils.rng import default_impl

    sizes = tuple(sizes)
    n = int(mesh.shape[axis])
    prng_impl = default_impl()  # honors QUIVER_TPU_PRNG override

    def pipeline(ip, ix, row_starts, seeds, valid, seed_scalar):
        # seeds/valid: [1, B] per-shard (every shard runs the same
        # program on ITS OWN seed batch — data-parallel sampling)
        with jax.named_scope(SAMPLER):
            key = jax.random.key(seed_scalar, impl=prng_impl)
        ip, ix = ip[0], ix[0]
        frontier, fmask = seeds[0], valid[0]
        blocks = []
        counted = []
        for l, k in enumerate(sizes):
            F = frontier.shape[0]
            cap = _cap(F, request_cap_frac, n)
            with jax.named_scope(SAMPLER):
                key, sub = jax.random.split(key)
            hop = _hop(axis, n, gather_mode, sample_rng, l + 1, k, cap)
            nbrs, mask, counts = hop(ip, ix, row_starts, frontier, fmask, sub)
            counted.append(counts)
            with jax.named_scope(sampler_hop(l + 1)):
                # POSITIONAL holds through the exchange: it unpacks the
                # owners' answers into [F, k] in REQUEST order whatever the
                # number of rounds, so target b's draws are row b; a request
                # a caller's cap dropped, a dead frontier slot and a target
                # of degree 0 come back mask == False, which the promise
                # exempts; and the frontier below is the old one with
                # nbrs.reshape(-1) appended, F (1 + k) long
                pos = (F + jnp.arange(F, dtype=jnp.int32)[:, None] * k
                       + jnp.arange(k, dtype=jnp.int32)[None, :])
                blocks.append(LayerBlock(
                    nbr_local=jnp.where(mask, pos, 0),
                    mask=mask,
                    num_targets=fmask.sum().astype(jnp.int32),
                    layout=POSITIONAL,
                ))
                frontier = jnp.concatenate(
                    [frontier, jnp.where(mask, nbrs, 0).reshape(-1)]
                )
                fmask = jnp.concatenate([fmask, mask.reshape(-1)])
        # leading [1] axis on every leaf so out_specs can globalize
        # the per-shard results onto the mesh axis
        blocks_out = tuple(
            jax.tree_util.tree_map(lambda a: a[None], b)  # keeps ``layout``
            for b in blocks[::-1]  # outermost-first, like SampledBatch
        )
        return (frontier[None], fmask[None],
                fmask.sum().astype(jnp.int32)[None], blocks_out,
                *(jnp.stack(c)[None] for c in zip(*counted)))

    blocks_spec = tuple(
        LayerBlock(
            nbr_local=P(axis, None, None),
            mask=P(axis, None, None),
            num_targets=P(axis),
            layout=POSITIONAL,
        )
        for _ in sizes
    )
    f = shard_map(
        pipeline, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(),
                  P(axis, None), P(axis, None), P()),
        out_specs=(P(axis, None), P(axis, None),
                   P(axis), blocks_spec, P(axis, None),
                   P(axis, None), P(axis, None)),
    )

    def qt_dist_sample(indptr, indices, row_starts, seeds, valid, key):
        return f(indptr, indices, row_starts, seeds, valid, key)

    return jax.jit(qt_dist_sample)


class DistGraphSampler:
    """Multi-hop sampler over a row-sharded CSR on a device mesh.

    Args:
      topo: full host-side :class:`CSRTopo` (single-controller build).
      mesh: mesh whose ``axis`` dimension the edges shard over.
      sizes: fanouts (outward order).
      request_cap_frac: 1.0 (the default) is the exact exchange: buckets
        sized for an owner's share of the frontier, shipped in as many
        rounds as the fullest bucket asks for, nothing dropped whatever
        the skew (``dist/exchange.py``).  Smaller is ONE round of buckets
        of that fraction of the frontier (twice an even share of it):
        overflowed seeds sample 0 neighbors and ``overflow_stats()``
        counts them.
      shard_rows, shard_edges: length of every device's ``indptr`` /
        ``indices`` shard.  Default: the largest range's, rounded up to
        the tile (:func:`~quiver_tpu.dist.exchange.shard_len`).  A larger
        length of the caller's own keeps graphs of nearly one size (a
        snapshot a day, a graph that grows) at ONE shape, so that the
        programs are compiled once; what was used is ``.shard_rows`` /
        ``.shard_edges`` (hand the first to
        :meth:`DistFeature.from_row_ranges` for the same of the table).

    The per-hop exchange (``dist.exchange.exchange``), per round:
      1. owner = searchsorted(row_starts, frontier ids)
      2. all_to_all the round's bucketed ids to owners
      3. owner shard samples locally (dense ``[n * bucket, k]`` + mask)
      4. all_to_all blocks back, unpacked to frontier order
    """

    def __init__(self, topo: CSRTopo, mesh: Mesh, sizes,
                 axis: str = "data", request_cap_frac: float = 1.0,
                 seed: int = 0, gather_mode: str = "auto",
                 sample_rng: str = "auto", shard_rows=None,
                 shard_edges=None):
        from ..config import resolve_gather_mode, resolve_sample_rng

        self.topo = topo
        self.mesh = mesh
        self.axis = axis
        self.gather_mode = resolve_gather_mode(gather_mode)
        self.sample_rng = resolve_sample_rng(sample_rng)
        self.sizes = list(sizes)
        self.n = int(mesh.shape[axis])
        self.request_cap_frac = request_cap_frac
        indptr = np.asarray(topo.indptr)
        indices = np.asarray(topo.indices)
        row_starts = plan_row_shards(indptr, self.n)
        self.row_starts_host = row_starts
        # a [n+1] table, replicated: the program's argument, not its constant
        self.row_starts = replicate(mesh, row_starts.astype(np.int32))
        # local shards of one length (the largest range's, rounded up to
        # the tile, or the caller's own), each put on its device from a
        # slice of the host CSR.  A multiple of the tile is a multiple of
        # 128: the element gather's 128-lane reshape covers the whole table,
        # its tail truncation never drops real rows
        rows = np.diff(row_starts)
        edges = indptr[row_starts[1:]] - indptr[row_starts[:-1]]
        max_ip = shard_len(int(rows.max()) + 1, shard_rows)
        max_id = shard_len(int(edges.max()), shard_edges)
        self.shard_rows, self.shard_edges = max_ip, max_id

        def local_indptr(p):
            # rebased to the shard's edge offset; pads repeat the final
            # offset (padded "rows" read degree 0, never negative — mirrors
            # uva.py's hot-tier padding)
            lo, hi = row_starts[p], row_starts[p + 1]
            ip = (indptr[lo: hi + 1] - indptr[lo]).astype(np.int32)
            return np.pad(ip, (0, max_ip - len(ip)), mode="edge")

        def local_indices(p):
            # pads are never dereferenced (counts=min(deg,k) masks them):
            # where the host array goes on past the shard, the pad is a
            # view of what follows; only at its end is it a zero-filled copy
            lo = int(indptr[row_starts[p]])
            if lo + max_id <= len(indices):
                return indices[lo: lo + max_id].astype(np.int32, copy=False)
            ix = np.zeros(max_id, np.int32)
            ix[:len(indices) - lo] = indices[lo:]
            return ix

        self.indptr_sh = put_row_blocks(mesh, axis, (max_ip,), local_indptr)
        self.indices_sh = put_row_blocks(mesh, axis, (max_id,),
                                         local_indices)
        self._fn = {}
        # retry pacing for the exchange path: short, jittered (so shards
        # that timed out together don't re-collide), seeded off the
        # sampler seed so runs replay byte-identically
        import random as _random

        self._retry_backoff = Backoff(0.005, cap_s=0.02, jitter=0.5,
                                      rng=_random.Random(seed))

    def hop_caps(self, B: int):
        """The request bucket's slots at each hop for a batch of ``B``
        seeds a rank: at ``request_cap_frac`` 1.0 the exact exchange's
        (:func:`~quiver_tpu.dist.exchange.bucket_len`: an owner's share of
        the frontier, shipped in as many rounds as the counts ask for),
        else the caller's one-round cap."""
        return [bucket_len(F, self.n, _cap(F, self.request_cap_frac, self.n))
                for F in self.hop_frontiers(B)]

    def hop_frontiers(self, B: int):
        """A rank's frontier slots at each hop for a batch of ``B`` seeds:
        ``B``, ``B (1 + k_1)``, ...; each is asked of the owners and
        unpacked once a round."""
        out, F = [], B
        for k in self.sizes:
            out.append(F)
            F *= 1 + k
        return out

    def sample(self, seed_batches: np.ndarray, key=None):
        """``seed_batches``: [n_shards, B] — one seed batch per device;
        ``key``: int seed (PRNG keys are derived per shard inside).
        Returns per-shard :class:`SampledBatch`-style pytrees stacked on
        the leading axis; the blocks are ``layout=POSITIONAL`` per rank
        (:func:`sample_program`).

        After each call ``self.last_overflow`` holds a ``[n_shards, L]``
        device array of per-hop counts of frontier entries that overflowed
        their destination bucket and were silently dropped (sampled 0
        neighbors).  Always zero at ``request_cap_frac=1.0``.
        ``self.last_live`` holds, in the same shape, the requests each rank
        sent at each hop (its live frontier slots, whoever owns them), and
        ``self.last_rounds`` the rounds each hop's exchange was shipped in
        (every rank's the same; 1 under a caller's cap).

        Telemetry: each call folds into the ``sampler.sample`` span (the
        name ``GraphSageSampler.sample`` uses: the same boundary), with two
        parts inside it: ``sampler.sample.place`` (the seeds, their mask
        and the key put onto the mesh: everything before the program is
        called) and ``sampler.sample.launch`` (the call of
        ``jit_qt_dist_sample``, retries included, until it returns to
        Python).  All three time how long the CALLER's thread is held, not
        the device: the program runs on after the call has returned.
        """
        with telemetry.span(HOST_SAMPLE):
            return self._sample_impl(seed_batches, key)

    def _sample_impl(self, seed_batches, key):
        with telemetry.span(HOST_SAMPLE + PLACE):
            seeds = jnp.asarray(seed_batches, jnp.int32)
            nd, B = seeds.shape
            assert nd == self.n, (nd, self.n)
            valid = jnp.ones((nd, B), bool)
            if key is None:
                key = np.random.randint(0, 2**31 - 1)
            sh = NamedSharding(self.mesh, P(self.axis, None))
            seeds = jax.device_put(seeds, sh)
            valid = jax.device_put(valid, sh)
            args = (self.indptr_sh, self.indices_sh, self.row_starts, seeds,
                    valid, jnp.int32(key))
            if B not in self._fn:
                self._fn[B] = sample_program(
                    self.mesh, self.axis, self.sizes, self.request_cap_frac,
                    self.gather_mode, self.sample_rng)
                register_program(self._fn[B], args)

        def _exchange():
            _CHAOS_EXCHANGE()
            return self._fn[B](*args)

        def _on_retry(attempt, exc):
            telemetry.counter("dist_sampler_retries_total").inc()

        # one retried attempt with a short jittered backoff — a
        # transient peer stall usually clears; a second timeout surfaces
        # to the caller (sampling has no partial-answer degrade: a
        # frontier with holes would silently bias the training batch)
        with telemetry.span(HOST_SAMPLE + LAUNCH):
            n_id, n_mask, num, blocks, overflow, live, rounds = retry_call(
                _exchange, attempts=2, backoff=self._retry_backoff,
                retry_on=(PeerTimeout, TimeoutError), on_retry=_on_retry)
        self.last_overflow = overflow
        self._overflow_recorded = False
        self.last_live = live       # [n_shards, L], beside last_overflow
        self.last_rounds = rounds
        self._last_exchange = (self.n * np.asarray(self.hop_caps(B)), rounds,
                               live, self.hop_frontiers(B))
        self._exchange_recorded = False
        return n_id, n_mask, num, blocks

    def exchange_stats(self):
        """``(slots, live_slots)`` of the most recent ``sample``'s request
        exchanges, summed over hops and ranks: slots shipped to the owners
        (rounds x ranks x bucket; each comes back carrying ``k`` draws) and
        those that held a request; None before any call.  Feeds
        ``dist_exchange_slots_total`` / ``dist_exchange_live_slots_total``
        / ``dist_exchange_unpacked_slots_total`` /
        ``dist_exchange_rounds_total{layer="sampler"}`` once a call, at
        query time like :meth:`overflow_stats`."""
        return record_exchange(self, "sampler")

    def overflow_stats(self):
        """Per-hop dropped-request counts from the most recent ``sample``
        call, as a host ``[n_shards, L]`` int array (None before any call).
        Parity note: the reference has no analogue — NCCL send/recv moves
        exact ragged sizes; fixed-capacity buckets are the TPU trade, so
        the drop counter is the safety net.  Materializing here also
        feeds ``dist_sampler_overflow_total`` — at query time, never in
        the sample hot path (that would force a device sync)."""
        if getattr(self, "last_overflow", None) is None:
            return None
        arr = np.asarray(self.last_overflow)
        if not getattr(self, "_overflow_recorded", True):
            self._overflow_recorded = True
            total = float(arr.sum())
            if total:
                telemetry.counter("dist_sampler_overflow_total").inc(total)
        return arr
