"""Distributed neighbor sampling over a row-sharded graph.

The reference handles graphs bigger than device memory with UVA: the CSR
stays in pinned host memory and CUDA kernels read it over PCIe
(``quiver.cu.hpp:16-26``, mode ``ZERO_COPY``).  The TPU equivalent is to
**shard the edge array over the mesh** and let ICI play the role of PCIe —
each device owns a contiguous row range (so ``indptr`` stays local and
dense), seeds are routed to their owner with the same fixed-capacity
all-to-all bucketing as :class:`quiver_tpu.dist.DistFeature`, sampled
neighbor blocks ride back on a second all-to-all.

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
from ..sampler import LayerBlock, SampledBatch

__all__ = ["DistGraphSampler", "shard_csr_by_rows", "plan_row_shards"]

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
    indices list) — local indptr is rebased to each shard's edge offset."""
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


class DistGraphSampler:
    """Multi-hop sampler over a row-sharded CSR on a device mesh.

    Args:
      topo: full host-side :class:`CSRTopo` (single-controller build).
      mesh: mesh whose ``axis`` dimension the edges shard over.
      sizes: fanouts (outward order).
      request_cap: per-destination bucket capacity as a fraction of the
        frontier (1.0 = worst case, always exact; smaller trades overflow
        drops for bandwidth — overflowed seeds just sample 0 neighbors).

    The per-hop exchange:
      1. owner = searchsorted(row_starts, frontier ids)
      2. all_to_all the bucketed ids to owners
      3. owner shard samples locally (dense ``[cap, k]`` + mask)
      4. all_to_all blocks back, unpacked to frontier order
    """

    def __init__(self, topo: CSRTopo, mesh: Mesh, sizes,
                 axis: str = "data", request_cap_frac: float = 1.0,
                 seed: int = 0, gather_mode: str = "auto",
                 sample_rng: str = "auto"):
        from ..config import resolve_gather_mode, resolve_sample_rng

        self.topo = topo
        self.mesh = mesh
        self.axis = axis
        self.gather_mode = resolve_gather_mode(gather_mode)
        self.sample_rng = resolve_sample_rng(sample_rng)
        self.sizes = list(sizes)
        self.n = int(mesh.shape[axis])
        self.request_cap_frac = request_cap_frac
        row_starts, lips, lids = shard_csr_by_rows(topo, self.n)
        self.row_starts = jnp.asarray(row_starts, jnp.int32)
        # pad local shards to a common size, stack, shard over the mesh
        # (round up to 128 so the element gather's 128-lane reshape covers
        # the whole table — its tail truncation must never drop real rows)
        r128 = lambda v: -(-v // 128) * 128
        max_ip = r128(max(len(x) for x in lips))
        max_id = r128(max(len(x) for x in lids))
        # indptr pads repeat the final offset (padded "rows" read degree 0,
        # never negative — mirrors uva.py's hot-tier padding); indices pads
        # are plain zeros (never dereferenced: counts=min(deg,k) masks them)
        pad_edge = lambda a, m: np.pad(a, (0, m - len(a)), mode="edge")
        pad_zero = lambda a, m: np.pad(a, (0, m - len(a)))
        ip = np.stack([pad_edge(x, max_ip) for x in lips]).astype(np.int32)
        ix = np.stack([pad_zero(x, max_id) for x in lids]).astype(np.int32)
        sh2 = NamedSharding(mesh, P(axis, None))
        self.indptr_sh = jax.device_put(ip, sh2)
        self.indices_sh = jax.device_put(ix, sh2)
        self._fn = {}
        # retry pacing for the exchange path: short, jittered (so shards
        # that timed out together don't re-collide), seeded off the
        # sampler seed so runs replay byte-identically
        import random as _random

        self._retry_backoff = Backoff(0.005, cap_s=0.02, jitter=0.5,
                                      rng=_random.Random(seed))

    # ------------------------------------------------------------------
    def _hop(self, k: int, cap: int):
        n, axis = self.n, self.axis
        gm, srng = self.gather_mode, self.sample_rng
        row_starts = self.row_starts

        def body(ip, ix, ids, valid, key):
            # ip: [1, max_ip]; ix: [1, max_id]; ids/valid: [1, F]
            ip, ix, ids, valid = ip[0], ix[0], ids[0], valid[0]
            me = jax.lax.axis_index(axis)
            F = ids.shape[0]
            owner = (
                jnp.searchsorted(row_starts, ids, side="right") - 1
            ).astype(jnp.int32)
            owner = jnp.where(valid, owner, n)
            onehot = owner[:, None] == jnp.arange(n)[None, :]
            rank_in = jnp.cumsum(onehot, axis=0) - 1
            slot = jnp.sum(jnp.where(onehot, rank_in, 0), axis=1)
            overflow = slot >= cap
            ok = valid & ~overflow
            ocount = (valid & overflow).sum().astype(jnp.int32)
            dest = jnp.where(ok, owner * cap + slot, n * cap)
            reqs = jnp.zeros((n * cap,), jnp.int32).at[dest].add(
                ids + 1, mode="drop"
            ).reshape(n, cap)
            recv = jax.lax.all_to_all(reqs, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            rids = recv.reshape(-1) - 1
            rvalid = rids >= 0
            # rebase to local rows and sample from the local shard
            local = jnp.clip(rids - row_starts[me], 0, ip.shape[0] - 2)
            sub = jax.random.fold_in(key, me)
            out = sample_neighbors(ip, ix, local, k, sub,
                                   seed_mask=rvalid,
                                   gather_mode=gm, sample_rng=srng)
            # ship [n, cap, k] neighbor ids (+1, 0=invalid) back
            payload = jnp.where(out.mask, out.nbrs + 1, 0).reshape(
                n, cap, k
            )
            back = jax.lax.all_to_all(payload, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            flat = back.reshape(n * cap, k)
            got = jnp.take(flat, jnp.clip(dest, 0, n * cap - 1), axis=0)
            nbrs = jnp.where(ok[:, None], got - 1, -1)
            mask = nbrs >= 0
            return nbrs[None], mask[None], ocount

        return body

    def _build(self, B: int):
        from ..utils.rng import default_impl

        sizes = tuple(self.sizes)
        n, axis = self.n, self.axis
        frac = self.request_cap_frac
        prng_impl = default_impl()  # honors QUIVER_TPU_PRNG override

        def pipeline(ip, ix, seeds, valid, seed_scalar):
            # seeds/valid: [1, B] per-shard (every shard runs the same
            # program on ITS OWN seed batch — data-parallel sampling)
            key = jax.random.key(seed_scalar, impl=prng_impl)
            frontier, fmask = seeds[0], valid[0]
            blocks = []
            ocounts = []
            for l, k in enumerate(sizes):
                F = frontier.shape[0]
                if frac >= 1.0:
                    # truly exact: even if every frontier entry lands on one
                    # shard, slot < F, so overflow is impossible
                    cap = F
                else:
                    cap = min(max(int(np.ceil(F * frac / n)) * 2, 8), F)
                key, sub = jax.random.split(key)
                nbrs, mask, oc = self._hop(k, cap)(
                    ip, ix, frontier[None], fmask[None], sub
                )
                ocounts.append(oc)
                nbrs, mask = nbrs[0], mask[0]
                pos = (F + jnp.arange(F, dtype=jnp.int32)[:, None] * k
                       + jnp.arange(k, dtype=jnp.int32)[None, :])
                blocks.append(LayerBlock(
                    nbr_local=jnp.where(mask, pos, 0),
                    mask=mask,
                    num_targets=fmask.sum().astype(jnp.int32),
                ))
                frontier = jnp.concatenate(
                    [frontier, jnp.where(mask, nbrs, 0).reshape(-1)]
                )
                fmask = jnp.concatenate([fmask, mask.reshape(-1)])
            # leading [1] axis on every leaf so out_specs can globalize
            # the per-shard results onto the mesh axis
            blocks_out = tuple(
                LayerBlock(
                    nbr_local=b.nbr_local[None],
                    mask=b.mask[None],
                    num_targets=b.num_targets[None],
                )
                for b in blocks[::-1]  # outermost-first, like SampledBatch
            )
            return (frontier[None], fmask[None],
                    fmask.sum().astype(jnp.int32)[None], blocks_out,
                    jnp.stack(ocounts)[None])

        blocks_spec = tuple(
            LayerBlock(
                nbr_local=P(self.axis, None, None),
                mask=P(self.axis, None, None),
                num_targets=P(self.axis),
            )
            for _ in sizes
        )
        f = shard_map(
            pipeline, mesh=self.mesh,
            in_specs=(P(self.axis, None), P(self.axis, None),
                      P(self.axis, None), P(self.axis, None), P()),
            out_specs=(P(self.axis, None), P(self.axis, None),
                       P(self.axis), blocks_spec, P(self.axis, None)),
        )
        return jax.jit(f)

    def sample(self, seed_batches: np.ndarray, key=None):
        """``seed_batches``: [n_shards, B] — one seed batch per device;
        ``key``: int seed (PRNG keys are derived per shard inside).
        Returns per-shard :class:`SampledBatch`-style pytrees stacked on
        the leading axis.

        After each call ``self.last_overflow`` holds a ``[n_shards, L]``
        device array of per-hop counts of frontier entries that overflowed
        their destination bucket and were silently dropped (sampled 0
        neighbors).  Always zero at ``request_cap_frac=1.0``.
        """
        seeds = jnp.asarray(seed_batches, jnp.int32)
        nd, B = seeds.shape
        assert nd == self.n, (nd, self.n)
        valid = jnp.ones((nd, B), bool)
        if key is None:
            key = np.random.randint(0, 2**31 - 1)
        if B not in self._fn:
            self._fn[B] = self._build(B)
        sh = NamedSharding(self.mesh, P(self.axis, None))
        seeds = jax.device_put(seeds, sh)
        valid = jax.device_put(valid, sh)
        def _exchange():
            _CHAOS_EXCHANGE()
            return self._fn[B](
                self.indptr_sh, self.indices_sh, seeds, valid,
                jnp.int32(key),
            )

        def _on_retry(attempt, exc):
            from .. import telemetry

            telemetry.counter("dist_sampler_retries_total").inc()

        # one retried attempt with a short jittered backoff — a
        # transient peer stall usually clears; a second timeout surfaces
        # to the caller (sampling has no partial-answer degrade: a
        # frontier with holes would silently bias the training batch)
        n_id, n_mask, num, blocks, overflow = retry_call(
            _exchange, attempts=2, backoff=self._retry_backoff,
            retry_on=(PeerTimeout, TimeoutError), on_retry=_on_retry)
        self.last_overflow = overflow
        self._overflow_recorded = False
        return n_id, n_mask, num, blocks

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
                from .. import telemetry

                telemetry.counter("dist_sampler_overflow_total").inc(total)
        return arr
