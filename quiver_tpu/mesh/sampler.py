"""Frontier exchange over the mesh: row-range-sharded neighbor sampling.

Each shard holds the CSR rows of ITS row range only (local ``indptr``
over ``rows_per_shard`` rows, global ids in ``indices``) and samples
the full frontier with :func:`~quiver_tpu.ops.sample.
sample_neighbors_overlay` — the SAME op the stream tier serves — under
a ``seed_mask`` marking the rows it owns.  The op's uniforms are keyed
by ``(key, B, k)`` alone, never by seed ids, so every shard reproduces
the exact draw stream of the single-device sampler for the rows it
owns; the per-shard outputs are disjoint by construction and a
``pmax``/``psum`` collective over the ``shard`` axis (the frontier
exchange) reassembles the global ``SampleOut`` **bit-identically** to
the unsharded path (``tests/test_mesh.py`` pins it).

Executable accounting (docs/RETRACE.md discipline): the per-shard
sampling op is ONE module-level jit whose shapes are uniform across
shards — local ``indptr`` is ``rows_per_shard + 1`` everywhere and
``indices`` pads to one pow2 bucket over the *largest* shard — so its
key is effectively extended by the shard count (``rows_per_shard``
moves when ``n_shards`` does) and N shards reuse ONE executable.  The
combine is cached under ``("combine", B, k, n_shards)`` in the
``mesh_sampler`` program cache.  Steady-state serving over a fixed
frontier-size ladder builds nothing.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import telemetry
from ..analysis.staging import no_sync
from ..ops.sample import SampleOut, sample_neighbors_overlay
from ..recovery.registry import program_cache
from .topology import (SHARD_AXIS, build_mesh, row_shard, shard_devices,
                       shard_ranges, stack_shards)

__all__ = ["MeshSampler"]


def _pow2(n: int) -> int:
    b = 1
    while b < max(int(n), 1):
        b <<= 1
    return b


class MeshSampler:
    """One-hop frontier sampling over a row-range-sharded CSR."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 n_shards: Optional[int] = None, mesh=None,
                 gather_mode: str = "xla", sample_rng: str = "auto"):
        import jax

        from ..config import (get_config, resolve_gather_mode,
                              resolve_sample_rng)

        cfg = get_config()
        if n_shards is None:
            n_shards = cfg.mesh_shards
        self.n_shards = int(n_shards)
        if self.n_shards < 1:
            raise ValueError(
                f"MeshSampler needs n_shards >= 1 (config.mesh_shards "
                f"is off); got {self.n_shards}")
        self.mesh = mesh if mesh is not None else build_mesh(self.n_shards)
        self.axis = SHARD_AXIS
        self.gather_mode = resolve_gather_mode(gather_mode)
        self.sample_rng = resolve_sample_rng(sample_rng)
        indptr = np.asarray(indptr, dtype=np.int32)
        indices = np.asarray(indices, dtype=np.int32)
        self.node_count = len(indptr) - 1
        self.rows_per_shard, self.ranges = shard_ranges(
            self.node_count, self.n_shards)
        # one pow2 edge bucket over the largest shard: uniform shapes ->
        # ONE sampling executable reused by every shard.  Every table a
        # multiple of 128, as ``CSRTopo.to_device`` pads: what the
        # ``blocked`` gather path asks of a table
        edge_pad = max(128, _pow2(max(
            int(indptr[hi] - indptr[lo]) for lo, hi in self.ranges)))
        ip_pad = -(-(self.rows_per_shard + 1) // 128) * 128
        # each shard's CSR (and its empty overlay) is COMMITTED to that
        # shard's device, so its hop runs there on data that never left
        self._devices = shard_devices(self.mesh)[0]
        self._indptr, self._indices, self._overlay = [], [], []
        for (lo, hi), dev in zip(self.ranges, self._devices):
            lp = np.zeros(ip_pad, dtype=np.int32)
            lp[: hi - lo + 1] = indptr[lo:hi + 1] - indptr[lo]
            lp[hi - lo + 1:] = lp[hi - lo]      # pad rows: degree 0
            li = np.zeros(edge_pad, dtype=np.int32)
            li[: lp[hi - lo]] = indices[indptr[lo]:indptr[hi]]
            self._indptr.append(jax.device_put(lp, dev))
            self._indices.append(jax.device_put(li, dev))
            # frozen-graph mesh tier: no tombstones, empty delta overlay
            # — the overlay op with zero deltas is bitwise the frozen
            # sampler
            self._overlay.append(tuple(
                jax.device_put(np.zeros(n, dtype=np.int32), dev)
                for n in (edge_pad, ip_pad, 128)))
        self._sharding = row_shard(self.mesh)
        self._edge_base = np.asarray(
            [int(indptr[lo]) for lo, _ in self.ranges], dtype=np.int32)
        self._jitted = program_cache("mesh_sampler", owner=self)
        from . import _set_active_sampler

        _set_active_sampler(self)

    # ------------------------------------------------------------------
    def _combine_fn(self, B: int, k: int):
        """The frontier exchange: per-shard disjoint ``SampleOut``
        blocks -> the global sample, as a collective over ``shard``."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        key = ("combine", B, k, self.n_shards)
        fn = self._jitted.get(key)
        if fn is None:
            axis = self.axis

            def _local(nbrs, mask, counts, eid, base):
                # exactly one shard owns each seed row: ids are >= 0
                # there and -1 on every other shard, so pmax selects
                # the owner's block unchanged; counts sum (others are 0)
                nb = jax.lax.pmax(nbrs[0], axis)
                mk = jax.lax.pmax(mask[0].astype(jnp.int32), axis) > 0
                # int32 cast makes the count-sum provably integer (QT015
                # bit-exactness contract): psum is reserved for counts,
                # payload rows go through the pmax sentinel above
                ct = jax.lax.psum(counts[0].astype(jnp.int32), axis)
                # shard-local edge positions -> global: offset by the
                # shard's first edge (eid stays -1 where masked)
                ei = jnp.where(eid[0] >= 0, eid[0] + base[0],
                               jnp.int32(-1))
                ei = jax.lax.pmax(ei, axis)
                return nb, mk, ct, ei

            fn = jax.jit(shard_map(
                _local, mesh=self.mesh,
                in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
                out_specs=(P(), P(), P(), P())))
            # quiverlint: ignore[QT014] -- raw B is deliberate: the mesh
            # sampler is bit-identical to the single-device path under
            # the same key, and padding seeds would change RNG
            # consumption; serving feeds pow2-padded batches, and
            # seal()/retrace_budget guard steady-state.
            self._jitted[key] = fn
        return fn

    def sample(self, seeds, k: int, key) -> SampleOut:
        """One dense ``[B, k]`` hop over the sharded CSR, bit-identical
        to the single-device sampler under the same ``key``."""
        import jax
        import jax.numpy as jnp

        seeds = np.asarray(seeds, dtype=np.int64).reshape(-1)
        B = len(seeds)
        outs = []
        for s, (lo, hi) in enumerate(self.ranges):
            owned = (seeds >= lo) & (seeds < hi)
            telemetry.gauge("mesh_shard_frontier_rows",
                            shard=str(s)).set(float(owned.sum()))
            local = np.clip(seeds - lo, 0, self.rows_per_shard - 1)
            outs.append(sample_neighbors_overlay(
                self._indptr[s], self._indices[s], *self._overlay[s],
                jnp.asarray(local, jnp.int32), k, key,
                seed_mask=jnp.asarray(owned),
                gather_mode=self.gather_mode,
                sample_rng=self.sample_rng))
        stack = [stack_shards(self.mesh, xs)
                 for xs in (tuple(o.nbrs for o in outs),
                            tuple(o.mask for o in outs),
                            tuple(o.counts for o in outs),
                            tuple(o.eid for o in outs))]
        base = jax.device_put(jnp.asarray(self._edge_base),
                              self._sharding)
        # the cross-shard combine dispatches collectives; a host sync
        # here would serialize the whole mesh per hop
        with no_sync("mesh combine"):
            nb, mk, ct, ei = self._combine_fn(B, k)(*stack, base)
        return SampleOut(nbrs=nb, mask=mk, counts=ct, eid=ei)

    def stats(self) -> dict:
        return dict(n_shards=self.n_shards,
                    rows_per_shard=self.rows_per_shard,
                    node_count=self.node_count,
                    executables=len(self._jitted),
                    # where each shard's CSR lives, and how much of it
                    placement=[dict(device=str(next(iter(ix.devices()))),
                                    bytes=int(ip.nbytes + ix.nbytes))
                               for ip, ix in zip(self._indptr,
                                                 self._indices)])

    def __repr__(self):
        return (f"MeshSampler(nodes={self.node_count}, "
                f"shards={self.n_shards})")
