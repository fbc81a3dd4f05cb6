"""Multi-hop GraphSAGE sampler — TPU-native GraphSageSampler.

Reference parity: ``srcs/python/quiver/pyg/sage_sampler.py:40-178``.  The
reference returns PyG's ``(n_id, batch_size, adjs)`` with ragged
``edge_index`` per layer; we return a :class:`SampledBatch` of dense
``[B_l, k_l]`` blocks (static shapes, jit-able end to end) plus adapters to
the ragged PyG form.

Modes (vs reference UVA/GPU/CPU, ``sage_sampler.py:55-81``):
  * ``"TPU"`` — topology in HBM, sampling under jit (replaces both GPU and
    UVA: there is no zero-copy middle tier on TPU; big graphs shard instead).
  * ``"CPU"`` — native C++ host sampler (``quiver_tpu.cpp``), used by the
    serving hybrid path and the mixed sampler.

Padded-frontier discipline: layer l's frontier is padded to
``P_l = min(P_{l-1} * (1 + k_l), frontier_caps[l])``.  With no caps the
result is exact (every sampled node kept); caps trade a vanishing amount of
tail-dropping for bounded shapes — measured frontiers on power-law graphs
sit far below the no-dedup bound, so a cap ~2x the typical frontier loses
~nothing and keeps XLA shapes small.

Typed graphs (``schema=``, a :class:`Schema`): node types by id range and
relations between them; each hop then draws ``sizes[h]`` sources PER
RELATION from the part of a target's CSR row that holds that relation's
source type (its window, read off the CSR once, when the sampler is built),
into a typed tree whose every position has a type known when the program
is traced (:meth:`Schema.tree`).  The untyped graph is the schema of one
type and one relation whose window is the whole row, and samples as it
always did.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .ops.sample import (sample_neighbors, sample_neighbors_overlay,
                         sample_neighbors_weighted, row_cumsum_weights)
from .ops.blockgather import NO_WINDOW, fallback_slots
from .ops.reindex import reindex
from .telemetry.device_scopes import HOST_SAMPLE, SAMPLER, sampler_hop
from .utils.topology import CSRTopo

__all__ = ["GraphSageSampler", "SampledBatch", "LayerBlock", "POSITIONAL",
           "Schema"]


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _Positional:
    """Type of :data:`POSITIONAL`: a pytree node with no leaves (a frozen
    dataclass, equal to and hashed like every instance with the same
    fields), so the marker is part of a block's tree *structure* and
    crosses ``jax.jit`` as the Python value it is, never as a tracer.

    A typed tree's block (:meth:`Schema.tree`) carries its place: its
    ``relation``, its ``targets`` as ``(start, count)`` runs of the previous
    frontier in order, and ``sources``, the frontier row of its first
    source: target ``b``'s sources are rows ``sources + b*k .. sources +
    b*k + k - 1``.  :data:`POSITIONAL` (the untyped tree's block, from this
    sampler, the stream overlay and ``dist/``) leaves its place to be read
    off the block's shape: relation 0, the first ``T`` rows, the ``T k``
    after them (:meth:`place`)."""

    relation: int = 0
    targets: Tuple[Tuple[int, int], ...] = ()
    sources: Optional[int] = None

    def place(self, t: int) -> Tuple[Tuple[Tuple[int, int], ...], int]:
        """``(targets, sources)`` of a block of ``t`` targets."""
        if self.sources is None:
            return ((0, t),), t
        return self.targets, self.sources


POSITIONAL = _Positional()


@dataclasses.dataclass(frozen=True)
class Schema:
    """The node types and relations of a typed graph, for a sampler that
    draws per relation.

    Args:
      type_offsets: where each node type's id range starts, and the node
        count last: ``(0, n_type0, n_type0 + n_type1, ..., N)`` (the form
        ``models.RGNN`` takes).
      relations: ``(source type, target type)`` of each relation; a
        relation's edges are the CSR entries of a target of its target type
        whose source is of its source type.
      seed_type: the type of every seed.
      names: optional relation names, the counters' ``relation`` label
        (the relation's index where absent).

    A CSR row must hold its sources grouped by type, in the order of the
    types: true of any CSR whose rows are sorted by id.  Edges of a pair of
    types no relation names are never drawn.
    """

    type_offsets: Tuple[int, ...]
    relations: Tuple[Tuple[int, int], ...]
    seed_type: int = 0
    names: Tuple[str, ...] = ()

    def __post_init__(self):
        off = self.type_offsets
        n = len(off) - 1
        if n < 1 or off[0] != 0 or any(a > b for a, b in zip(off, off[1:])):
            raise ValueError(f"type_offsets must rise from 0: {off}")
        if not self.relations or any(
                not (0 <= s < n and 0 <= t < n) for s, t in self.relations):
            raise ValueError(f"relations must join types 0..{n - 1}: "
                             f"{self.relations}")
        if len(set(self.relations)) != len(self.relations):
            raise ValueError(f"a pair of types is one relation: "
                             f"{self.relations}")
        if not 0 <= self.seed_type < n:
            raise ValueError(f"seed_type {self.seed_type} of {n} types")
        if self.names and len(self.names) != len(self.relations):
            raise ValueError("one name per relation")

    def label(self, r: int) -> str:
        return self.names[r] if self.names else str(r)

    def tree(self, batch: int, sizes: Sequence[int]
             ) -> Tuple[Tuple[_Positional, ...], ...]:
        """The typed tree of ``batch`` seeds under fanouts ``sizes``, hop 1
        first: per hop, for each relation in order whose target type the
        frontier holds, the place of its ``[n_t, k]`` block.  Targets are
        every frontier position of the target type (runs of adjacent ones
        merged); the block's sources are appended to the frontier in
        relation order, so each position's type is known here."""
        segs = [(self.seed_type, 0, batch)]     # (type, start, count)
        size = batch
        hops = []
        for k in sizes:
            runs, grown = [], []
            for r, (s, t) in enumerate(self.relations):
                tgt = []
                for ty, a, n in segs:
                    if ty != t:
                        continue
                    if tgt and tgt[-1][0] + tgt[-1][1] == a:
                        tgt[-1] = (tgt[-1][0], tgt[-1][1] + n)
                    else:
                        tgt.append((a, n))
                n_t = sum(n for _, n in tgt)
                if not n_t:
                    continue
                runs.append(_Positional(relation=r, targets=tuple(tgt),
                                        sources=size))
                grown.append((s, size, n_t * k))
                size += n_t * k
            hops.append(tuple(runs))
            segs += grown
        return tuple(hops)

    def slots(self, batch: int, sizes: Sequence[int]) -> list:
        """Per relation, the draw slots of its blocks over all hops."""
        out = [0] * len(self.relations)
        for k, runs in zip(sizes, self.tree(batch, sizes)):
            for run in runs:
                out[run.relation] += k * sum(n for _, n in run.targets)
        return out

    def window_base(self) -> Tuple[int, ...]:
        """Where each relation's windows start in :meth:`windows`' table:
        two entries (start, end) per node of its target type."""
        base, at = [], 0
        for _, t in self.relations:
            base.append(at)
            at += 2 * (self.type_offsets[t + 1] - self.type_offsets[t])
        return tuple(base)

    def window_rows(self, relation: int, nodes: jax.Array) -> jax.Array:
        """Traced: the entry of each node's window of ``relation`` in the
        table, an ``indptr``-like pair ``table[i], table[i + 1]``."""
        t = self.relations[relation][1]
        return (self.window_base()[relation]
                + 2 * (nodes - self.type_offsets[t]))

    def windows(self, indptr, indices) -> np.ndarray:
        """Host: the ``[start, end)`` of every relation's part of every row
        of its target type, in positions of ``indices``, as one int32 table
        (relation by relation, a node's pair side by side; padded to a
        multiple of 128 for the ``blocked`` gather).  Raises where a row
        does not hold its sources grouped in the order of the types."""
        indptr = np.asarray(indptr, np.int64)
        off = np.asarray(self.type_offsets, np.int64)
        if off[-1] != len(indptr) - 1:
            raise ValueError(f"type_offsets count {off[-1]} nodes, the CSR "
                             f"{len(indptr) - 1}")
        etype = np.searchsorted(off[1:-1], np.asarray(indices),
                                side="right").astype(np.int8)
        down = np.flatnonzero(np.diff(etype) < 0) + 1
        if not np.isin(down, indptr).all():
            raise ValueError("a CSR row does not hold its sources grouped "
                             "in the order of the types (sort rows by id)")
        parts = []
        for s, t in self.relations:
            lo, hi = off[t], off[t + 1]
            e0 = indptr[lo]
            seg = etype[e0:indptr[hi]]
            row0, row1 = indptr[lo:hi] - e0, indptr[lo + 1:hi + 1] - e0
            pair = np.empty((hi - lo, 2), np.int64)
            for side, b in enumerate((s, s + 1)):
                # entries of each row whose source type is below b
                c = np.zeros(len(seg) + 1, np.int64)
                np.cumsum(seg < b, out=c[1:])
                pair[:, side] = e0 + row0 + c[row1] - c[row0]
            parts.append(pair.reshape(-1))
        table = np.concatenate(parts) if parts else np.zeros(0, np.int64)
        out = np.zeros(-(-max(len(table), 1) // 128) * 128, np.int32)
        out[:len(table)] = table
        return out


# the untyped graph: one type and one relation, whose window of a node is
# its whole row (``indptr[v], indptr[v + 1]``)
_UNTYPED = Schema((0, 1), ((0, 0),))


class LayerBlock(NamedTuple):
    """One message-passing layer's bipartite block, dense form.

    Targets are the first ``num_targets`` entries of the *previous* (inner)
    frontier; ``nbr_local[b, j]`` indexes into this layer's frontier
    (``n_id``) to find source nodes.

    ``layout=POSITIONAL`` is the producer's static promise that this
    layer's frontier has length ``T * (1 + k)`` and that
    ``nbr_local[b, j] == T + b * k + j`` wherever ``mask[b, j]``: the
    sources of target ``b`` are the contiguous frontier rows
    ``T + b*k .. T + b*k + k - 1``, so a model reads them as a slice
    (``models.layers.sources``) and no gather, nor its scatter-add
    backward, is compiled.  ``None`` (the default: ``dedup="hop"``, the
    host sampler, hand-built blocks) promises nothing and ``nbr_local`` is
    gathered through.  ``dist.DistGraphSampler`` promises it per rank.
    """

    nbr_local: jax.Array   # [T, k] int32 indices into this layer's n_id
    mask: jax.Array        # [T, k] bool
    num_targets: jax.Array  # scalar int32 (valid targets; T is the pad)
    eid: Optional[jax.Array] = None  # [T, k] int32 global edge ids (-1 pad)
    layout: Optional[_Positional] = None  # static, see above


class SampledBatch(NamedTuple):
    n_id: jax.Array         # [P] int32 final (outermost) frontier, padded
    n_id_mask: jax.Array    # [P] bool
    num_nodes: jax.Array    # scalar int32
    batch_size: int         # static: number of seed nodes
    layers: Tuple[LayerBlock, ...]  # outermost-first (PyG adjs order)
    drops: Optional[jax.Array] = None  # [L] per-hop frontier-cap drop
    # counts for THIS batch (overflow_stats(batch) reads it; the
    # sampler-level last_drops is unreliable under prefetching)
    version: Optional[int] = None  # streaming: the graph version this
    # batch sampled (the snapshot's), None on frozen-CSR samplers
    window_misses: Optional[jax.Array] = None  # per block, hop 1 first
    # (one per hop on the untyped tree): count of targets whose CSR window
    # did not fit the ``blocked`` path's block (window_stats(batch) reads
    # it; ops.blockgather.NO_WINDOW where the draw has no window route)
    relation_live: Optional[jax.Array] = None  # [R] live draws of each
    # relation over all hops (relation_stats(batch) reads it; one relation
    # on the untyped tree)

    def to_pyg_adjs(self):
        """Ragged ``(n_id, batch_size, [Adj])`` view, PyG-compatible.

        Host-side (numpy); mirrors ``sage_sampler.py:118-147``'s return.
        Each Adj is ``(edge_index[2, e], e_id[e], (n_src, n_dst))``.

        Sizes are the PADDED per-layer frontier lengths: each hop's target
        frontier is by construction a *prefix* of its source frontier (both
        pipelines append new nodes after the previous frontier), so the
        standard PyG shrinking loop ``x = x[:size[1]]`` between layers
        slices exactly the next layer's node set.  Masked pad slots hold
        node 0 and are referenced by no edge, so they flow through as inert
        rows; ``n_id`` is returned in full (padded) for the same reason.

        Untyped batches only: a typed batch's hop is a block per relation.
        """
        if not all(isinstance(blk, LayerBlock) for blk in self.layers):
            raise ValueError("a typed batch has a block per relation and "
                             "hop; read its layers directly")
        adjs = []
        n_src = int(self.n_id.shape[0])
        for blk in self.layers:
            m = np.asarray(blk.mask)  # quiverlint: sync-ok[PyG export boundary]
            nbr = np.asarray(blk.nbr_local)  # quiverlint: sync-ok[PyG export boundary]
            t, k = m.shape
            row = np.repeat(np.arange(t, dtype=np.int64), k).reshape(t, k)
            col = nbr.astype(np.int64)
            e = m.reshape(-1)
            edge_index = np.stack([col.reshape(-1)[e], row.reshape(-1)[e]])
            # quiverlint: sync-ok[PyG export boundary]
            e_id = (np.asarray(blk.eid).reshape(-1)[e]
                    if blk.eid is not None else np.empty(0, np.int64))
            adjs.append((edge_index, e_id, (n_src, t)))
            n_src = t  # this layer's targets = next (inner) layer's sources
        return (np.asarray(self.n_id), self.batch_size, adjs)  # quiverlint: sync-ok[PyG export boundary]


def _blocks_in_order(layers):
    """A batch's blocks hop 1 first, a typed hop's in relation order."""
    for hop in layers[::-1]:
        yield from ((hop,) if isinstance(hop, LayerBlock) else hop)


def _hop_targets(layers):
    """Targets of each block, hop 1 first, from a batch's blocks."""
    return [blk.mask.shape[0] for blk in _blocks_in_order(layers)]


def _run_rows(frontier, fmask, runs):
    """A block's targets: the ``(start, count)`` runs of the frontier and
    its mask, in order (a run of the whole frontier is the frontier: JAX
    adds no operation for a full slice or a concatenation of one)."""
    return tuple(jnp.concatenate([a[s:s + n] for s, n in runs])
                 for a in (frontier, fmask))


def _relation_live(layers, relations):
    """``[relations]`` int32 live draws of each relation (an untyped
    block's relation is 0)."""
    live = [jnp.int32(0)] * relations
    for blk in _blocks_in_order(layers):
        r = getattr(blk.layout, "relation", 0)
        live[r] = live[r] + blk.mask.sum(dtype=jnp.int32)
    return jnp.stack(live)


def _window_misses(nfalls):
    """[L] int32 from each hop's ``SampleOut.nfall``."""
    return jnp.stack([jnp.int32(NO_WINDOW) if n is None else n
                      for n in nfalls])


def _sample_pipeline_nodedup(indptr, indices, seeds, key, sizes,
                             gather_mode="xla", cum_weights=None,
                             return_eid=False, sample_rng="auto",
                             schema=None, windows=None):
    """Traced multi-hop pipeline WITHOUT dedup — the TPU hot path.

    Design note (why no hash table / no sort): the reference dedups every
    hop because on GPU the saved gathers/compute outweigh a hash-table
    kernel (reindex.cu.hpp).  On TPU the trade inverts: sort/searchsorted/
    scatter are the *slow* ops (measured: a hop-3-sized sort costs ~10x the
    sampling itself) while the MXU/HBM make duplicated frontier rows nearly
    free.  So the hot path relabels **positionally**: the hop-l frontier is
    ``concat(prev_frontier, sampled_nbrs.flat)`` and neighbor j of target b
    lives at position ``P_prev + b*k + j`` — no table, no sort, no scatter.
    Duplicate nodes compute duplicate embeddings (= original GraphSAGE
    tree-expansion semantics); validity masks carry through.  Exact-dedup
    per hop stays available via ``dedup="hop"`` for parity.

    Because that position is a function of ``(b, j)`` alone, every block
    is marked ``layout=POSITIONAL`` and the convs read a target's sources
    as the slice ``x[P_prev:]`` viewed ``[P_prev, k, D]`` instead of
    gathering through ``nbr_local`` (a row-at-a-time gather whose
    backward is a scatter-add; PERF.md, PR 27).  ``nbr_local`` is still
    returned, value for value, for every consumer that indexes by it
    (``to_pyg_adjs``, host checks); inside a fused program nothing reads
    it and XLA drops its ``iota + where``.

    With a ``schema`` (:class:`Schema`) the tree is typed: a hop draws
    per relation, for every frontier position of the relation's target
    type, from that node's window of the relation (``windows``, the table
    of :meth:`Schema.windows`, read as ``indptr`` is: the same draw); each
    relation's ``[n_t, k]`` block is appended to the frontier in relation
    order and carries its place (:class:`_Positional`), so a hop returns a
    tuple of blocks.  Each relation's draw takes its own key of the hop's.
    """
    with jax.named_scope(SAMPLER):
        B = seeds.shape[0]
        frontier = seeds.astype(jnp.int32)
        fmask = jnp.ones((B,), dtype=bool)
        keys = jax.random.split(key, len(sizes))
    tree = (schema or _UNTYPED).tree(B, sizes)
    blocks = []
    nfalls = []
    for l, (k, runs) in enumerate(zip(sizes, tree)):
        with jax.named_scope(sampler_hop(l + 1)):
            run_keys = ([keys[l]] if len(runs) == 1
                        else jax.random.split(keys[l], len(runs)))
            hop, outs = [], []
            for run, run_key in zip(runs, run_keys):
                tgt, tmask = _run_rows(frontier, fmask, run.targets)
                table, rows = indptr, tgt
                if schema is not None:
                    table = windows
                    rows = schema.window_rows(run.relation, tgt)
                if cum_weights is not None:
                    out = sample_neighbors_weighted(
                        table, indices, cum_weights, rows, k, run_key,
                        seed_mask=tmask, sample_rng=sample_rng,
                        gather_mode=gather_mode)
                else:
                    out = sample_neighbors(table, indices, rows, k, run_key,
                                           seed_mask=tmask,
                                           gather_mode=gather_mode,
                                           sample_rng=sample_rng)
                nfalls.append(out.nfall)
                t = tgt.shape[0]
                pos = (run.sources + jnp.arange(t, dtype=jnp.int32)[:, None]
                       * k + jnp.arange(k, dtype=jnp.int32)[None, :])
                hop.append(
                    LayerBlock(
                        nbr_local=jnp.where(out.mask, pos, 0),
                        mask=out.mask,
                        num_targets=tmask.sum().astype(jnp.int32),
                        # None lets XLA DCE the eid computation entirely —
                        # an extra [T, k] int32 per hop is ~40% more
                        # sampler output HBM traffic, only worth it for
                        # edge-featured models
                        eid=out.eid if return_eid else None,
                        layout=POSITIONAL if schema is None else run,
                    )
                )
                outs.append(out)
            blocks.append(hop[0] if schema is None else tuple(hop))
            frontier = jnp.concatenate(
                [frontier] + [jnp.where(out.mask, out.nbrs, 0).reshape(-1)
                              for out in outs]
            )
            fmask = jnp.concatenate(
                [fmask] + [out.mask.reshape(-1) for out in outs])
    with jax.named_scope(SAMPLER):
        num_nodes = fmask.sum().astype(jnp.int32)
        drops = jnp.zeros((len(sizes),), jnp.int32)  # nothing ever dropped
        misses = _window_misses(nfalls)
    return frontier, fmask, num_nodes, tuple(blocks[::-1]), drops, misses


def _sample_pipeline_overlay(indptr, indices, tomb, d_indptr, d_indices,
                             seeds, key, sizes, base_ts=None, d_ts=None,
                             window_lo=None, window_hi=None,
                             gather_mode="xla", return_eid=False,
                             sample_rng="auto", windowed=False):
    """Traced multi-hop pipeline over base CSR + delta overlay.

    Structurally identical to :func:`_sample_pipeline_nodedup` (same key
    split, same positional relabel and ``layout=POSITIONAL`` marker), with
    the one-hop op swapped for
    :func:`~quiver_tpu.ops.sample.sample_neighbors_overlay` — so with an
    empty delta segment and no tombstones the outputs are bitwise
    identical to the frozen positional pipeline (the streaming tier's
    equivalence contract).
    """
    with jax.named_scope(SAMPLER):
        B = seeds.shape[0]
        frontier = seeds.astype(jnp.int32)
        fmask = jnp.ones((B,), dtype=bool)
        keys = jax.random.split(key, len(sizes))
    blocks = []
    for l, k in enumerate(sizes):
        with jax.named_scope(sampler_hop(l + 1)):
            out = sample_neighbors_overlay(
                indptr, indices, tomb, d_indptr, d_indices, frontier, k,
                keys[l], seed_mask=fmask, base_ts=base_ts, d_ts=d_ts,
                window_lo=window_lo, window_hi=window_hi,
                gather_mode=gather_mode, sample_rng=sample_rng,
                windowed=windowed)
            t = frontier.shape[0]
            pos = (t + jnp.arange(t, dtype=jnp.int32)[:, None] * k
                   + jnp.arange(k, dtype=jnp.int32)[None, :])
            blocks.append(
                LayerBlock(
                    nbr_local=jnp.where(out.mask, pos, 0),
                    mask=out.mask,
                    num_targets=fmask.sum().astype(jnp.int32),
                    eid=out.eid if return_eid else None,
                    layout=POSITIONAL,
                )
            )
            frontier = jnp.concatenate(
                [frontier, jnp.where(out.mask, out.nbrs, 0).reshape(-1)]
            )
            fmask = jnp.concatenate([fmask, out.mask.reshape(-1)])
    with jax.named_scope(SAMPLER):
        num_nodes = fmask.sum().astype(jnp.int32)
        drops = jnp.zeros((len(sizes),), jnp.int32)
        # the overlay op fetches per draw under every mode
        misses = _window_misses([None] * len(sizes))
    return frontier, fmask, num_nodes, tuple(blocks[::-1]), drops, misses


def _sample_pipeline(indptr, indices, seeds, key, sizes, caps,
                     gather_mode="xla", cum_weights=None,
                     return_eid=False, sample_rng="auto"):
    """Traced multi-hop pipeline: outward sampling with per-hop dedup."""
    with jax.named_scope(SAMPLER):
        B = seeds.shape[0]
        frontier = seeds.astype(jnp.int32)
        fmask = jnp.ones((B,), dtype=bool)
        keys = jax.random.split(key, len(sizes))
    blocks = []
    drops = []  # per-hop count of frontier nodes dropped by the cap
    nfalls = []
    for l, (k, cap) in enumerate(zip(sizes, caps)):
        with jax.named_scope(sampler_hop(l + 1)):
            if cum_weights is not None:
                out = sample_neighbors_weighted(indptr, indices, cum_weights,
                                                frontier, k, keys[l],
                                                seed_mask=fmask,
                                                sample_rng=sample_rng,
                                                gather_mode=gather_mode)
            else:
                out = sample_neighbors(indptr, indices, frontier, k, keys[l],
                                       seed_mask=fmask,
                                       gather_mode=gather_mode,
                                       sample_rng=sample_rng)
            nfalls.append(out.nfall)
            r = reindex(frontier, out.nbrs, out.mask, seed_mask=fmask)
            blocks.append(
                LayerBlock(
                    nbr_local=r.local_nbrs,
                    mask=r.mask,
                    num_targets=fmask.sum().astype(jnp.int32),
                    eid=out.eid if return_eid else None,
                )
            )
            n_id, n_mask = r.n_id, r.n_id_mask
            drop = jnp.int32(0)
            if cap is not None and n_id.shape[0] > cap:
                # Keep the prefix: seeds region is intact (caps must be >= T);
                # dropped tail nodes get masked out of this layer's block.
                drop = n_mask[cap:].sum().astype(jnp.int32)
                n_id, n_mask = n_id[:cap], n_mask[:cap]
                keep = blocks[-1].nbr_local < cap
                blocks[-1] = blocks[-1]._replace(
                    mask=blocks[-1].mask & keep,
                    nbr_local=jnp.where(keep, blocks[-1].nbr_local, 0),
                    eid=(jnp.where(keep, blocks[-1].eid, jnp.int32(-1))
                         if blocks[-1].eid is not None else None),
                )
            drops.append(drop)
            frontier, fmask = n_id, n_mask
    with jax.named_scope(SAMPLER):
        num_nodes = fmask.sum().astype(jnp.int32)
        drops = jnp.stack(drops)
        misses = _window_misses(nfalls)
    return frontier, fmask, num_nodes, tuple(blocks[::-1]), drops, misses


def _is_stream_graph(obj) -> bool:
    """Duck-typed StreamingGraph detection (no static import cycle):
    anything exposing ``snapshot()`` + ``base`` samples via the overlay
    pipeline."""
    return hasattr(obj, "snapshot") and hasattr(obj, "base")


def run_pipeline(dedup, indptr, indices, seeds, key, sizes, caps,
                 gather_mode="xla", cum_weights=None, return_eid=False,
                 sample_rng="auto", overlay=None, schema=None,
                 windows=None):
    """Dispatch to the dedup='none' or dedup='hop' traced pipeline — the
    single place that mapping lives (sampler jit + fused train/eval).

    ``overlay`` (a dict of delta-CSR arrays + window scalars, see
    ``GraphSageSampler._build_stream_jit``) routes to the streaming
    overlay pipeline; it rides the positional (``dedup='none'``)
    formulation only.  So does a typed graph's ``schema`` with its
    ``windows`` table (:class:`Schema`), uniform draws only.
    """
    if schema is not None and (dedup != "none" or overlay is not None
                               or cum_weights is not None):
        raise ValueError("a typed graph samples through the positional "
                         "pipeline (dedup='none'), frozen and uniform")
    if overlay is not None:
        if dedup != "none":
            raise ValueError(
                "overlay sampling rides the positional pipeline only "
                f"(dedup='none'); got dedup={dedup!r}")
        if cum_weights is not None:
            raise ValueError("overlay sampling is uniform-only")
        return _sample_pipeline_overlay(
            indptr, indices, overlay["tomb"], overlay["d_indptr"],
            overlay["d_indices"], seeds, key, sizes,
            base_ts=overlay.get("base_ts"), d_ts=overlay.get("d_ts"),
            window_lo=overlay.get("window_lo"),
            window_hi=overlay.get("window_hi"),
            gather_mode=gather_mode, return_eid=return_eid,
            sample_rng=sample_rng,
            windowed=bool(overlay.get("windowed", False)))
    if dedup == "none":
        return _sample_pipeline_nodedup(indptr, indices, seeds, key, sizes,
                                        gather_mode=gather_mode,
                                        cum_weights=cum_weights,
                                        return_eid=return_eid,
                                        sample_rng=sample_rng,
                                        schema=schema, windows=windows)
    return _sample_pipeline(indptr, indices, seeds, key, sizes, caps,
                            gather_mode=gather_mode,
                            cum_weights=cum_weights, return_eid=return_eid,
                            sample_rng=sample_rng)


class GraphSageSampler:
    """K-hop neighbor sampler over a CSR graph.

    Args:
      csr_topo: :class:`CSRTopo`.
      sizes: fanout per layer, e.g. ``[15, 10, 5]`` (outward order, like PyG).
      device: jax device for the topology (None = default).
      mode: ``"TPU"`` (jit, default) or ``"CPU"`` (native host sampler).
      frontier_caps: optional per-layer cap on the padded frontier size
        (see module docstring).  Only meaningful with ``dedup="hop"``.
      dedup: ``"auto"`` (default: ``"none"``, ``config.resolve_dedup``),
        ``"none"`` (TPU hot path — positional relabel, no sort; frontier
        may contain duplicate nodes) or ``"hop"`` (reference-parity exact
        dedup each hop via ``ops.reindex``).
      gather_mode: ``"auto"`` (default: the backend's,
        ``config.resolve_gather_mode``), ``"xla"`` or ``"blocked"``.
      edge_weights: optional ``[E]`` weights; hops then draw neighbors
        weight-proportionally WITH replacement
        (``ops.sample_neighbors_weighted``, reference weight_sample path).
      return_eid: materialize per-edge global CSR positions in
        ``LayerBlock.eid`` (and ``to_pyg_adjs`` e_id) for edge-featured
        models.  Off by default: it costs an extra ``[T, k]`` int32 per
        hop of output traffic, and the reference's default e_id is empty
        too (``sage_sampler.py:143``).
      schema: a typed graph's :class:`Schema`.  Each hop then draws
        ``sizes[h]`` sources per relation into the typed tree of
        :meth:`Schema.tree`; seeds must be of ``schema.seed_type``.  The
        relations' windows are read off the CSR here, once, and ride into
        every program as an argument (:meth:`windows_device`).  TPU mode,
        the positional pipeline (``dedup`` ``"auto"`` / ``"none"``), a frozen
        CSR and uniform draws only.  ``relation_stats()`` counts each
        relation's slots and draws.
    """

    def __init__(self, csr_topo: CSRTopo, sizes: Sequence[int], device=None,
                 mode: str = "TPU",
                 frontier_caps: Optional[Sequence[Optional[int]]] = None,
                 dedup: str = "auto", gather_mode: str = "auto",
                 edge_weights=None, return_eid: bool = False,
                 uva_budget: Union[int, str, None] = None,
                 sample_rng: str = "auto", uva_overlap: bool = True,
                 uva_timings: Optional[dict] = None,
                 schema: Optional[Schema] = None):
        assert mode in ("TPU", "CPU", "UVA", "GPU"), mode
        if mode == "GPU":  # compat alias from the reference API
            mode = "TPU"
        # streaming graphs (quiver_tpu.stream.StreamingGraph) are duck-
        # typed to avoid a static sampler -> stream import cycle; they
        # sample through the jitted overlay pipeline (TPU mode,
        # positional relabel, uniform draws only)
        is_stream = _is_stream_graph(csr_topo)
        if is_stream and mode != "TPU":
            raise ValueError(
                f"StreamingGraph samples in TPU mode only, got "
                f"{mode!r} (compact to a frozen CSRTopo for "
                "CPU/UVA sampling)")
        if mode == "UVA" and uva_budget is None:
            mode = "TPU"  # whole graph fits the (unbounded) budget
        from .config import (resolve_dedup, resolve_gather_mode,
                             resolve_sample_rng)

        dedup = resolve_dedup(dedup)
        self.gather_mode = resolve_gather_mode(gather_mode)
        self.sample_rng = resolve_sample_rng(sample_rng)
        self.return_eid = return_eid
        self.csr_topo = csr_topo  # property setter: splits stream/frozen
        if is_stream:
            assert dedup == "none", (
                "StreamingGraph: positional pipeline only (dedup='none')")
            assert edge_weights is None, (
                "StreamingGraph: uniform sampling only")
        self.sizes = list(sizes)
        # live fanout scale (QoS degradation ladder L1).  Applies to the
        # HOST sampling path only: device pipelines bake ``sizes`` into
        # the jitted closure, and recompiling under overload is exactly
        # the wrong reaction — the CPU lane is where brownout headroom
        # is won anyway.
        self._fanout_frac = 1.0
        self.mode = mode
        self.dedup = dedup
        self.device = device
        self.frontier_caps = (
            list(frontier_caps) if frontier_caps is not None
            else [None] * len(self.sizes)
        )
        assert len(self.frontier_caps) == len(self.sizes)
        from .recovery.registry import program_cache

        self._jitted = program_cache(
            "sampler", owner=self)  # batch_size -> compiled pipeline
        # (mixed-size workloads — e.g. serving buckets — must not evict
        # each other)
        self._cpu = None
        self.uva_budget = uva_budget
        # uva_overlap=False serializes the device/host tiers (the A/B
        # baseline for the overlap claim); uva_timings accumulates the
        # cold tier's host wall ("host_s") when a dict is passed
        self.uva_overlap = uva_overlap
        self.uva_timings = uva_timings
        self._uva = None
        if mode == "UVA":
            assert dedup == "none", "UVA mode: positional pipeline only"
            assert edge_weights is None, "UVA mode: uniform sampling only"
            assert not return_eid, (
                "UVA mode: hot-tier edge positions are sub-CSR local, so "
                "global eids are unavailable; use TPU or CPU mode"
            )
        if schema is not None and (mode != "TPU" or is_stream
                                   or edge_weights is not None
                                   or dedup != "none"):
            raise ValueError("a typed graph samples in TPU mode over a "
                             "frozen CSR, uniformly, with dedup='none'")
        self.schema = schema
        self._windows = None if schema is None else schema.windows(
            csr_topo.indptr, csr_topo.indices)
        self._windows_dev = {}
        self._cum_weights = None
        self._edge_weights = edge_weights
        if edge_weights is not None and mode == "TPU":
            cw = row_cumsum_weights(csr_topo.indptr, edge_weights)
            import jax.numpy as _jnp

            from .ops.fastgather import pad_table_128

            # edge-value fill: clipped probes past E read a harmless
            # value; the blocked gather path requires 128-multiple tables
            self._cum_weights = pad_table_128(
                _jnp.asarray(cw), fill=float(cw[-1]) if len(cw) else None)
        if mode == "TPU":
            if self._stream is not None:
                self._stream.snapshot(device)  # warm the device view
            else:
                csr_topo.to_device(device)
            self.windows_device()

    def windows_device(self):
        """The typed graph's window table on the sampler's device (placed
        once), or None for an untyped graph: what a program reads in place
        of ``indptr`` to find a node's part of its row under a relation
        (:meth:`Schema.windows`)."""
        if self._windows is None:
            return None
        dev = self._windows_dev.get(self.device)
        if dev is None:
            dev = self._windows_dev[self.device] = jax.device_put(
                self._windows, self.device)
        return dev

    # -- topology access ----------------------------------------------
    @property
    def csr_topo(self):
        """The live base CSR.  For streaming graphs this follows the
        compactor's base swaps; single-hop helpers (``sample_layer``,
        ``sample_prob``) read it and therefore see the base WITHOUT the
        pending delta overlay — multi-hop :meth:`sample` is the overlay-
        aware path."""
        if self._stream is not None:
            return self._stream.base
        return self._csr_topo

    @csr_topo.setter
    def csr_topo(self, value):
        if _is_stream_graph(value):
            self._stream = value
            self._csr_topo = None
        else:
            self._stream = None
            self._csr_topo = value

    # -- single-hop API (parity with sample_layer / reindex,
    #    sage_sampler.py:83-116) --------------------------------------
    def sample_layer(self, batch, size: int, key=None):
        indptr, indices = self.csr_topo.to_device(self.device)
        if key is None:
            from .utils.rng import make_key

            key = make_key(0)
        seeds = jnp.asarray(np.asarray(batch), dtype=jnp.int32)
        return sample_neighbors(indptr, indices, seeds, size, key)

    def reindex(self, inputs, nbrs, mask):
        return reindex(jnp.asarray(np.asarray(inputs), jnp.int32), nbrs, mask)

    def sample_sub(self, seeds, size: int, key=None):
        """One-hop subgraph extraction: dedup'd node set + relabeled COO.

        Parity: ``TorchQuiver::sample_sub`` (quiver_sample.cu:258-303) —
        returns ``(nodes, row, col)`` where ``nodes[:len(seeds)] == seeds``
        and (row, col) are local-id edges of the sampled subgraph.
        """
        seeds = np.asarray(seeds)
        out = self.sample_layer(seeds, size, key=key)
        r = self.reindex(seeds, out.nbrs, out.mask)
        num = int(r.num_nodes)  # quiverlint: sync-ok[host subgraph export]
        nodes = np.asarray(r.n_id)[:num]  # quiverlint: sync-ok[host subgraph export]
        m = np.asarray(r.mask)  # quiverlint: sync-ok[host subgraph export]
        local = np.asarray(r.local_nbrs)  # quiverlint: sync-ok[host subgraph export]
        row = np.repeat(np.arange(len(seeds)), out.nbrs.shape[1]).reshape(
            m.shape
        )[m]
        col = local[m]
        return nodes, row, col

    # -- multi-hop API ------------------------------------------------
    def _build_jit(self, batch_size: int):
        indptr, indices = self.csr_topo.to_device(self.device)
        sizes = tuple(self.sizes)
        caps = tuple(self.frontier_caps)
        dedup = self.dedup
        gm = self.gather_mode
        cw = self._cum_weights

        ret_eid = self.return_eid

        srng = self.sample_rng
        schema = self.schema
        relations = 1 if schema is None else len(schema.relations)

        @jax.jit
        def fn(indptr, indices, cw, windows, seeds, key):
            out = run_pipeline(dedup, indptr, indices, seeds, key, sizes,
                               caps, gather_mode=gm, cum_weights=cw,
                               return_eid=ret_eid, sample_rng=srng,
                               schema=schema, windows=windows)
            return out + (_relation_live(out[3], relations),)

        # the tables ride as ARGUMENTS: a device array captured by the
        # closure is baked into the executable as a constant — a second
        # copy of the graph in HBM per compiled batch size
        return functools.partial(fn, indptr, indices, cw,
                                 self.windows_device())

    def _build_stream_jit(self, batch_size: int, windowed: bool):
        """Compile the overlay pipeline for one (batch, snapshot-shape)
        key.  Unlike :meth:`_build_jit` the topology arrays are traced
        ARGUMENTS, not closure constants: snapshot contents change every
        graph version, and baking them in would recompile per mutation.
        Executables therefore key on shapes only —
        ``(B, epad, delta_bucket, has_ts, windowed)`` — which is the
        additive-key discipline the retrace budget enforces."""
        sizes = tuple(self.sizes)
        gm = self.gather_mode
        srng = self.sample_rng
        ret_eid = self.return_eid
        caps = tuple(self.frontier_caps)

        @jax.jit
        def fn(indptr, indices, tomb, d_indptr, d_indices, base_ts, d_ts,
               seeds, key, window_lo, window_hi):
            overlay = dict(tomb=tomb, d_indptr=d_indptr,
                           d_indices=d_indices, base_ts=base_ts,
                           d_ts=d_ts, window_lo=window_lo,
                           window_hi=window_hi, windowed=windowed)
            return run_pipeline("none", indptr, indices, seeds, key,
                                sizes, caps, gather_mode=gm,
                                return_eid=ret_eid, sample_rng=srng,
                                overlay=overlay)

        return fn

    def sample(self, input_nodes, key=None,
               time_window=None) -> SampledBatch:
        """Sample k-hop neighborhood of ``input_nodes``.

        Returns a :class:`SampledBatch`; call ``.to_pyg_adjs()`` for the
        reference's ``(n_id, batch_size, adjs)`` tuple.

        ``time_window=(lo, hi)`` (streaming graphs with ``edge_ts``
        only) restricts draws to edges with ``lo <= ts < hi``; the
        window rides as traced scalars, so varying it never recompiles.

        Telemetry: each call folds into the ``sampler.sample`` span and
        the ``sampler_sample_seconds{mode}`` histogram (TPU mode times
        dispatch, not device completion — async), plus batch/seed
        counters.
        """
        mode = self.mode.lower()
        with telemetry.span(HOST_SAMPLE), telemetry.histogram(
                "sampler_sample_seconds", mode=mode).time():
            batch = self._sample_impl(input_nodes, key,
                                      time_window=time_window)
        telemetry.counter("sampler_batches_total", mode=mode).inc()
        telemetry.counter("sampler_seeds_total", mode=mode).inc(
            float(batch.batch_size))
        return batch

    def _sample_impl(self, input_nodes, key=None,
                     time_window=None) -> SampledBatch:
        if self._stream is not None:
            return self._sample_stream(input_nodes, key, time_window)
        if time_window is not None:
            raise ValueError(
                "time_window requires a StreamingGraph with per-edge "
                "timestamps (quiver_tpu.stream)")
        if self.mode == "CPU":
            return self._sample_cpu(input_nodes)
        if self.mode == "UVA":
            return self._sample_uva(input_nodes, key)
        if isinstance(input_nodes, jax.Array):  # stay on device
            seeds = input_nodes.astype(jnp.int32)
        else:
            seeds = jnp.asarray(np.asarray(input_nodes), dtype=jnp.int32)
        B = seeds.shape[0]
        fn = self._jitted.get(B)
        if fn is None:
            # quiverlint: ignore[QT014] -- raw B is the sampler's
            # contract: one executable per seed-batch size, bit-stable
            # RNG per seed row (padding would consume extra key splits).
            # Serving pads upstream via _pad_ids; seal()/retrace_budget
            # guard the steady state.
            fn = self._jitted[B] = self._build_jit(B)
        if key is None:
            from .utils.rng import make_key

            key = make_key(np.random.randint(0, 2**31 - 1))
        n_id, n_mask, num_nodes, blocks, drops, misses, live = fn(seeds, key)
        # [L] per-hop frontier-cap drop counts (always 0 without caps),
        # window misses and each relation's draws; kept on device until
        # someone asks via overflow_stats() / window_stats() /
        # relation_stats() — the counters are incremented there, at
        # materialization, so the hot loop never pays a device sync for
        # accounting
        self._keep_counts(drops, misses, blocks, live, B)
        return SampledBatch(
            n_id=n_id, n_id_mask=n_mask, num_nodes=num_nodes,
            batch_size=B, layers=blocks, drops=drops, window_misses=misses,
            relation_live=live,
        )

    def _sample_stream(self, input_nodes, key, time_window) -> SampledBatch:
        """Overlay-aware multi-hop sampling against the current
        :class:`~quiver_tpu.stream.graph.DeltaSnapshot`."""
        snap = self._stream.snapshot(self.device)
        windowed = time_window is not None
        if windowed and not snap.has_ts:
            raise ValueError(
                "time_window needs a StreamingGraph constructed with "
                "edge_ts")
        if isinstance(input_nodes, jax.Array):  # stay on device
            seeds = input_nodes.astype(jnp.int32)
        else:
            seeds = jnp.asarray(np.asarray(input_nodes), dtype=jnp.int32)
        B = seeds.shape[0]
        jk = ("stream", B, snap.epad, snap.delta_bucket, snap.has_ts,
              windowed)
        fn = self._jitted.get(jk)
        if fn is None:
            # quiverlint: ignore[QT014] -- B: same raw-batch-size
            # contract as the static path.  epad moves only at
            # compaction/fold (O(graph versions), not O(requests)) and
            # delta_bucket is _fanout_bucket-padded at snapshot build;
            # both ride the DeltaSnapshot NamedTuple, whose device-array
            # provenance the symbolic trace cannot follow.
            fn = self._jitted[jk] = self._build_stream_jit(B, windowed)
        if key is None:
            from .utils.rng import make_key

            key = make_key(np.random.randint(0, 2**31 - 1))
        if windowed:
            lo, hi = time_window
            # device scalars, not Python ints: traced operands, so a new
            # window is a new argument value — never a new executable
            window_lo = jnp.int32(lo)
            window_hi = jnp.int32(hi)
        else:
            window_lo = window_hi = None
        n_id, n_mask, num_nodes, blocks, drops, misses = fn(
            snap.indptr, snap.indices, snap.tomb, snap.d_indptr,
            snap.d_indices, snap.base_ts, snap.d_ts, seeds, key,
            window_lo, window_hi)
        self._keep_counts(drops, misses, blocks)
        return SampledBatch(
            n_id=n_id, n_id_mask=n_mask, num_nodes=num_nodes,
            batch_size=B, layers=blocks, drops=drops,
            version=snap.version, window_misses=misses,
        )

    def _keep_counts(self, drops, misses, blocks, live=None, batch_size=0):
        """The newest call's device-side counts, for ``overflow_stats()``,
        ``window_stats()`` and ``relation_stats()`` (the arrays and the
        blocks' target counts, not the batch: that would pin its
        frontier)."""
        self.last_drops = drops
        self._drops_recorded = False
        self._last_window = (misses, _hop_targets(blocks))
        self._window_recorded = False
        self._last_live = (live, batch_size)
        self._live_recorded = False

    def relation_stats(self, batch: Optional[SampledBatch] = None):
        """Per relation, its draw slots and the live draws in them over all
        hops: a list of ``{"relation", "slots", "live"}`` (``relation``:
        the schema's name, else the index; one relation, ``"0"``, on an
        untyped graph).  Slots come from the tree's shapes, so ``live /
        slots`` is the share of the relation blocks' slots that hold a
        draw.

        ``batch`` / no ``batch`` as :meth:`overflow_stats`; only the
        sampler-level form feeds ``sampler_relation_slots_total`` and
        ``sampler_relation_live_slots_total`` (label ``relation``), once per
        ``sample`` call.  None before any TPU-mode call.
        """
        if batch is not None:
            live, seeds = batch.relation_live, batch.batch_size
        else:
            live, seeds = getattr(self, "_last_live", (None, 0))
        if live is None:
            return None
        # the deliberate materialization point for the counts
        live = np.asarray(live).tolist()
        schema = self.schema or _UNTYPED
        slots = schema.slots(seeds, self.sizes)
        stats = [{"relation": schema.label(r), "slots": s, "live": n}
                 for r, (s, n) in enumerate(zip(slots, live))]
        if batch is None and not getattr(self, "_live_recorded", True):
            self._live_recorded = True
            for s in stats:
                telemetry.counter("sampler_relation_slots_total",
                                  relation=s["relation"]).inc(
                                      float(s["slots"]))
                telemetry.counter("sampler_relation_live_slots_total",
                                  relation=s["relation"]).inc(
                                      float(s["live"]))
        return stats

    def overflow_stats(self, batch: Optional[SampledBatch] = None):
        """[L] per-hop counts of frontier nodes dropped by ``frontier_caps``.

        Pass the :class:`SampledBatch` to get THAT batch's counts — the
        only reliable form when a loader samples ahead (``SeedLoader``
        dispatches batch i+1 before batch i is consumed, so the
        sampler-level "most recent call" is usually the next batch).
        Without ``batch``: the most recent ``sample`` call (None before
        any TPU-mode call; always zero without caps or ``dedup='none'``).
        """
        if batch is not None:
            # quiverlint: sync-ok[deliberate materialization point for drops]
            return None if batch.drops is None else np.asarray(batch.drops)
        if getattr(self, "last_drops", None) is None:
            return None
        # quiverlint: sync-ok[deliberate materialization point for drops]
        arr = np.asarray(self.last_drops)
        # count into the registry exactly once per sample() call (the
        # batch= form can't dedup across repeat queries, so only the
        # sampler-level path feeds the counter)
        if not getattr(self, "_drops_recorded", True):
            self._drops_recorded = True
            total = float(arr.sum())
            if total:
                telemetry.counter("sampler_frontier_drops_total",
                                  mode=self.mode.lower()).inc(total)
        return arr

    def window_stats(self, batch: Optional[SampledBatch] = None):
        """Per block, hop 1 first (a hop's blocks in relation order on a
        typed graph, one a hop otherwise), how the ``blocked`` path fetched
        the draws: a list of ``{"window", "fallback", "classic"}`` -
        targets served by their covering block, targets compacted into the
        per-draw fallback, and whether the WHOLE draw took the per-draw
        path (more misses than fallback slots, a hop with ``k <= U``, or
        the ``xla`` path; ``window`` and ``fallback`` are then 0).

        ``batch`` / no ``batch`` as :meth:`overflow_stats`; only the
        sampler-level form feeds the registry, once per ``sample`` call.
        None before any TPU-mode call.
        """
        if batch is not None:
            misses, hop_targets = (batch.window_misses,
                                   _hop_targets(batch.layers))
        else:
            misses, hop_targets = getattr(self, "_last_window", (None, ()))
        if misses is None:
            return None
        # the deliberate materialization point for the misses
        misses = np.asarray(misses)
        stats = []
        for miss, targets in zip(misses.tolist(), hop_targets):
            classic = miss == NO_WINDOW or miss > fallback_slots(targets)
            stats.append({"window": 0 if classic else targets - miss,
                          "fallback": 0 if classic else miss,
                          "classic": bool(classic)})
        if batch is None and not getattr(self, "_window_recorded", True):
            self._window_recorded = True
            mode = self.mode.lower()
            fallen = float(sum(s["fallback"] for s in stats))
            if fallen:
                telemetry.counter("sampler_window_fallback_targets_total",
                                  mode=mode).inc(fallen)
            wholesale = sum(m > 0 for m, s in zip(misses, stats)
                            if s["classic"])
            if wholesale:
                telemetry.counter("sampler_window_classic_hops_total",
                                  mode=mode).inc(float(wholesale))
        return stats

    def _sample_uva(self, input_nodes, key) -> SampledBatch:
        """Hot/cold big-graph sampling (``quiver_tpu.uva``): HBM-budgeted
        hot rows on device, cold rows on the native host sampler,
        overlapped per hop."""
        from .uva import UVAGraph, sample_uva

        if self._uva is None:
            self._uva = UVAGraph(self.csr_topo, self.uva_budget)
        if key is None:
            from .utils.rng import make_key

            key = make_key(np.random.randint(0, 2**31 - 1))
        gm = self.gather_mode
        n_id, n_mask, num, blocks = sample_uva(
            self._uva, self.sizes, input_nodes, key, gather_mode=gm,
            sample_rng=self.sample_rng,
            overlap=self.uva_overlap, timings=self.uva_timings,
        )
        return SampledBatch(
            n_id=jnp.asarray(n_id), n_id_mask=jnp.asarray(n_mask),
            num_nodes=jnp.asarray(num), batch_size=len(input_nodes),
            layers=tuple(
                LayerBlock(jnp.asarray(nl), jnp.asarray(m),
                           jnp.asarray(t))
                for nl, m, t in blocks
            ),
        )

    def set_fanout_frac(self, frac: float) -> None:
        """Scale the host-path fanout to ``frac`` of the configured
        ``sizes`` (each layer floored at 1 neighbor).  ``1.0`` restores
        full fanout.  Reversible brownout knob for the QoS ladder —
        device executables are untouched (their sizes are compile-time
        constants)."""
        self._fanout_frac = float(min(max(frac, 0.0), 1.0))

    def _effective_sizes(self):
        frac = self._fanout_frac
        if frac >= 1.0:
            return self.sizes
        return [max(1, int(s * frac)) for s in self.sizes]

    def _sample_cpu(self, input_nodes) -> SampledBatch:
        from .cpp import native

        if self._cpu is None:
            self._cpu = native.CPUSampler(
                self.csr_topo.indptr, self.csr_topo.indices,
                edge_weights=self._edge_weights,
            )
        seeds = np.asarray(input_nodes, dtype=np.int64)
        n_id, n_mask, num_nodes, blocks = self._cpu.sample_multihop(
            seeds, self._effective_sizes()
        )
        return SampledBatch(
            n_id=jnp.asarray(n_id), n_id_mask=jnp.asarray(n_mask),
            num_nodes=jnp.asarray(num_nodes), batch_size=len(seeds),
            layers=tuple(
                LayerBlock(jnp.asarray(nl), jnp.asarray(m), jnp.asarray(t))
                for nl, m, t in blocks
            ),
        )

    # -- sampling probability (parity: sample_prob,
    #    sage_sampler.py:149-157 + cal_next, cuda_random.cu.hpp:72-104) --
    def sample_prob(self, train_idx, total_node_count: int):
        from .ops.prob import sample_prob as _sp

        indptr, indices = self.csr_topo.to_device(self.device)
        return _sp(indptr, indices, jnp.asarray(np.asarray(train_idx)),
                   total_node_count, self.sizes,
                   num_edges=self.csr_topo.edge_count)

    # -- spawn/IPC parity: jax is single-controller, nothing to share; keep
    #    the API so reference code ports 1:1 (sage_sampler.py:159-178). --
    def share_ipc(self):
        return self.csr_topo, self.sizes, self.mode

    @classmethod
    def lazy_from_ipc_handle(cls, ipc_handle):
        csr_topo, sizes, mode = ipc_handle
        return cls(csr_topo, sizes, mode=mode)

    def __repr__(self):
        return (
            f"GraphSageSampler(sizes={self.sizes}, mode={self.mode!r}, "
            f"dedup={self.dedup!r}, gather={self.gather_mode!r}, "
            f"graph={self.csr_topo!r})"
        )
