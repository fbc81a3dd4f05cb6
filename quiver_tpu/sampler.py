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

__all__ = ["GraphSageSampler", "SampledBatch", "LayerBlock", "POSITIONAL"]


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _Positional:
    """Type of :data:`POSITIONAL`: a pytree node with no leaves (and, as
    a field-less frozen dataclass, equal to and hashed like every other
    instance), so the marker is part of a block's tree *structure* and
    crosses ``jax.jit`` as the Python value it is, never as a tracer."""


POSITIONAL = _Positional()


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
    window_misses: Optional[jax.Array] = None  # [L] per-hop count of
    # targets whose CSR window did not fit the ``blocked`` path's block
    # (window_stats(batch) reads it; ops.blockgather.NO_WINDOW where the
    # hop has no window route)

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
        """
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


def _hop_targets(layers):
    """Targets of each hop, hop 1 first, from a batch's blocks."""
    return [blk.mask.shape[0] for blk in layers[::-1]]


def _window_misses(nfalls):
    """[L] int32 from each hop's ``SampleOut.nfall``."""
    return jnp.stack([jnp.int32(NO_WINDOW) if n is None else n
                      for n in nfalls])


def _sample_pipeline_nodedup(indptr, indices, seeds, key, sizes,
                             gather_mode="xla", cum_weights=None,
                             return_eid=False, sample_rng="auto"):
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
    """
    with jax.named_scope(SAMPLER):
        B = seeds.shape[0]
        frontier = seeds.astype(jnp.int32)
        fmask = jnp.ones((B,), dtype=bool)
        keys = jax.random.split(key, len(sizes))
    blocks = []
    nfalls = []
    for l, k in enumerate(sizes):
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
            t = frontier.shape[0]
            pos = (t + jnp.arange(t, dtype=jnp.int32)[:, None] * k
                   + jnp.arange(k, dtype=jnp.int32)[None, :])
            blocks.append(
                LayerBlock(
                    nbr_local=jnp.where(out.mask, pos, 0),
                    mask=out.mask,
                    num_targets=fmask.sum().astype(jnp.int32),
                    # None lets XLA DCE the eid computation entirely — an
                    # extra [T, k] int32 per hop is ~40% more sampler output
                    # HBM traffic, only worth it for edge-featured models
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
                 sample_rng="auto", overlay=None):
    """Dispatch to the dedup='none' or dedup='hop' traced pipeline — the
    single place that mapping lives (sampler jit + fused train/eval).

    ``overlay`` (a dict of delta-CSR arrays + window scalars, see
    ``GraphSageSampler._build_stream_jit``) routes to the streaming
    overlay pipeline; it rides the positional (``dedup='none'``)
    formulation only.
    """
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
                                        sample_rng=sample_rng)
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
    """

    def __init__(self, csr_topo: CSRTopo, sizes: Sequence[int], device=None,
                 mode: str = "TPU",
                 frontier_caps: Optional[Sequence[Optional[int]]] = None,
                 dedup: str = "auto", gather_mode: str = "auto",
                 edge_weights=None, return_eid: bool = False,
                 uva_budget: Union[int, str, None] = None,
                 sample_rng: str = "auto", uva_overlap: bool = True,
                 uva_timings: Optional[dict] = None):
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

        @jax.jit
        def fn(indptr, indices, cw, seeds, key):
            return run_pipeline(dedup, indptr, indices, seeds, key, sizes,
                                caps, gather_mode=gm, cum_weights=cw,
                                return_eid=ret_eid, sample_rng=srng)

        # the tables ride as ARGUMENTS: a device array captured by the
        # closure is baked into the executable as a constant — a second
        # copy of the graph in HBM per compiled batch size
        return functools.partial(fn, indptr, indices, cw)

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
        n_id, n_mask, num_nodes, blocks, drops, misses = fn(seeds, key)
        # [L] per-hop frontier-cap drop counts (always 0 without caps)
        # and window misses; kept on device until someone asks via
        # overflow_stats() / window_stats() — the counters are
        # incremented there, at materialization, so the hot loop never
        # pays a device sync for accounting
        self._keep_counts(drops, misses, blocks)
        return SampledBatch(
            n_id=n_id, n_id_mask=n_mask, num_nodes=num_nodes,
            batch_size=B, layers=blocks, drops=drops, window_misses=misses,
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

    def _keep_counts(self, drops, misses, blocks):
        """The newest call's device-side counts, for ``overflow_stats()``
        and ``window_stats()`` (the arrays and the hops' target counts,
        not the batch: that would pin its frontier)."""
        self.last_drops = drops
        self._drops_recorded = False
        self._last_window = (misses, _hop_targets(blocks))
        self._window_recorded = False

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
        """Per hop, how the ``blocked`` path fetched the draws:
        a list of ``{"window", "fallback", "classic"}`` - targets served
        by their covering block, targets compacted into the per-draw
        fallback, and whether the WHOLE hop took the per-draw path (more
        misses than fallback slots, a hop with ``k <= U``, or the ``xla``
        path; ``window`` and ``fallback`` are then 0).

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
