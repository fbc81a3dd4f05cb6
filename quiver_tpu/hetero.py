"""Heterogeneous graph topology + sampler (R-GAT / mag240m-class workloads).

Reference parity: the reference's mag240m benchmark samples a heterogeneous
graph through PyG/DGL hetero loaders on top of quiver's feature store
(``/root/reference/benchmarks/ogbn-mag240m/``); quiver itself is
type-agnostic.  Here hetero sampling is first-class: one CSR per relation,
per-relation fanouts, and the same dedup-free positional frontier scheme as
the homogeneous TPU pipeline (``sampler.py``) — per node type.

A relation is ``(src_type, name, dst_type)`` and its CSR rows are DST
nodes with neighbor lists of SRC nodes (we sample sources for targets,
message flow src -> dst).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from flax import struct

from .ops.sample import sample_neighbors
from .utils.topology import CSRTopo

__all__ = ["HeteroCSRTopo", "HeteroGraphSageSampler", "HeteroLayerBlock",
           "HeteroSampledBatch", "HeteroFeature"]

Relation = Tuple[str, str, str]


@struct.dataclass
class HeteroLayerBlock:
    """One (relation, hop) bipartite block; ``relation`` is static pytree
    metadata so batches cross jit boundaries."""

    nbr_local: jax.Array   # [T, k] positions into the SRC type's frontier
    mask: jax.Array        # [T, k]
    num_targets: jax.Array  # valid targets (prefix of DST frontier)
    relation: Relation = struct.field(pytree_node=False)


@struct.dataclass
class HeteroSampledBatch:
    # per node type: padded frontier ids + validity
    n_id: Dict[str, jax.Array]
    n_id_mask: Dict[str, jax.Array]
    # layers[l] = list of HeteroLayerBlock for hop l, OUTERMOST first
    layers: Tuple[Tuple[HeteroLayerBlock, ...], ...]
    batch_size: int = struct.field(pytree_node=False)
    seed_type: str = struct.field(pytree_node=False)


class HeteroCSRTopo:
    """Dict of per-relation CSRs + per-type node counts."""

    def __init__(self, relations: Dict[Relation, CSRTopo],
                 node_counts: Dict[str, int]):
        self.relations = dict(relations)
        self.node_counts = dict(node_counts)
        for (s, _, d), topo in self.relations.items():
            assert s in self.node_counts and d in self.node_counts, (s, d)
            assert topo.node_count <= self.node_counts[d], (
                f"relation rows ({topo.node_count}) exceed {d} count"
            )

    @classmethod
    def from_edge_index_dict(cls, edge_index_dict: Dict[Relation, np.ndarray],
                             node_counts: Dict[str, int]):
        rels = {}
        for rel, ei in edge_index_dict.items():
            s, _, d = rel
            ei = np.asarray(ei)
            # rows = dst, neighbors = src
            rels[rel] = CSRTopo(edge_index=np.stack([ei[1], ei[0]]),
                                node_count=node_counts[d])
        return cls(rels, node_counts)

    def node_types(self) -> List[str]:
        return list(self.node_counts)

    def to_device(self, device=None):
        for topo in self.relations.values():
            topo.to_device(device)
        return self


class HeteroFeature:
    """Per-node-type feature stores with one batch-level lookup.

    Thin dict-of-:class:`quiver_tpu.Feature` with the ergonomics the
    hetero pipeline needs: ``hf.lookup(batch)`` returns the feature dict
    for every type's (padded) frontier, empty types included.
    """

    def __init__(self, features: Dict[str, "Feature"]):
        self.features = dict(features)

    @classmethod
    def from_cpu_tensors(cls, tensors: Dict[str, np.ndarray],
                         device_cache_size="1G", **kwargs):
        from .feature import Feature

        return cls({
            t: Feature(device_cache_size=device_cache_size,
                       **kwargs).from_cpu_tensor(x)
            for t, x in tensors.items()
        })

    def __getitem__(self, key):
        node_type, ids = key
        return self.features[node_type][ids]

    def lookup(self, batch: "HeteroSampledBatch") -> Dict[str, jax.Array]:
        out = {}
        for t, f in self.features.items():
            n_id = batch.n_id.get(t)
            if n_id is None or n_id.shape[0] == 0:
                out[t] = jnp.zeros((0, f.dim), jnp.float32)
            else:
                out[t] = f[np.asarray(n_id)]
        return out


class HeteroGraphSageSampler:
    """Multi-hop hetero sampler with per-relation fanouts.

    Args:
      topo: :class:`HeteroCSRTopo`.
      sizes: per-hop fanout dict ``{relation: k}`` or list of such dicts
        (one per hop); a plain int applies to every relation.
      seed_type: node type of the seeds (e.g. ``"paper"``).

    The frontier of each node type grows by appending sampled sources
    (positional relabel, no dedup) — each hop emits one block per relation
    whose DST type currently has a frontier.
    """

    def __init__(self, topo: HeteroCSRTopo, sizes, num_hops: int = None,
                 seed_type: str = "paper", device=None,
                 gather_mode: str = "auto", sample_rng: str = "auto"):
        self.topo = topo
        from .config import resolve_gather_mode, resolve_sample_rng

        self.gather_mode = resolve_gather_mode(gather_mode)
        self.sample_rng = resolve_sample_rng(sample_rng)
        if isinstance(sizes, (list, tuple)):
            self.hop_sizes = [self._norm(s) for s in sizes]
        else:
            assert num_hops is not None, "need num_hops with scalar sizes"
            self.hop_sizes = [self._norm(sizes)] * num_hops
        self.seed_type = seed_type
        self.device = device
        from .recovery.registry import program_cache

        self._jitted = program_cache("hetero", owner=self)
        topo.to_device(device)

    def _norm(self, s) -> Dict[Relation, int]:
        if isinstance(s, int):
            return {rel: s for rel in self.topo.relations}
        return dict(s)

    def _pipeline(self, seeds, key):
        nt = self.topo.node_types()
        frontiers = {
            t: (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool))
            for t in nt
        }
        frontiers[self.seed_type] = (
            seeds.astype(jnp.int32),
            jnp.ones((seeds.shape[0],), bool),
        )
        all_layers = []
        for hop, hop_size in enumerate(self.hop_sizes):
            blocks = []
            # snapshot: sample for the frontier as it stood at hop start
            snap = {t: frontiers[t] for t in nt}
            for rel, k in hop_size.items():
                s_t, _, d_t = rel
                dst_ids, dst_mask = snap[d_t]
                if dst_ids.shape[0] == 0:
                    continue
                indptr, indices = self.topo.relations[rel].to_device(
                    self.device
                )
                key, sub = jax.random.split(key)
                out = sample_neighbors(indptr, indices, dst_ids, k, sub,
                                       seed_mask=dst_mask,
                                       gather_mode=self.gather_mode,
                                       sample_rng=self.sample_rng)
                src_ids, src_mask = frontiers[s_t]
                base = src_ids.shape[0]
                t_len = dst_ids.shape[0]
                pos = (base
                       + jnp.arange(t_len, dtype=jnp.int32)[:, None] * k
                       + jnp.arange(k, dtype=jnp.int32)[None, :])
                blocks.append(HeteroLayerBlock(
                    relation=rel,
                    nbr_local=jnp.where(out.mask, pos, 0),
                    mask=out.mask,
                    num_targets=dst_mask.sum().astype(jnp.int32),
                ))
                frontiers[s_t] = (
                    jnp.concatenate(
                        [src_ids,
                         jnp.where(out.mask, out.nbrs, 0).reshape(-1)]
                    ),
                    jnp.concatenate([src_mask, out.mask.reshape(-1)]),
                )
            all_layers.append(tuple(blocks))
        n_id = {t: frontiers[t][0] for t in nt}
        n_mask = {t: frontiers[t][1] for t in nt}
        return n_id, n_mask, tuple(all_layers[::-1])

    def sample(self, input_nodes, key=None) -> HeteroSampledBatch:
        seeds = jnp.asarray(np.asarray(input_nodes), jnp.int32)
        B = seeds.shape[0]
        if B not in self._jitted:
            # jit the bound method directly — a fresh lambda here would
            # defeat jax's executable cache if this dict were ever reset
            # quiverlint: ignore[QT014] -- hetero keys on raw B by
            # design: seed counts come from the caller's loader, which
            # fixes the batch size; padding here would ripple through
            # every per-type frontier shape.  seal()/retrace_budget
            # guard the steady state.
            self._jitted[B] = jax.jit(self._pipeline)
        if key is None:
            from .utils.rng import make_key

            key = make_key(np.random.randint(0, 2**31 - 1))
        n_id, n_mask, layers = self._jitted[B](seeds, key)
        return HeteroSampledBatch(
            n_id=n_id, n_id_mask=n_mask, batch_size=B,
            seed_type=self.seed_type, layers=layers,
        )
