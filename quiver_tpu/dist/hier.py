"""Two-tier ICI x DCN feature store — the NVLink-clique x NCCL hierarchy.

Reference parity: the reference composes TWO remote-access tiers — the hot
set partitioned across a P2P clique and read over NVLink
(``feature.py:225-265`` + ``quiver_feature.cu:246-302``), and the cold
partition fetched from its owner host over NCCL (``feature.py:529-567`` +
``comm.py:127-182``).  ``HierFeature`` is the TPU equivalent over a hybrid
``[dcn, ici]`` mesh (:func:`quiver_tpu.dist.make_hybrid_mesh`):

  * **hot tier**: the top-``hot_count`` rows (degree/probability order),
    replicated per host group and SHARDED over the ICI axis — a hot lookup
    never leaves the host group; XLA's ici all_to_all plays NVLink.
  * **cold tier**: remaining rows partitioned by owner host (DCN axis) and
    sub-sharded over that host's chips (ICI axis).

One jitted ``shard_map`` body does the whole dance: route queries to their
owner host (DCN all_to_all) -> route to the owner chip within the host
(ICI all_to_all) -> local gather -> two reversed all_to_alls home.  Hot
queries are self-destined at the DCN stage, so they add ZERO cross-host
traffic — the property :meth:`traffic_stats` surfaces and
``tests/test_hier.py`` asserts against a flat mesh.

Everything is fixed-capacity buckets + validity masks (static shapes);
overflowed queries return zero rows and are COUNTED, never silent.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["HierFeature"]


def _bucket(owner, valid, n_dest, cap):
    """Slot each element into its destination's fixed bucket.

    Returns (flat dest index in [0, n_dest*cap] — n_dest*cap means
    dropped/invalid, overflow mask).
    """
    owner = jnp.where(valid, owner, n_dest)
    onehot = owner[:, None] == jnp.arange(n_dest)[None, :]
    rank_in = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.sum(jnp.where(onehot, rank_in, 0), axis=1)
    overflow = valid & (slot >= cap)
    dest = jnp.where(valid & ~overflow, owner * cap + slot, n_dest * cap)
    return dest, overflow


def _scatter_ids(ids, dest, n_slots):
    """Pack (id+1) into the bucket layout; 0 = empty slot."""
    return jnp.zeros((n_slots,), jnp.int32).at[dest].add(
        (ids + 1).astype(jnp.int32), mode="drop"
    )


class HierFeature:
    """Hierarchical (host-group x chip) sharded feature store.

    Args:
      mesh: 2-axis mesh, DCN major / ICI minor (``make_hybrid_mesh``).
      hot_count: rows [0, hot_count) are the hot tier (callers order rows
        by degree/probability first, as ``Feature.from_cpu_tensor`` does).
      global2host: ``[N]`` owner host per node (cold rows; hot entries
        ignored).  Defaults to contiguous range partition of the cold tail.
      dcn_cap / ici_cap: per-destination bucket capacities for the two
        exchange stages (defaults = exact worst case: nothing dropped).
    """

    def __init__(self, mesh: Mesh, hot_count: int, global2host=None,
                 dcn_axis: str = "dcn", ici_axis: str = "ici",
                 dcn_cap: Optional[int] = None,
                 ici_cap: Optional[int] = None):
        self.mesh = mesh
        self.dcn_axis, self.ici_axis = dcn_axis, ici_axis
        self.H = int(mesh.shape[dcn_axis])
        self.C = int(mesh.shape[ici_axis])
        self.hot_count = hot_count
        self.global2host = global2host
        self.dcn_cap, self.ici_cap = dcn_cap, ici_cap
        self._fn = {}

    @classmethod
    def from_global_feature(cls, feature: np.ndarray, mesh: Mesh,
                            hot_count: int, global2host=None, **kw):
        self = cls(mesh, hot_count, global2host, **kw)
        N, D = feature.shape
        H, C = self.H, self.C
        hot_count = min(hot_count, N)
        self.hot_count = hot_count = hot_count - hot_count % C  # C-divisible
        self.node_count, self.dim = N, D

        # hot tier: [hot_count, D], sharded over ici, replicated over dcn
        hot = np.ascontiguousarray(feature[:hot_count])
        self.hot_shard = hot_count // C if C else 0
        if hot_count:
            self.hot = jax.device_put(
                hot, NamedSharding(mesh, P(self.ici_axis, None))
            )
        else:
            self.hot = jax.device_put(
                np.zeros((C, D), feature.dtype),
                NamedSharding(mesh, P(self.ici_axis, None)),
            )
            self.hot_shard = 1

        # cold tier: owner host per node, local slots, chip sub-shards
        n_cold = N - hot_count
        if global2host is None:
            # contiguous range partition of the cold tail
            g2h = np.minimum(
                (np.arange(N, dtype=np.int64) - hot_count)
                // max(1, -(-n_cold // H)), H - 1
            ).astype(np.int32)
            g2h[:hot_count] = 0
        else:
            g2h = np.asarray(global2host, dtype=np.int32).copy()
        self._g2h_np = g2h
        g2l = np.zeros(N, dtype=np.int32)
        counts = np.zeros(H, dtype=np.int64)
        cold_ids = np.arange(hot_count, N)
        for h in range(H):
            ids = cold_ids[g2h[cold_ids] == h]
            g2l[ids] = np.arange(len(ids), dtype=np.int32)
            counts[h] = len(ids)
        m = int(counts.max()) if n_cold else 1
        self.m_c = m_c = -(-m // C)  # per-chip cold rows
        m = m_c * C
        cold = np.zeros((H * m, D), dtype=feature.dtype)
        for h in range(H):
            ids = cold_ids[g2h[cold_ids] == h]
            cold[h * m + g2l[ids]] = feature[ids]
        self.cold = jax.device_put(
            cold, NamedSharding(mesh, P((self.dcn_axis, self.ici_axis),
                                        None)),
        )
        self.g2h = jnp.asarray(g2h)
        self.g2l = jnp.asarray(g2l)
        return self

    # ------------------------------------------------------------------
    def _build(self, B: int, dcap: int, icap: int):
        H, C = self.H, self.C
        dax, iax = self.dcn_axis, self.ici_axis
        hot_count, hot_shard, m_c = self.hot_count, self.hot_shard, self.m_c
        g2h, g2l = self.g2h, self.g2l

        def body(hot, cold, ids, valid):
            # hot: [hot_shard, D] (this chip's ici shard, same per host)
            # cold: [m_c, D] (this chip's slice of this host's partition)
            # ids/valid: [1, 1, B] — this chip's query batch
            ids, valid = ids[0, 0], valid[0, 0]
            me_h = jax.lax.axis_index(dax)
            is_hot = ids < hot_count
            dest_h = jnp.where(is_hot, me_h, g2h[ids])
            # ---- stage 1: route queries to their owner host over DCN
            d1, ovf1 = _bucket(dest_h, valid, H, dcap)
            reqs1 = _scatter_ids(ids, d1, H * dcap).reshape(H, dcap)
            recv1 = jax.lax.all_to_all(reqs1, dax, split_axis=0,
                                       concat_axis=0, tiled=True)
            r1 = recv1.reshape(-1) - 1          # [H*dcap] ids (-1 empty)
            v1 = r1 >= 0
            r1s = jnp.where(v1, r1, 0)
            # ---- stage 2: route to the owner chip within the host
            r1_hot = r1s < hot_count
            dest_c = jnp.where(r1_hot, r1s // jnp.int32(hot_shard),
                               g2l[r1s] // jnp.int32(m_c))
            d2, ovf2 = _bucket(dest_c, v1, C, icap)
            reqs2 = _scatter_ids(r1s, d2, C * icap).reshape(C, icap)
            recv2 = jax.lax.all_to_all(reqs2, iax, split_axis=0,
                                       concat_axis=0, tiled=True)
            r2 = recv2.reshape(-1) - 1          # [C*icap]
            v2 = r2 >= 0
            r2s = jnp.where(v2, r2, 0)
            # ---- local gather (hot slice or cold slice of this chip)
            hslot = r2s % jnp.int32(hot_shard)
            cslot = g2l[r2s] % jnp.int32(m_c)
            rows = jnp.where(
                (r2s < hot_count)[:, None],
                jnp.take(hot, hslot, axis=0),
                jnp.take(cold, cslot, axis=0),
            )
            rows = jnp.where(v2[:, None], rows, 0)
            # ---- reverse stage 2 (ICI) back to the in-host requester slot
            back2 = jax.lax.all_to_all(rows.reshape(C, icap, -1), iax,
                                       split_axis=0, concat_axis=0,
                                       tiled=True)
            flat2 = jnp.concatenate(
                [back2.reshape(C * icap, -1),
                 jnp.zeros((1, back2.shape[-1]), back2.dtype)]
            )
            rows1 = jnp.take(flat2, jnp.clip(d2, 0, C * icap), axis=0)
            rows1 = jnp.where(v1[:, None], rows1, 0)
            # ---- reverse stage 1 (DCN) home to the querying chip
            back1 = jax.lax.all_to_all(rows1.reshape(H, dcap, -1), dax,
                                       split_axis=0, concat_axis=0,
                                       tiled=True)
            flat1 = jnp.concatenate(
                [back1.reshape(H * dcap, -1),
                 jnp.zeros((1, back1.shape[-1]), back1.dtype)]
            )
            out = jnp.take(flat1, jnp.clip(d1, 0, H * dcap), axis=0)
            out = jnp.where((valid & ~ovf1)[:, None], out, 0)
            # ---- stats: cross-DCN query count + overflow drops
            dcn_cross = (valid & (dest_h != me_h)).sum().astype(jnp.int32)
            drops = (ovf1.sum() + (v1 & ovf2).sum()).astype(jnp.int32)
            return (out[None, None], dcn_cross[None, None],
                    drops[None, None])

        f = shard_map(
            body, mesh=self.mesh,
            in_specs=(P(self.ici_axis, None),
                      P((self.dcn_axis, self.ici_axis), None),
                      P(self.dcn_axis, self.ici_axis, None),
                      P(self.dcn_axis, self.ici_axis, None)),
            out_specs=(P(self.dcn_axis, self.ici_axis, None, None),
                       P(self.dcn_axis, self.ici_axis),
                       P(self.dcn_axis, self.ici_axis)),
        )
        return jax.jit(f)

    def lookup(self, ids, valid=None):
        """``ids``: [H, C, B] (one query batch per chip).  Returns
        [H, C, B, D]; :meth:`traffic_stats` afterwards for DCN counts."""
        ids = jnp.asarray(ids, jnp.int32)
        H, C, B = ids.shape
        assert (H, C) == (self.H, self.C), (ids.shape, self.H, self.C)
        if valid is None:
            valid = jnp.ones((H, C, B), bool)
        dcap = self.dcn_cap or B            # exact: one host can own all B
        icap = self.ici_cap or H * dcap     # exact: one chip can own all
        key = (B, dcap, icap)
        if key not in self._fn:
            self._fn[key] = self._build(B, dcap, icap)
        spec = NamedSharding(self.mesh, P(self.dcn_axis, self.ici_axis,
                                          None))
        ids = jax.device_put(ids, spec)
        valid = jax.device_put(valid, spec)
        out, cross, drops = self._fn[key](self.hot, self.cold, ids, valid)
        self.last_dcn_cross = cross
        self.last_drops = drops
        return out

    def traffic_stats(self):
        """Per-chip [H, C] counts from the last lookup: queries that
        crossed DCN, and bucket-overflow drops (0 at default caps)."""
        if getattr(self, "last_dcn_cross", None) is None:
            return None
        return dict(
            dcn_crossings=np.asarray(self.last_dcn_cross),
            drops=np.asarray(self.last_drops),
            dcn_bytes_est=int(
                np.asarray(self.last_dcn_cross).sum()
                * self.dim * np.dtype(np.float32).itemsize
            ),
        )
