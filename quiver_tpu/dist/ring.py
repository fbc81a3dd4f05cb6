"""Ring-structured sharded feature gather.

Complement to :class:`DistFeature`'s all-to-all exchange.  When every
device needs rows scattered across ALL shards (dense demand — large
batches, small shard count), rotating the shards around the ring and
picking up matches each step moves each shard exactly once over ICI
(all-gather bandwidth) instead of paying per-request all-to-all overhead —
the same reasoning behind ring attention's rotation of KV blocks, applied
to the feature dimension.  Demand-sparse workloads should stay on
DistFeature.

Mechanism per step (``shard_map`` body, ``jax.lax.ppermute`` rotation):
every device holds the wanted-ids list; as each foreign shard arrives it
resolves ``ids in [base, base+rows)`` locally and accumulates.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["RingFeature"]


class RingFeature:
    """Row-range-sharded feature with ring-rotation lookup.

    Rows are contiguously range-sharded: device d owns
    ``[d*rows_per, (d+1)*rows_per)`` (pad the feature to a multiple).
    """

    def __init__(self, feature: np.ndarray, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.n = int(mesh.shape[axis])
        n_rows, d = feature.shape
        self.rows_per = (n_rows + self.n - 1) // self.n
        pad = self.rows_per * self.n - n_rows
        if pad:
            feature = np.concatenate(
                [feature, np.zeros((pad, d), feature.dtype)]
            )
        self.node_count = n_rows
        self.dim = d
        sh = NamedSharding(mesh, P(axis, None))
        self.shards = jax.device_put(feature, sh)
        self._fn = {}

    def _build(self, B: int):
        n, axis, rows_per = self.n, self.axis, self.rows_per

        def body(shard, ids):
            # shard: [rows_per, D] local; ids: [1, B] this device's wants
            ids = ids[0]
            me = jax.lax.axis_index(axis)
            # derive from a varying value so the carry's manual-axes
            # annotation is stable across the fori_loop (shard_map VMA)
            out = jnp.zeros((B, shard.shape[1]), shard.dtype) + (
                shard[0, 0] * 0
            )

            def step(s, carry):
                block, out = carry
                # block currently holds the shard of device (me - s) % n
                owner = (me - s) % n
                base = owner * rows_per
                local = ids - base
                hit = (local >= 0) & (local < rows_per)
                rows = jnp.take(block, jnp.clip(local, 0, rows_per - 1),
                                axis=0)
                out = jnp.where(hit[:, None], rows, out)
                # rotate: send my current block to the next device
                block = jax.lax.ppermute(
                    block, axis,
                    [(i, (i + 1) % n) for i in range(n)],
                )
                return block, out

            block, out = jax.lax.fori_loop(0, n, step, (shard, out))
            return out[None]

        f = shard_map(
            body, mesh=self.mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=P(axis, None),
        )
        return jax.jit(f)

    def lookup(self, ids):
        """``ids``: [n_devices, B] per-device wanted rows -> [n, B, D]."""
        ids = jnp.asarray(ids, jnp.int32)
        nd, B = ids.shape
        assert nd == self.n
        if B not in self._fn:
            self._fn[B] = self._build(B)
        sh = NamedSharding(self.mesh, P(self.axis, None))
        return self._fn[B](self.shards, jax.device_put(ids, sh))
