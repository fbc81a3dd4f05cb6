"""Collective communication layer — TPU-native ``NcclComm``.

Reference parity: ``srcs/cpp/src/quiver/cuda/quiver_comm.cu:9-100`` (NCCL
wrapper) and ``srcs/python/quiver/comm.py`` (HostRankTable + the greedy
``schedule()`` host-pairing at comm.py:42-75).

TPU-first redesign: point-to-point send/recv and the contention-avoiding
pairing schedule disappear entirely — the exchange is expressed as
``jax.lax.all_to_all`` inside ``shard_map`` over a mesh axis, and XLA's
collective scheduler owns link contention (ICI within a slice, DCN across
hosts).  ``getNcclId``-style bootstrap is ``jax.distributed.initialize``.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["TpuComm", "getNcclId"]


def getNcclId():
    """Parity shim: jax needs no explicit communicator id."""
    return b"jax-single-controller"


class TpuComm:
    """Mesh-axis collectives with the reference NcclComm's surface.

    Args:
      mesh: ``jax.sharding.Mesh``.
      axis: mesh axis name over which ranks (reference: hosts) are laid out.
    """

    def __init__(self, mesh: Mesh, axis: str = "data",
                 rank: Optional[int] = None):
        self.mesh = mesh
        self.axis = axis
        self.n = int(mesh.shape[axis])
        self.rank = rank if rank is not None else 0

    # -- primitives ----------------------------------------------------
    def allreduce(self, x):
        """Sum over the axis; parity: ``NcclComm::allreduce``."""
        f = shard_map(
            lambda v: jax.lax.psum(v[0], self.axis),
            mesh=self.mesh,
            in_specs=P(self.axis),
            out_specs=P(),
        )
        return f(x)

    def all_to_all(self, x):
        """Per-rank matrix exchange: ``x`` is ``[n, ...]`` sharded on axis 0
        with each rank holding ``[n_local=..., chunk]`` destined rows; result
        transposes the (source, dest) layout.  Replaces phase-1/phase-2
        send/recv loops of ``comm.py:153-181``."""

        def body(v):  # v: [1, n, ...] local block (sharded leading axis)
            out = jax.lax.all_to_all(
                v[0], self.axis, split_axis=0, concat_axis=0, tiled=True
            )
            return out[None]

        f = shard_map(
            body, mesh=self.mesh,
            in_specs=P(self.axis), out_specs=P(self.axis),
        )
        return f(x)

    def exchange(self, *args, **kwargs):
        raise NotImplementedError(
            "use quiver_tpu.dist.DistFeature for the feature exchange"
        )
