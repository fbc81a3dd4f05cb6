"""The fixed-capacity exchange that :class:`DistFeature` and
:class:`DistGraphSampler` share: requests bucketed by owner, one
``all_to_all`` to the owners, one back, answers unpacked into request
order.  Both halves run inside a ``shard_map`` body; everything they trace
sits under ``<layer>/qt.exchange`` (``telemetry.device_scopes.exchange``),
so that a trace tells what a layer costs because its table is sharded from
what it costs on one chip.

Ragged per-owner request counts become buckets of ``cap`` slots with
validity: ``cap`` = the number of requests is exact whatever the skew (a
request's rank in its bucket is below the number of requests); a smaller
``cap`` drops what overflows, and ``dropped`` counts it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.device_scopes import exchange as exchange_scope

__all__ = ["Routed", "route", "unroute", "put_row_blocks", "shard_len",
           "record_exchange", "TILE"]

# elements of the chip's tile of a 1-D int32 array (``T(1024)``): a shard
# whose length is no multiple of it is re-laid out, whole, by every program
# that views it as rows of 128 (7.8 ms a step for a 1.6 GB ``indices``
# shard: PERF.md section 5, PR 34)
TILE = 1024


class Routed(NamedTuple):
    """One rank's requests on their way out and in."""

    rids: jax.Array      # [n*cap] ids this rank was asked for (any where
    #                      ``rvalid`` is False), by source rank
    rvalid: jax.Array    # [n*cap] which received slots hold a request
    dest: jax.Array      # [F] slot of each request in the sent buffer
    ok: jax.Array        # [F] requests that were sent (valid, not dropped)
    dropped: jax.Array   # [] int32: valid requests over their bucket's cap
    live: jax.Array      # [] int32: slots of the sent buffer with a request


def route(layer: str, axis: str, n: int, cap: int, ids, owner, valid
          ) -> Routed:
    """Send each valid id to ``owner`` (``[F]`` int32 in ``[0, n)``)."""
    with exchange_scope(layer):
        owner = jnp.where(valid, owner, n)      # invalid -> nowhere
        onehot = owner[:, None] == jnp.arange(n)[None, :]
        rank_in = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.sum(jnp.where(onehot, rank_in, 0), axis=1)
        overflow = slot >= cap
        ok = valid & ~overflow
        dest = jnp.where(ok, owner * cap + slot, n * cap)
        # [n, cap] node ids, shifted by one: 0 is an empty slot
        reqs = jnp.zeros((n * cap,), jnp.int32).at[dest].add(
            (ids + 1).astype(jnp.int32), mode="drop").reshape(n, cap)
        recv = jax.lax.all_to_all(reqs, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        rids = recv.reshape(-1) - 1
        return Routed(rids, rids >= 0, dest, ok,
                      (valid & overflow).sum().astype(jnp.int32),
                      ok.sum().astype(jnp.int32))


def unroute(layer: str, axis: str, n: int, cap: int, payload, r: Routed):
    """Ship ``payload`` (``[n*cap, ...]``, the answer to each received
    slot) back and put the answers in request order: ``[F, ...]``, where
    ``r.ok`` is False whatever the slot it points at held."""
    with exchange_scope(layer):
        back = jax.lax.all_to_all(
            payload.reshape((n, cap) + payload.shape[1:]), axis,
            split_axis=0, concat_axis=0, tiled=True)
        flat = back.reshape((n * cap,) + payload.shape[1:])
        return jnp.take(flat, jnp.clip(r.dest, 0, n * cap - 1), axis=0)


def shard_len(need: int, room=None) -> int:
    """Length of every device's shard of a table whose largest range
    takes ``need`` rows (or edges): ``need`` rounded up to :data:`TILE`.
    ``room`` is a caller's own, larger length (a deployment that
    re-partitions graphs of nearly one size, or lets one grow, and wants
    their shards of ONE shape so that the programs are compiled once);
    less than ``need`` raises."""
    need = max(int(need), 1)
    if room is None:
        room = need
    elif int(room) < need:
        raise ValueError(f"a shard of {int(room):,} cannot hold the largest "
                         f"range's {need:,}")
    return -(-int(room) // TILE) * TILE


def record_exchange(owner, layer: str):
    """Materialise ``owner._last_exchange`` (slots shipped, a device array
    of live counts) and count it once, at query time: what the
    ``exchange_stats()`` of sampler and feature store return."""
    last = getattr(owner, "_last_exchange", None)
    if last is None:
        return None
    slots, live = int(last[0]), int(np.asarray(last[1]).sum())
    if not getattr(owner, "_exchange_recorded", False):
        owner._exchange_recorded = True
        from .. import telemetry

        telemetry.counter("dist_exchange_slots_total",
                          layer=layer).inc(float(slots))
        telemetry.counter("dist_exchange_live_slots_total",
                          layer=layer).inc(float(live))
    return slots, live


def put_row_blocks(mesh, axis: str, shape, block):
    """A ``[n, *shape]`` array over ``axis``, device ``p`` holding
    ``block(p)`` (a host array of that shape, ideally a view): each block
    goes to its device by itself, so the host never holds a stacked copy
    of a table that is there to be divided."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = int(mesh.shape[axis])
    sharding = NamedSharding(mesh, P(axis, *([None] * len(shape))))

    def one(idx):
        got = np.asarray(block(idx[0].start or 0))
        assert got.shape == tuple(shape), (got.shape, shape)
        return got[None]

    return jax.make_array_from_callback((n,) + tuple(shape), sharding, one)
