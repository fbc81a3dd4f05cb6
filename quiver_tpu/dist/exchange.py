"""The exchange that :class:`DistFeature` and :class:`DistGraphSampler`
share: requests bucketed by owner, an ``all_to_all`` to the owners, the
owner's answers, an ``all_to_all`` back, answers unpacked into request
order.  :func:`exchange` runs inside a ``shard_map`` body; everything it
traces itself sits under ``<layer>/qt.exchange``
(``telemetry.device_scopes.exchange``), so that a trace tells what a layer
costs because its table is sharded from what it costs on one chip.

Ragged per-owner request counts become buckets of a static length with
validity.  A caller's own ``cap`` is ONE round of buckets that long: what
overflows a bucket is dropped, and ``dropped`` counts it.  No ``cap`` is
the exact exchange: a bucket sized for an owner's share of the frontier
(:func:`bucket_len`), shipped in as many rounds as the fullest bucket of
any rank asks for.  A request's rank in its owner's bucket is below that
owner's count, so round ``rank // bucket`` ships it whatever the skew:
a balanced frontier takes one round, every id on one owner ``n``, and
nothing is dropped.  (One round of buckets as long as the whole frontier
is exact too, and has every owner serve ``n`` frontiers' slots for one:
twice the step of the four-chip cell, PERF.md section 6, PR 35.)

A round's answers are unpacked into request order by one gather out of
what came back, one row per request slot.  A slot that sent nothing in
that round (an invalid id, one dropped, one shipped in another round)
reads a row of its own, as the one-chip gather's dead slots do
(``feature._dead_rows``), and the ``where`` after it keeps zeros (or an
earlier round's answer) there: sent to one row, the 56.5% of the four-chip
cell's slots that are dead made the unpacking the dearest operation in
the lookup's loop (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..feature import _dead_rows
from ..telemetry.device_scopes import exchange as exchange_scope

__all__ = ["Counts", "exchange", "bucket_len", "put_row_blocks", "shard_len",
           "record_exchange", "TILE", "LANES"]

# elements of the chip's tile of a 1-D int32 array (``T(1024)``): a shard
# whose length is no multiple of it is re-laid out, whole, by every program
# that views it as rows of 128 (7.8 ms a step for a 1.6 GB ``indices``
# shard: PERF.md section 5, PR 34)
TILE = 1024
# a request bucket is a multiple of the chip's 128 lanes long
LANES = 128
# room in an exact bucket over an owner's even share ``F / n``, in standard
# deviations of a balanced frontier's per-owner count (under ``sqrt(F /
# n)``): a frontier that is ALL live and evenly spread (hop 1's seeds: 256
# +- 14 of 1,024 to each of four owners) then goes in one round, not two,
# at 1.5% more slots on a million-slot frontier (PERF.md section 6, PR 35)
_SLACK_SIGMAS = 8


def bucket_len(F: int, n: int, cap=None) -> int:
    """Slots of a request bucket for a frontier of ``F`` over ``n`` owners.
    A caller's own ``cap`` is that.  The exact exchange's (``cap`` None):
    an owner's even share and :data:`_SLACK_SIGMAS` of room, rounded up to
    :data:`LANES`; the whole frontier where that is no shorter (a small
    frontier, ``n == 1``), which is always one round."""
    if cap is not None:
        return cap
    share = F / n
    room = -(-math.ceil(share + _SLACK_SIGMAS * math.sqrt(share))
             // LANES) * LANES
    return min(room, F)


class Counts(NamedTuple):
    """What one rank's exchange counted, int32 scalars."""

    dropped: jax.Array   # valid requests over their bucket's cap
    live: jax.Array      # requests sent: slots of the sent buffers in use
    rounds: jax.Array    # rounds shipped, the same on every rank


def exchange(layer: str, axis: str, n: int, cap, ids, owner, valid, serve,
             row):
    """Ask ``owner`` (``[F]`` int32 in ``[0, n)``) for each valid id and
    return ``(answers [F, *row.shape], Counts)``: zeros where no request
    was sent (an invalid slot, one dropped).

    ``serve(rids, rvalid, r)`` is the owner's side of round ``r``: the ids
    this rank was asked for (``[n * bucket]``, by source rank; any where
    ``rvalid`` is False) -> their answers ``[n * bucket, *row.shape]`` of
    ``row.dtype`` (``row``: a ``jax.ShapeDtypeStruct`` of one answer).
    ``cap``: a bucket's slots for ONE round that drops what overflows, or
    None for the exact exchange in rounds (module docstring).  Every rank
    runs the same number of rounds, so the same collectives."""
    F = ids.shape[0]
    bucket = bucket_len(F, n, cap)
    # a bucket as long as the frontier cannot overflow: one round, stated
    looped = cap is None and bucket < F
    with exchange_scope(layer):
        owner = jnp.where(valid, owner, n)      # invalid -> nowhere
        onehot = owner[:, None] == jnp.arange(n)[None, :]
        rank_in = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.sum(jnp.where(onehot, rank_in, 0), axis=1)
        if looped:
            # the fullest bucket's count is its last request's rank + 1
            most = jnp.max(jnp.where(valid, slot + 1, 0))
            rounds = jax.lax.pmax(-(-most // bucket), axis).astype(jnp.int32)
            ok = valid
        else:
            rounds = jnp.int32(1)
            ok = valid & (slot < bucket)

    def one_round(r, answers):
        with exchange_scope(layer):
            at = slot - r * bucket
            mine = ok & (at >= 0) & (at < bucket)
            dest = jnp.where(mine, owner * bucket + at, n * bucket)
            # [n, bucket] node ids, shifted by one: 0 is an empty slot
            reqs = jnp.zeros((n * bucket,), jnp.int32).at[dest].add(
                (ids + 1).astype(jnp.int32), mode="drop").reshape(n, bucket)
            recv = jax.lax.all_to_all(reqs, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            rids = recv.reshape(-1) - 1
            rvalid = rids >= 0
        payload = serve(rids, rvalid, r)
        with exchange_scope(layer):
            back = jax.lax.all_to_all(
                payload.reshape((n, bucket) + row.shape), axis,
                split_axis=0, concat_axis=0, tiled=True)
            got = back.reshape((n * bucket,) + row.shape).at[jnp.where(
                mine, dest, _dead_rows(F, n * bucket, dest.dtype))].get(
                mode="promise_in_bounds")
            return jnp.where(mine.reshape((F,) + (1,) * len(row.shape)),
                             got, answers)

    with exchange_scope(layer):
        answers = jnp.zeros((F,) + row.shape, row.dtype)
    if looped:
        with jax.named_scope(layer):
            answers = jax.lax.fori_loop(
                0, rounds, one_round,
                jax.lax.pcast(answers, axis, to="varying"))
    else:
        answers = one_round(0, answers)
    with exchange_scope(layer):
        return answers, Counts((valid & ~ok).sum().astype(jnp.int32),
                               ok.sum().astype(jnp.int32), rounds)


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
    """Materialise ``owner._last_exchange`` (the slots one round ships per
    rank and the frontier's slots, by hop where there are hops; the device
    arrays of rounds and of live counts) and count it once, at query time:
    what the ``exchange_stats()`` of sampler and feature store return.
    Slots are what was SHIPPED, rounds x ranks x bucket summed over the
    ranks (and hops); a round is counted once, not once a rank.  Unpacked
    slots are the request slots every round unpacks, frontier x rounds
    summed over the ranks: ``1 - live / unpacked`` is the share of them
    that read a dead row."""
    last = getattr(owner, "_last_exchange", None)
    if last is None:
        return None
    rounds = np.asarray(last[1]).astype(np.int64)
    slots = int((rounds * np.asarray(last[0], np.int64)).sum())
    live = int(np.asarray(last[2]).sum())
    unpacked = int((rounds * np.asarray(last[3], np.int64)).sum())
    if not getattr(owner, "_exchange_recorded", False):
        owner._exchange_recorded = True
        from .. import telemetry

        telemetry.counter("dist_exchange_slots_total",
                          layer=layer).inc(float(slots))
        telemetry.counter("dist_exchange_live_slots_total",
                          layer=layer).inc(float(live))
        telemetry.counter("dist_exchange_unpacked_slots_total",
                          layer=layer).inc(float(unpacked))
        telemetry.counter("dist_exchange_rounds_total",
                          layer=layer).inc(float(rounds[0].sum()))
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
