"""Neighbor sampling ops — pure-XLA dense formulation.

Reference parity: the warp-per-row reservoir kernel
``srcs/cpp/include/quiver/cuda_random.cu.hpp:8-69`` and the 2-tensor
``sample_neighbor`` contract of ``quiver_sample.cu:113-191``.

TPU-first redesign: instead of ragged (flat neighbors + per-seed counts +
prefix sums), every op returns **dense ``[B, k]`` neighbor blocks with a
validity mask**.  Static shapes let XLA fuse the whole hop into a couple of
gathers; the mask replaces the CUDA prefix-sum/compaction step.  Downstream
(models, gather) consume the dense form natively; a ragged view is available
via :func:`to_ragged` for API parity.

Without-replacement sampling: the CUDA kernel does reservoir sampling.  On
TPU we use **stratified positions** — neighbor slot ``j`` draws uniformly
from window ``[floor(j*deg/k), floor((j+1)*deg/k))``.  For ``deg > k`` the
windows are disjoint and non-empty, so the k draws are distinct.  Marginals:
an element's inclusion probability is ``1/|window|`` with window sizes
``floor(deg/k)`` or ``ceil(deg/k)`` — exactly ``k/deg`` when ``k | deg``,
within a ``±k/deg`` relative factor otherwise (vs exact-uniform reservoir);
CSR neighbor order is arbitrary, so the tiny position-correlated bias has
no graph-semantic alignment.  No hash table, no atomics, no sequential
loop.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["sample_neighbors", "sample_neighbors_overlay", "SampleOut",
           "to_ragged"]


class SampleOut(NamedTuple):
    """Dense one-hop sample: ``nbrs[b, j]`` valid where ``mask[b, j]``."""

    nbrs: jax.Array   # [B, k] int32 global neighbor ids (garbage where ~mask)
    mask: jax.Array   # [B, k] bool
    counts: jax.Array  # [B] int32 = min(degree, k), 0 for invalid seeds
    eid: Optional[jax.Array] = None  # [B, k] int32 global edge positions
    # scalar int32, ``blocked`` only: targets whose window did not fit
    # (``ops.blockgather.blocked_window_gather``)
    nfall: Optional[jax.Array] = None


# counter-hash constants
HASH_PHI = 0x9E3779B9    # Weyl increment (golden-ratio word)
HASH_MUL1 = 0x85EBCA6B   # murmur3 finalizer multipliers
HASH_MUL2 = 0xC2B2AE35


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer: full avalanche (every input bit flips
    every output bit with ~1/2 probability)."""
    x = (x ^ (x >> 16)) * jnp.uint32(HASH_MUL1)
    x = (x ^ (x >> 13)) * jnp.uint32(HASH_MUL2)
    return x ^ (x >> 16)


def _fold_key_words(key: jax.Array):
    """Fold arbitrary-width PRNG key data into two 32-bit words via a
    POSITION-SENSITIVE multiplicative chain (a plain XOR fold would
    collapse word permutations of 4-word keys — rbg impls — onto one
    stream); threefry's two words enter order-distinguished too."""
    data = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    k0 = jnp.uint32(0)
    k1 = jnp.uint32(HASH_PHI)
    for i, w in enumerate(data):
        k0 = (k0 ^ w) * jnp.uint32(HASH_MUL1) + jnp.uint32(i + 1)
        k1 = ((k1 + w) * jnp.uint32(HASH_MUL2)) ^ jnp.uint32(
            ((i + 1) * HASH_PHI) & 0xFFFFFFFF)
    return k0, k1


def _hash_uniform(key: jax.Array, shape) -> jax.Array:
    """Counter-based uniforms from a keyed integer hash — compiles to
    ~15 elementwise VPU ops, no RNG algorithm HLO at all.

    Escape hatch for backends where even the hardware-RNG lowering is
    slow to compile (``sample_rng="hash"``); statistical quality is ample
    for neighbor subsampling (the reference's curand Philox is likewise a
    counter hash, just with more rounds — ``cuda_random.cu.hpp:12-20``).

    Keying: the FULL key (both 32-bit words of a threefry key; folded
    words of wider impls) is injected between full-avalanche finalizer
    rounds, never as an additive counter offset — so two distinct keys
    produce structurally unrelated streams.  (The round-2 scheme offset
    ONE shared 2^32 counter stream by a 32-bit fold of the key; keys
    whose offsets landed near each other replayed identical uniform
    segments at shifted positions.  Cross-key tests:
    ``tests/test_sample.py::TestHashUniformCrossKey``.)
    """
    k0, k1 = _fold_key_words(key)
    n = 1
    for s in shape:
        n *= s
    # Weyl-spread counter, then key words between avalanche rounds
    x = jax.lax.iota(jnp.uint32, n).reshape(shape) * jnp.uint32(HASH_PHI)
    x = _fmix32(x ^ k0)
    x = _fmix32(x ^ k1)
    # 24-bit mantissa -> [0, 1)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _uniform(key, shape, impl: str):
    if impl == "hash":
        return _hash_uniform(key, shape)
    return jax.random.uniform(key, shape, dtype=jnp.float32)


def _stratified_positions(u: jax.Array, deg: jax.Array, k: int) -> jax.Array:
    """In-window draw positions ``[B, k]`` from uniforms ``u`` — neighbor
    slot ``j`` draws from stratum ``[floor(j*deg/k), floor((j+1)*deg/k))``
    (distinct windows for ``deg > k``, identity for ``deg <= k``)."""
    j = jnp.arange(k, dtype=jnp.int32)[None, :]              # [1, k]
    degf = deg.astype(jnp.float32)[:, None]                  # [B, 1]
    # Stratum bounds computed in float to avoid an int64 multiply;
    # deg < 2^24 holds for any real graph's max degree.
    lo = jnp.floor(j.astype(jnp.float32) * degf / k)
    hi = jnp.floor((j + 1).astype(jnp.float32) * degf / k)
    strat = lo + jnp.floor(u * jnp.maximum(hi - lo, 1.0))
    pos = jnp.where(deg[:, None] <= k, j, strat.astype(jnp.int32))
    return jnp.minimum(pos.astype(jnp.int32),
                       jnp.maximum(deg[:, None] - 1, 0))


def _gather(table: jax.Array, idx: jax.Array, mode: str) -> jax.Array:
    """Element gather by the name ``config.resolve_gather_mode`` hands
    out: ``"xla"`` = ``jnp.take`` (clipped); ``"blocked"`` = row gather +
    lane select (``ops.fastgather``), which sidesteps XLA's serialized
    1-D scalar gather on TPU and needs the table padded to a multiple of
    128 (``CSRTopo.to_device`` guarantees it).  Under ``"blocked"`` only
    the k draws of a target share a window (``ops.blockgather``); the
    scattered [B] reads that come here (``indptr``: two adjacent entries
    per target, themselves a window of two: ROADMAP S3) go per element."""
    if mode == "xla":
        return jnp.take(table, idx, mode="clip")
    if mode != "blocked":
        raise ValueError(
            f"gather_mode must be xla | blocked at the op "
            f"(config.resolve_gather_mode maps auto), got {mode!r}")
    from .fastgather import element_gather

    assert table.shape[0] % 128 == 0, (
        f"blocked gather needs a 128-multiple table, got "
        f"{table.shape[0]} — pad with ops.fastgather.pad_table_128 "
        f"(CSRTopo.to_device / the samplers do this for you)"
    )
    m = table.shape[0]
    return element_gather(table[:m].reshape(-1, 128),
                          jnp.clip(idx, 0, m - 1))


@functools.partial(jax.jit, static_argnames=("k", "gather_mode",
                                             "sample_rng"))
def sample_neighbors(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    seed_mask: Optional[jax.Array] = None,
    gather_mode: str = "xla",
    sample_rng: str = "auto",
) -> SampleOut:
    """Sample up to ``k`` distinct neighbors per seed from a CSR graph.

    Args:
      indptr: ``[N+1]`` int32 CSR row pointers (device-resident).
      indices: ``[E]`` int32 CSR column indices.
      seeds: ``[B]`` int32 node ids.  Entries where ``seed_mask`` is False
        are treated as degree-0 (used for padded frontiers).
      k: fanout (static).
      key: PRNG key.
      seed_mask: optional ``[B]`` bool validity of seeds.

    Behavioral contract (vs ``cuda_random.cu.hpp:8-69``):
      * ``deg <= k``: all neighbors returned, in CSR order.
      * ``deg > k``: k distinct neighbors, inclusion probability k/deg each.
    """
    seeds = seeds.astype(jnp.int32)
    B = seeds.shape[0]
    start = _gather(indptr, seeds, gather_mode)
    end = _gather(indptr, seeds + 1, gather_mode)
    deg = end - start
    if seed_mask is not None:
        deg = jnp.where(seed_mask, deg, 0)
    counts = jnp.minimum(deg, k).astype(jnp.int32)

    j = jnp.arange(k, dtype=jnp.int32)[None, :]              # [1, k]
    u = _uniform(key, (B, k), sample_rng)
    pos = _stratified_positions(u, deg, k)

    mask = j < counts[:, None]
    idx = start[:, None] + pos
    nfall = None
    if gather_mode == "blocked":
        from .blockgather import blocked_window_gather

        assert indices.shape[0] % 128 == 0, (
            f"blocked gather needs a 128-multiple indices table, got "
            f"{indices.shape[0]} — pad with ops.fastgather.pad_table_128"
        )
        nbrs, nfall = blocked_window_gather(
            indices.reshape(-1, 128), start, deg, pos)
    else:
        nbrs = _gather(indices, idx, gather_mode)
    nbrs = jnp.where(mask, nbrs, jnp.int32(-1))
    # global edge positions of the draws: index into CSRTopo.eid / edge-
    # feature arrays.  The reference's CSR carries edge ids for the same
    # purpose (quiver.cu.hpp eid); PyG's Adj e_id slot can be filled from
    # this instead of the reference's empty tensor (sage_sampler.py:143).
    eid = jnp.where(mask, idx, jnp.int32(-1))
    return SampleOut(nbrs=nbrs, mask=mask, counts=counts, eid=eid,
                     nfall=nfall)


@functools.partial(jax.jit, static_argnames=("k", "gather_mode",
                                             "sample_rng", "windowed"))
def sample_neighbors_overlay(
    indptr: jax.Array,
    indices: jax.Array,
    tomb: jax.Array,
    d_indptr: jax.Array,
    d_indices: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    seed_mask: Optional[jax.Array] = None,
    base_ts: Optional[jax.Array] = None,
    d_ts: Optional[jax.Array] = None,
    window_lo: Optional[jax.Array] = None,
    window_hi: Optional[jax.Array] = None,
    gather_mode: str = "xla",
    sample_rng: str = "auto",
    windowed: bool = False,
) -> SampleOut:
    """One-hop sampling over a base CSR **plus a delta-CSR overlay**.

    The streaming tier (``quiver_tpu.stream``) layers pending edge
    insertions (an append-only segment re-CSR'd per snapshot) and
    deletions (a tombstone table over base edge positions) on the frozen
    CSR.  This op draws from the **combined** neighborhood: a seed's
    degree is ``base_deg + delta_deg`` and the stratified positions index
    the virtual concatenation ``[base neighbors | delta neighbors]`` —
    identical position math to :func:`sample_neighbors`, so with zero
    deltas and no tombstones the outputs are bitwise identical to the
    frozen path (the equivalence contract ``tests/test_stream.py``
    enforces).

    Deletion/window semantics are **rejection, not resampling**: a draw
    landing on a tombstoned base edge (``tomb[pos] != 0``) or outside the
    half-open timestamp window ``[window_lo, window_hi)`` is masked out,
    so rows with many pending deletes can return fewer than
    ``min(deg, k)`` neighbors until the compactor folds the deltas in.
    That keeps the op one fused pass (no data-dependent second draw — a
    retrace/perf hazard); the compactor restores exact fanout.

    Args (beyond :func:`sample_neighbors`):
      tomb: ``[E_pad]`` int32, nonzero = base edge position deleted.
      d_indptr / d_indices: delta CSR over the same node-id space;
        ``d_indices`` is padded to the snapshot's pow2 fanout bucket so
        executable keys stay additive (coldcache discipline).
      base_ts / d_ts: optional ``[E_pad]`` int32 per-edge timestamps
        (required when ``windowed``).
      window_lo / window_hi: traced int32 scalars — changing the window
        does NOT retrace; only ``windowed`` (filter on/off) is static.

    Delta draws report ``eid = indices.shape[0] + delta_pos`` so edge ids
    stay unambiguous across the two segments.
    """
    seeds = seeds.astype(jnp.int32)
    B = seeds.shape[0]
    start = _gather(indptr, seeds, gather_mode)
    end = _gather(indptr, seeds + 1, gather_mode)
    bdeg = end - start
    dstart = _gather(d_indptr, seeds, gather_mode)
    dend = _gather(d_indptr, seeds + 1, gather_mode)
    ddeg = dend - dstart
    if seed_mask is not None:
        bdeg = jnp.where(seed_mask, bdeg, 0)
        ddeg = jnp.where(seed_mask, ddeg, 0)
    deg = bdeg + ddeg

    j = jnp.arange(k, dtype=jnp.int32)[None, :]              # [1, k]
    u = _uniform(key, (B, k), sample_rng)
    pos = _stratified_positions(u, deg, k)

    # position < base_deg draws from the base segment, the rest from the
    # delta segment (both index expressions clipped so the untaken side
    # of the select still gathers in-bounds)
    in_base = pos < bdeg[:, None]
    bidx = start[:, None] + jnp.minimum(
        pos, jnp.maximum(bdeg[:, None] - 1, 0))
    dpos = jnp.maximum(pos - bdeg[:, None], 0)
    didx = dstart[:, None] + dpos
    nbrs = jnp.where(
        in_base,
        _gather(indices, bidx, gather_mode),
        _gather(d_indices, didx, gather_mode),
    )
    live = jnp.where(
        in_base, _gather(tomb, bidx, gather_mode) == 0, True)
    if windowed:
        ets = jnp.where(
            in_base,
            _gather(base_ts, bidx, gather_mode),
            _gather(d_ts, didx, gather_mode),
        )
        live = live & (ets >= window_lo) & (ets < window_hi)
    mask = (j < jnp.minimum(deg, k)[:, None]) & live
    counts = mask.sum(axis=1).astype(jnp.int32)
    nbrs = jnp.where(mask, nbrs, jnp.int32(-1))
    eid = jnp.where(
        mask,
        jnp.where(in_base, bidx, jnp.int32(indices.shape[0]) + didx),
        jnp.int32(-1),
    )
    return SampleOut(nbrs=nbrs, mask=mask, counts=counts, eid=eid)


@functools.partial(jax.jit, static_argnames=("k", "bits", "sample_rng",
                                              "gather_mode"))
def sample_neighbors_weighted(
    indptr: jax.Array,
    indices: jax.Array,
    cum_weights: jax.Array,
    seeds: jax.Array,
    k: int,
    key: jax.Array,
    seed_mask: Optional[jax.Array] = None,
    bits: int = 24,
    sample_rng: str = "auto",
    gather_mode: str = "xla",
) -> SampleOut:
    """Weight-proportional neighbor sampling (WITH replacement).

    Parity: the reference's ``weight_sample`` path
    (``cuda_random.cu.hpp:149-221`` — thrust discrete-distribution draws
    per row).  TPU formulation: ``cum_weights[e]`` is the inclusive
    per-row cumulative weight (host-precomputed once via
    :func:`row_cumsum_weights`); each draw inverts the row CDF with a
    fixed-depth binary search (``bits`` iterations of clipped gathers —
    data-independent control flow, so XLA unrolls it).

    ``deg <= k`` rows return all neighbors once (mask semantics identical
    to :func:`sample_neighbors`).
    """
    seeds = seeds.astype(jnp.int32)
    B = seeds.shape[0]
    start = _gather(indptr, seeds, gather_mode)
    end = _gather(indptr, seeds + 1, gather_mode)
    deg = end - start
    if seed_mask is not None:
        deg = jnp.where(seed_mask, deg, 0)
    counts = jnp.minimum(deg, k).astype(jnp.int32)
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    mask = j < counts[:, None]

    # total row weight = cum_weights[end-1] (inclusive cumsum per row)
    total = jnp.where(
        deg > 0,
        _gather(cum_weights, jnp.maximum(end - 1, 0), gather_mode),
        0.0,
    )
    u = _uniform(key, (B, k), sample_rng) * total[:, None]

    nfall = None
    if gather_mode == "blocked":
        # CDF inversion AND the neighbor reads both live in the seed's
        # contiguous window: one block gather + one VPU pass replaces the
        # ``bits``-round binary search of element gathers (ops.blockgather)
        from .blockgather import (blocked_weighted_positions,
                                  blocked_window_gather)

        assert (cum_weights.shape[0] % 128 == 0
                and indices.shape[0] % 128 == 0), (
            "blocked gather needs 128-multiple tables — pad with "
            "ops.fastgather.pad_table_128"
        )
        posl = blocked_weighted_positions(
            cum_weights.reshape(-1, 128), start, deg, u, bits=bits)
        # deg <= k: take all neighbors once instead of resampling
        posl = jnp.where(deg[:, None] <= k, j, posl)
        posl = jnp.minimum(posl, jnp.maximum(deg[:, None] - 1, 0))
        pos = start[:, None] + posl
        nbrs, nfall = blocked_window_gather(
            indices.reshape(-1, 128), start, deg, jnp.where(mask, posl, 0))
    else:
        # binary search for first position p in [start, end) with cw[p] > u
        lo = jnp.broadcast_to(start[:, None], (B, k))
        hi = jnp.broadcast_to(end[:, None], (B, k))

        def step(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            cw = _gather(cum_weights, mid, gather_mode)
            gt = cw > u
            return jnp.where(gt, lo, mid + 1), jnp.where(gt, mid, hi)

        lo, hi = jax.lax.fori_loop(0, bits, step, (lo, hi))
        pos = jnp.clip(lo, start[:, None], jnp.maximum(end[:, None] - 1, 0))
        # deg <= k: take all neighbors once instead of resampling
        pos = jnp.where(deg[:, None] <= k, start[:, None] + j, pos)
        nbrs = _gather(indices, jnp.where(mask, pos, 0), gather_mode)
    nbrs = jnp.where(mask, nbrs, jnp.int32(-1))
    eid = jnp.where(mask, pos, jnp.int32(-1))
    return SampleOut(nbrs=nbrs, mask=mask, counts=counts, eid=eid,
                     nfall=nfall)


def row_cumsum_weights(indptr, weights):
    """Host-side per-row inclusive cumulative weights for
    :func:`sample_neighbors_weighted`.  One pass at graph-build time."""
    import numpy as np

    indptr = np.asarray(indptr)
    # Accumulate in float64: a global float32 cumsum over E~1e8 edges has
    # ulp larger than typical per-edge weights, so late rows would get
    # quantized/zeroed relative weights.  Per-row totals are small, so the
    # final per-row float32 cast is safe.
    w = np.asarray(weights, dtype=np.float64)
    cw = np.cumsum(w)
    # subtract the cumsum value just before each row start
    prev = np.concatenate([[0.0], cw])[indptr[:-1]]
    out = cw - np.repeat(prev, np.diff(indptr))
    return out.astype(np.float32)


def to_ragged(out: SampleOut) -> Tuple[jax.Array, jax.Array]:
    """Dense ``[B, k]`` -> reference 2-tensor form (flat neighbors, counts).

    Matches ``TorchQuiver::sample_neighbor``'s return contract
    (``quiver_sample.cu:113-132``): neighbors of seed b occupy
    ``flat[offset[b] : offset[b] + counts[b]]``.  Host-side utility (uses a
    compaction scatter); not on the jit hot path.
    """
    nbrs = jnp.where(out.mask, out.nbrs, 0)
    counts = out.counts
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    # quiverlint: sync-ok[ragged export is a host boundary by contract]
    total = int(counts.sum())
    flat_pos = offsets[:, None] + jnp.cumsum(out.mask, axis=1) - 1
    flat = jnp.zeros(total, dtype=jnp.int32)
    flat = flat.at[jnp.where(out.mask, flat_pos, total)].set(
        nbrs, mode="drop"
    )
    return flat, counts
