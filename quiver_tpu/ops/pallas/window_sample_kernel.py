"""Fused Pallas TPU sampling hop: PRNG + stratified positions + per-seed
window DMA + lane select in ONE kernel.

This is the TPU answer to the reference's warp sampling kernel
(``cuda_random.cu.hpp:8-69``): there, a warp serves one seed and its
coalesced loads ride the CSR window's contiguity.  Here, each seed's
contiguous ``indices[start, start+deg)`` window (<= ``U`` 128-lane rows)
is moved HBM->VMEM by ONE async copy — the coalesced unit on TPU — with
``SUB`` seeds' copies in flight per stage and double buffering across
stages.  The draws never leave VMEM until the final ``[B, k]`` payload:

  * the counter-hash uniforms (``ops/sample.py::_hash_uniform``) are
    re-derived in-kernel, op for op, from the same folded key words — so
    the kernel's draws are BITWISE IDENTICAL to the XLA hash path and
    every correctness test can compare exactly;
  * the stratified position formula is
    ``ops/sample.py::_stratified_positions``, reproduced exactly;
  * the select is a ``[SUB, kpad, 128]`` one-hot per window row — the
    same VPU cost XLA pays in ``ops/blockgather.py``, but with no
    ``[B, U*128]`` HBM intermediate (the blocked mode's block gather
    round-trips ~2x the window bytes through HBM; this kernel writes
    only the ``[B, 128]`` output row per seed).

Traffic per seed: ``U*512`` bytes in, 512 bytes out — vs the ``lanes``
mode's ``k*512`` in + ``k*512 * 2`` intermediate, and one DMA issue per
SEED instead of per DRAW (on-chip time against the other modes: not
measured).

Seeds whose window spans more than ``U`` rows are recomputed outside by
the compacted classic fallback (same policy/structure as
``ops/blockgather.py``); cap overflow falls back wholesale via
``lax.cond``.  Results are bitwise identical on every route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_window_sample", "parse_pwindow"]

from ..blockgather import DEFAULT_U, FALLBACK_FRAC
from ..fastgather import LANES
# the kernel body re-derives the XLA hash path with the SAME finalizer
# and constants — imported, never copied, so they cannot diverge
from ..sample import HASH_PHI, _fmix32

SUB = 64      # seeds per stage = DMAs in flight per buffer
STAGES = 4    # stages per grid program (static unroll)
SPP = SUB * STAGES  # seeds per program
NBUF = 2      # double buffering


def parse_pwindow(mode: str) -> int:
    """``"pwindow"`` -> default U; ``"pwindow:4"`` -> 4."""
    from ..blockgather import parse_u_mode

    return parse_u_mode(mode, "pwindow", DEFAULT_U)


def _make_kernel(k: int, kpad: int, U: int):
    def kernel(r0c_ref, kw_ref, deg_ref, off_ref, table_ref, out_ref,
               win_ref, sem):
        # r0c_ref: SMEM [1, SPP] clipped covering-row starts (DMA addressing)
        # kw_ref:  SMEM [1, 2] folded key words (uint32)
        # deg_ref/off_ref: VMEM [SPP, 1] per-seed degree / in-block offset
        # table_ref: [R, 128] HBM (ANY); out_ref: VMEM [SPP, 128] block
        # win_ref: VMEM scratch [NBUF, SUB, U, 128]; sem: DMA [NBUF, SUB]
        pid = pl.program_id(0)
        k0 = kw_ref[0, 0]
        k1 = kw_ref[0, 1]

        def start_dmas(buf, st):
            base = st * SUB
            for e in range(SUB):
                pltpu.make_async_copy(
                    table_ref.at[pl.ds(r0c_ref[0, base + e], U)],
                    win_ref.at[buf, e],
                    sem.at[buf, e],
                ).start()

        def wait_dmas(buf, st):
            base = st * SUB
            for e in range(SUB):
                pltpu.make_async_copy(
                    table_ref.at[pl.ds(r0c_ref[0, base + e], U)],
                    win_ref.at[buf, e],
                    sem.at[buf, e],
                ).wait()

        start_dmas(0, 0)
        for st in range(STAGES):
            buf = st % NBUF
            if st + 1 < STAGES:
                start_dmas((st + 1) % NBUF, st + 1)

            # ---- in-kernel PRNG + positions (bitwise = the XLA hash path)
            deg = deg_ref[pl.ds(st * SUB, SUB), :]            # [SUB, 1] i32
            off = off_ref[pl.ds(st * SUB, SUB), :]            # [SUB, 1] i32
            e_iota = jax.lax.broadcasted_iota(jnp.uint32, (SUB, 1), 0)
            b = (pid.astype(jnp.uint32) * SPP
                 + jnp.uint32(st * SUB) + e_iota)              # [SUB, 1]
            j_iota = jax.lax.broadcasted_iota(jnp.int32, (1, kpad), 1)
            counter = b * jnp.uint32(k) + j_iota.astype(jnp.uint32)
            x = counter * jnp.uint32(HASH_PHI)
            x = _fmix32(x ^ k0)
            x = _fmix32(x ^ k1)
            # Mosaic has no uint32->f32 cast; x>>8 < 2^24 so the int32
            # detour is value-exact (bitwise = the XLA path's direct cast)
            u = ((x >> 8).astype(jnp.int32).astype(jnp.float32)
                 * jnp.float32(1.0 / (1 << 24)))
            degf = deg.astype(jnp.float32)                    # [SUB, 1]
            jf = j_iota.astype(jnp.float32)
            lo = jnp.floor(jf * degf / k)
            hi = jnp.floor((jf + 1) * degf / k)
            strat = lo + jnp.floor(u * jnp.maximum(hi - lo, 1.0))
            pos = jnp.where(deg <= k, j_iota, strat.astype(jnp.int32))
            pos = jnp.minimum(pos, jnp.maximum(deg - 1, 0))   # [SUB, kpad]
            rel = jnp.clip(off + pos, 0, U * LANES - 1)
            rel_row = rel >> 7
            rel_lane = rel & (LANES - 1)

            # ---- select from the DMA'd windows (one-hot per window row)
            wait_dmas(buf, st)
            lane_iota = jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, LANES), 2)
            onehot = rel_lane[:, :, None] == lane_iota        # [SUB,kpad,128]
            vals = jnp.zeros((SUB, kpad), out_ref.dtype)
            for uu in range(U):
                wu = win_ref[buf, :, uu, :]                   # [SUB, 128]
                pick = jnp.where(
                    onehot & (rel_row[:, :, None] == uu),
                    wu[:, None, :], 0)
                vals = vals + jnp.sum(pick, axis=2)
            out_ref[st * SUB:(st + 1) * SUB, 0:kpad] = vals

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("k", "U", "fallback_frac", "interpret"))
def pallas_window_sample(table2d: jax.Array, start: jax.Array,
                         deg: jax.Array, key: jax.Array, k: int,
                         U: int = DEFAULT_U,
                         fallback_frac: float = FALLBACK_FRAC,
                         interpret: bool = False) -> jax.Array:
    """One fused sampling hop: returns ``nbrs[b, j] =
    table.flat[start[b] + pos[b, j]]`` where ``pos`` is the stratified
    hash-RNG draw (``_stratified_positions`` of ``_hash_uniform(key,
    (B, k))``) — computed in-kernel for seeds whose window fits ``U``
    rows, by the identical XLA formula for the rest.

    ``table2d``: [R, 128] (128-padded flat table); ``start``/``deg``:
    [B] int32 window starts/lengths; ``key``: PRNG key (hash-folded).
    Rows where ``deg == 0`` return garbage (callers mask via counts).
    """
    from ..blockgather import _compact, _fit_split
    from ..fastgather import element_gather
    from ..sample import (_fold_key_words, _hash_uniform,
                          _stratified_positions)

    B = start.shape[0]
    R = table2d.shape[0]

    def classic(_=None):
        # the XLA route with identical draws — used for the early guards,
        # the cap-overflow wholesale fallback, and (compacted) the
        # non-fitting seeds, so every route stays bitwise equal
        u = _hash_uniform(key, (B, k))
        pos = _stratified_positions(u, deg, k)
        return element_gather(
            table2d, jnp.clip(start[:, None] + pos, 0, R * LANES - 1))

    if k > LANES or R < U:
        # fanout beyond one output row / table smaller than a window
        return classic()

    kpad = -(-k // 8) * 8  # next multiple of 8 (>= 8 for k >= 1)
    k0, k1 = _fold_key_words(key)
    r0, fits, nfall, S = _fit_split(start, deg, U, B, fallback_frac)
    r0c = jnp.clip(r0, 0, R - U)
    off = start - (r0c << 7)

    Bp = -(-B // SPP) * SPP
    padn = Bp - B
    padv = lambda a: (jnp.concatenate([a, jnp.zeros((padn,), a.dtype)])
                      if padn else a)
    r0c_p = padv(r0c).reshape(1, Bp)
    deg_p = padv(deg.astype(jnp.int32)).reshape(Bp, 1)
    off_p = padv(off).reshape(Bp, 1)
    kw = jnp.stack([k0, k1]).reshape(1, 2)

    def fused(_):
        out = pl.pallas_call(
            _make_kernel(k, kpad, U),
            grid=(Bp // SPP,),
            in_specs=[
                pl.BlockSpec((1, SPP), lambda i: (0, i),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 2), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((SPP, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((SPP, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((SPP, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((NBUF, SUB, U, LANES), table2d.dtype),
                pltpu.SemaphoreType.DMA((NBUF, SUB)),
            ],
            out_shape=jax.ShapeDtypeStruct((Bp, LANES), table2d.dtype),
            interpret=interpret,
        )(r0c_p, kw, deg_p, off_p, table2d)
        vals = out[:B, :k]
        seed_of_slot, valid = _compact(fits, S)
        # non-fitting seeds: identical draws via the XLA formula, gathered
        # per element on the compacted slots (same policy as blockgather)
        u_all = _hash_uniform(key, (B, k))
        fb_start = jnp.where(valid, jnp.take(start, seed_of_slot), 0)
        fb_deg = jnp.where(valid, jnp.take(deg, seed_of_slot), 0)
        fb_pos = _stratified_positions(
            jnp.take(u_all, seed_of_slot, axis=0), fb_deg, k)
        fb_idx = jnp.clip(fb_start[:, None] + fb_pos, 0, R * LANES - 1)
        fb_vals = element_gather(table2d, fb_idx)
        return vals.at[jnp.where(valid, seed_of_slot, B)].set(
            fb_vals, mode="drop")

    return jax.lax.cond(nfall <= S, fused, classic, None)
