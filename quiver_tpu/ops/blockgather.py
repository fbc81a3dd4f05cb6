"""Blocked window gather — one covering-block fetch serves ALL k draws
of a target.

The k draws of one target all read the same contiguous CSR window
``indices[start:end)`` (the reference's warp kernel exploits exactly this
contiguity with warp-wide coalesced loads, ``cuda_random.cu.hpp:8-69``).
A per-draw ``element_gather`` ignores it: every draw pays an independent
[128]-row fetch, 128x the payload per element, and on the chip a row
gather is bound by the number of rows asked for, not by their bytes.
Here a target whose window spans at most ``U`` 128-lane rows is served
by ``U`` rows fetched once + a VPU select of its k lanes: ``U`` rows per
target instead of ``k``.  Targets whose window spans more rows are
compacted into a small fallback that takes the per-draw path; if more
than its ``S`` slots are needed the whole hop takes the per-draw path
under ``lax.cond``.  A hop with ``k <= U`` has nothing to gain and lowers
to the per-draw path alone, with no ``cond``.  Results are bitwise
identical on every route, only the traffic changes.

``DEFAULT_U`` and ``FALLBACK_FRAC`` are the chip's (one TPU v5e;
measured, PR 31, by ``benchmarks/probe_window_gather.py`` at the cells'
own hops on lognormal CSRs of mean degree 14.5, and in the two cells'
traces; ``PERF.md`` section 6):

  * a row gather costs by the rows asked for: 901,120 rows of 512 B for
    the 180,224 x 5 draws of the SAGE cell's hop 3 take 11.4 ms, the
    360,448 rows of its two-row windows 4.4 ms (80 M rows/s either way);
  * ``U = 2``: a window of up to 129 entries always fits two rows, so
    157 of those 180,224 targets miss (0.09%; 29 of 26,624 in the typed
    cell's hop 2), where one row misses 12,658 (7%) and three rows move
    half as many rows again.  The whole routed hop: 6.0 ms at U=2 with
    B/32 slots against 11.5 ms per draw, 7.1 at U=1 and 12.2 at U=3 with
    B/4 slots; the typed hop 1.04 against 4.99 ms;
  * ``FALLBACK_FRAC = 1/32``: the fallback's static ``S x k`` rows are
    fetched on every call whoever misses, so at B/4 the same hop takes
    9.8 ms (typed: 2.28); B/32 is thirty times the misses seen;
  * the block is ``U`` gathers of one row per target: one gather over
    ``[B, U]`` ids needs a re-layout pass after it (5.8 against 5.0 ms
    with the select), ONE ``lax.gather`` of ``[U, 128]`` slices takes
    234 ms, a 1-KB row of the ``[R/2, 256]`` view 7.7 ms, and
    ``take_along_axis`` in place of the one-hot select 20 ms;
  * at B = 1,024 every route takes 0.24 ms: no floor on B is needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .fastgather import LANES, element_gather

__all__ = ["blocked_window_gather", "blocked_weighted_positions",
           "fallback_slots", "NO_WINDOW"]

DEFAULT_U = 2
FALLBACK_FRAC = 1 / 32
# what ``blocked_window_gather`` reports in place of a count for a hop
# that has no window route at all (k <= U)
NO_WINDOW = -1


def fallback_slots(B: int, fallback_frac: float = FALLBACK_FRAC) -> int:
    """Static slot count of the fallback for ``B`` targets: paid in full
    on every call, so sized by the share that can overflow."""
    return min(max(int(B * fallback_frac), 8), B)


def _fit_split(start, deg, U, B, fallback_frac):
    """The fit test.  Returns ``(r0, fits, nfall, S)``: ``fits[b]`` iff
    target b's window [start, start+deg) spans <= U rows of the 128-lane
    table from its first row ``r0[b]``, ``nfall`` how many do not, ``S``
    the fallback's static slot count."""
    r0 = jax.lax.shift_right_logical(start, 7)
    last = start + jnp.maximum(deg - 1, 0)
    fits = (jax.lax.shift_right_logical(last, 7) - r0) < U
    return (r0, fits, jnp.sum(~fits, dtype=jnp.int32),
            fallback_slots(B, fallback_frac))


def _compact(fits, S):
    """``(target_of_slot, valid)``: the targets that do not fit, in order,
    in ``S`` slots (0 where not ``valid``).  One sort of B keys and no
    scatter over all B, which costs more on the chip than the sort does
    at the cells' large hops (0.39 against 0.88 ms at B = 180,224)."""
    B = fits.shape[0]
    first = jax.lax.sort(jnp.where(fits, B, jnp.arange(B, dtype=jnp.int32)),
                         is_stable=False)[:S]
    valid = first < B
    return jnp.where(valid, first, 0), valid


def _block_rows(table2d, r0, U):
    """The covering block as ``U`` arrays ``[B, 128]``: block row u of
    every target (rows clipped to the table), one row gather each (the
    other ways of fetching it, timed: the module's docstring)."""
    last = table2d.shape[0] - 1
    return [table2d.at[jnp.minimum(r0 + u, last)].get(
        mode="promise_in_bounds") for u in range(U)]


def _block_select(blks, rel):
    """vals[b, j] = block[b].flat[rel[b, j]]: ``rel >> 7`` picks the block
    row, then a 128-lane one-hot VPU reduction (XLA fuses the compares
    into the reduce; no [B, k, 128] intermediate)."""
    src = blks[0][:, None, :]
    for u in range(1, len(blks)):
        src = jnp.where(
            jax.lax.shift_right_logical(rel, 7)[..., None] == u,
            blks[u][:, None, :], src)
    onehot = jnp.bitwise_and(rel, LANES - 1)[..., None] == \
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
    return jnp.sum(jnp.where(onehot, src, 0), axis=2, dtype=src.dtype)


def blocked_window_gather(table2d, start, deg, pos, U=DEFAULT_U,
                          fallback_frac=FALLBACK_FRAC):
    """``vals[b, j] = table.flat[start[b] + pos[b, j]]`` where every row
    b's reads lie in its window ``[start[b], start[b] + deg[b])``.

    Args:
      table2d: ``[rows, 128]`` (a 128-padded flat table, reshaped).
      start: ``[B]`` int32 window starts (flat element offsets).
      deg: ``[B]`` int32 window lengths (0 allowed).
      pos: ``[B, k]`` int32 in-window positions (garbage rows allowed
        where the caller masks them out; must be in [0, max(deg-1, 0)]).

    Returns ``(vals, nfall)``: ``nfall`` is the int32 count of targets
    whose window does not fit ``U`` rows (more than
    ``fallback_slots(B, fallback_frac)`` of them: the whole call took
    the per-draw path), or ``NO_WINDOW`` where ``k <= U`` and the call
    lowers to the per-draw path alone.
    """
    B, k = pos.shape
    nrows = table2d.shape[0]
    idx = jnp.clip(start[:, None] + pos, 0, nrows * LANES - 1)
    if k <= U:
        return element_gather(table2d, idx), jnp.int32(NO_WINDOW)
    r0, fits, nfall, S = _fit_split(start, deg, U, B, fallback_frac)

    def window(_):
        seed_of_slot, valid = _compact(fits, S)
        rel = jnp.clip(idx - (r0[:, None] << 7), 0, U * LANES - 1)
        vals = _block_select(_block_rows(table2d, r0, U), rel)
        fb_idx = jnp.where(valid[:, None],
                           jnp.take(idx, seed_of_slot, axis=0), 0)
        return vals.at[jnp.where(valid, seed_of_slot, B)].set(
            element_gather(table2d, fb_idx), mode="drop")

    def classic(_):
        return element_gather(table2d, idx)

    return jax.lax.cond(nfall <= S, window, classic, None), nfall


def blocked_weighted_positions(cw2d, start, deg, u, U=DEFAULT_U,
                               fallback_frac=FALLBACK_FRAC,
                               bits: int = 24):
    """Weighted draw positions via ONE pass over the gathered CDF block.

    ``cw2d`` is the 128-padded per-row inclusive cumulative-weight table
    (``row_cumsum_weights``) reshaped ``[rows, 128]``; ``u[b, j]`` is the
    uniform draw already scaled by the row total.  For a fitting seed the
    first CDF entry exceeding ``u`` equals the COUNT of in-window entries
    ``<= u`` (the CDF is nondecreasing within a row) — one masked VPU
    reduction over the block replaces the classic ``bits``-round binary
    search of element gathers.  Non-fitting seeds take the classic
    search, compacted; cap overflow falls back wholesale (lax.cond).

    Returns ``pos[b, j]`` in ``[0, deg[b])`` (garbage where deg == 0;
    callers mask).
    """
    B, k = u.shape
    nrows = cw2d.shape[0]
    r0, fits, nfall, S = _fit_split(start, deg, U, B, fallback_frac)

    def classic_search(starts, degs, us):
        """bits-round binary search over cw2d.flat (classic path)."""
        lo = jnp.broadcast_to(starts[:, None], us.shape)
        hi = jnp.broadcast_to((starts + degs)[:, None], us.shape)

        def step(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            cw = element_gather(cw2d, jnp.clip(mid, 0, nrows * LANES - 1))
            gt = cw > us
            return jnp.where(gt, lo, mid + 1), jnp.where(gt, mid, hi)

        lo, hi = jax.lax.fori_loop(0, bits, step, (lo, hi))
        return jnp.clip(lo - starts[:, None], 0,
                        jnp.maximum(degs[:, None] - 1, 0))

    def blocked(_):
        blk = jnp.concatenate(_block_rows(cw2d, r0, U), axis=1)  # [B, U*128]
        off = start - (r0 << 7)                                # [B]
        win = jax.lax.broadcasted_iota(jnp.int32, (1, U * LANES), 1)
        in_win = ((win >= off[:, None])
                  & (win < (off + deg)[:, None]))              # [B, W]
        # count of in-window CDF entries <= u  ->  first-exceed position
        le = blk[:, None, :] <= u[:, :, None]                  # [B, k, W]
        cnt = jnp.sum(jnp.where(in_win[:, None, :], le, False), axis=2)
        pos = jnp.clip(cnt, 0, jnp.maximum(deg[:, None] - 1, 0))
        pos = pos.astype(jnp.int32)
        seed_of_slot, valid = _compact(fits, S)
        fb_pos = classic_search(
            jnp.where(valid, jnp.take(start, seed_of_slot), 0),
            jnp.where(valid, jnp.take(deg, seed_of_slot), 0),
            jnp.take(u, seed_of_slot, axis=0))
        return pos.at[jnp.where(valid, seed_of_slot, B)].set(
            fb_pos, mode="drop")

    def classic(_):
        return classic_search(start, deg, u)

    return jax.lax.cond(nfall <= S, blocked, classic, None)
