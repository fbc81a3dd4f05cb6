"""Pallas TPU kernel: ragged page-granularity feature gather.

The data-layer application of the Ragged Paged Attention design
(PAPERS.md, arxiv 2604.15464): feature rows live in fixed-size HBM
pages (``page_rows`` x row-bytes, sized to a multiple of the 512B HBM
transaction), and one kernel gathers a variable-length frontier by
walking ``(page, offset)`` pairs — whole-page DMAs instead of
per-element transfers (on-chip time of either: not measured).

On a TPU the kernel compiles only for row widths that are a multiple of
128 lanes and plans whose scalar-prefetched vectors fit SMEM; anything
else raises :class:`~quiver_tpu.ops.pallas.KernelConstraintError`
(``tests/test_aot_compile.py`` keeps the compiler's own refusals).

Contract with the host-side planner (``ops/paged.py``):

  * the frontier is sorted by frame id, so each output block touches a
    *run* of pages; the planner emits, per block, the distinct frames
    the block needs (``blk_pages``, first-appearance order, at most
    ``ppb`` of them) and per row the block-local page index + in-page
    offset (``row_lp`` / ``row_off``);
  * the kernel DMAs each distinct page HBM->VMEM once (``NBUF``
    copies in flight), then serves every row of the block from VMEM —
    rows are VPU copies, transactions are page-sized;
  * padded rows (``B`` up to a multiple of ``block``; linear padding,
    never pow2) carry ``row_lp = row_off = 0`` — they read page slot 0
    of the scratch and are dropped by the caller's inverse-permutation
    take, so they can never read past a staged buffer.

Interpret mode (``interpret=True``) runs the same kernel logic on CPU;
tier-1 tests exercise exactly this path (no separate jnp re-
implementation to drift from the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_lane_width, check_scalar_prefetch

__all__ = ["page_gather", "NBUF"]

NBUF = 4  # outstanding page DMAs per program


def _kernel(blk_pages_ref, blk_np_ref, row_lp_ref, row_off_ref,
            frames_ref, out_ref, scratch, sem, *, page_rows, ppb):
    blk = out_ref.shape[0]
    b = pl.program_id(0)
    n_pages = blk_np_ref[b]

    def page_dma(slot, k):
        # one whole page: frames[frame_id] -> scratch rows [k*R, (k+1)*R)
        return pltpu.make_async_copy(
            frames_ref.at[blk_pages_ref[b * ppb + k]],
            scratch.at[pl.ds(k * page_rows, page_rows)],
            sem.at[slot],
        )

    # warm-up: fill the DMA pipeline
    for w in range(NBUF):
        @pl.when(w < n_pages)
        def _(w=w):
            page_dma(w, w).start()

    def dma_body(k, _):
        # wait k FIRST: its semaphore slot (k % NBUF) is reused by DMA
        # k+NBUF, so the slot must drain before the next start
        page_dma(k % NBUF, k).wait()

        @pl.when(k + NBUF < n_pages)
        def _():
            page_dma((k + NBUF) % NBUF, k + NBUF).start()

        return 0

    jax.lax.fori_loop(0, n_pages, dma_body, 0)

    base = b * blk

    def row_body(i, _):
        # block-local page index + in-page offset -> one scratch row
        lp = row_lp_ref[base + i]
        off = row_off_ref[base + i]
        row = scratch[pl.ds(lp * page_rows + off, 1), :]
        out_ref[pl.ds(i, 1), :] = row
        return 0

    jax.lax.fori_loop(0, blk, row_body, 0)


@functools.partial(jax.jit,
                   static_argnames=("page_rows", "block", "ppb",
                                    "interpret"))
def page_gather(frames: jax.Array, blk_pages: jax.Array,
                blk_np: jax.Array, row_lp: jax.Array,
                row_off: jax.Array, *, page_rows: int, block: int,
                ppb: int, interpret: bool = False) -> jax.Array:
    """Gather ``M`` rows (M = len(row_lp), M % block == 0) out of paged
    ``frames [F, page_rows, D]``.

    Args:
      frames: the device frame pool (DEVICE pages + OVERLAY pool).
      blk_pages: ``[nb * ppb]`` int32 — per block, the distinct frame
        ids it reads (first-appearance order, padded with 0).
      blk_np: ``[nb]`` int32 — how many of each block's ``ppb`` entries
        are real.
      row_lp: ``[M]`` int32 — per row, index into its block's
        ``blk_pages`` entries.
      row_off: ``[M]`` int32 — per row, offset within its page.
      page_rows / block / ppb: static geometry (rows per page, output
        rows per grid program, max distinct pages per block).
    """
    m = row_lp.shape[0]
    assert m % block == 0, (m, block)
    d = frames.shape[2]
    nb = m // block
    if not interpret:
        check_lane_width("page_gather", d)
        check_scalar_prefetch("page_gather",
                              4 * (2 * m + nb * ppb + nb))
    return pl.pallas_call(
        functools.partial(_kernel, page_rows=page_rows, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (block, d), lambda i, *refs: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((ppb * page_rows, d), frames.dtype),
                pltpu.SemaphoreType.DMA((NBUF,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), frames.dtype),
        interpret=interpret,
    )(blk_pages.astype(jnp.int32), blk_np.astype(jnp.int32),
      row_lp.astype(jnp.int32), row_off.astype(jnp.int32), frames)
