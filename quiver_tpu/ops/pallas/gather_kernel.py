"""Pallas TPU kernel: a masked row gather, one DMA per live row.

``out[i] = table[idx[i]]`` where ``mask[i]``, zeros where not.  XLA's row
gather cannot skip a request: it fetches a row for every id it is handed,
and a fused step hands it the whole positional frontier, 60% of whose
slots are dead.  This kernel starts a row DMA only for a live slot: each
grid program owns ``block`` slots, reads their ids from one SMEM block
(-1 in a dead slot; blocked per program, so a 1.08 M-slot frontier never
sits in SMEM whole), and copies each live row HBM -> HBM into an output
that XLA has zeroed, at most ``window`` copies outstanding on one DMA
semaphore.  Dead rows are those zeros, never unwritten memory (garbage
times a zero mask is NaN).

**No caller in the library: the chip turned it down** (PERF.md, PR 33;
``benchmarks/probe_feature_gather.py`` times it, one v5e chip, the SAGE
cell's frontier of 1,081,344 slots, 427,868 live, out of
``int32[13,882,495,128]``).  With every slot live it takes 15.7 ms, 14.5
ns a row, where XLA's gather takes 11.5 (10.7 ns a row over distinct
rows).  With the frontier's own mask it takes 15.0 ms: **a skipped slot
costs what a fetched one does**, because the program's scalar loop (an
SMEM read and a branch a slot) is what bounds it, not the DMAs.  Through
a zero-filled VMEM output block in place of the HBM -> HBM copy: 21.7 and
21.5 ms; ``window`` 8: 44 ms; the slot loop not unrolled: 20.9 (HBM) and
31.7 (VMEM).  XLA's gather with the dead slots sent to rows of their own
takes 11.5 ms and is what ``feature._lookup_tables`` does.  What would
change the verdict is a skip that costs a few cycles (a summary word per
run of dead slots, or a compaction off the scalar core).

What the chip's compiler allows (``tests/test_aot_compile.py`` keeps its
words): a one-row DMA only out of a table whose row is exactly one
128-lane row of 32-bit words.  Out of ``bf16[N,128]`` it is refused
("Slice shape along dimension 0 must be aligned to tiling (8), but is
1"; through ``ref.bitcast(int32)`` "... tiling (4)"), out of a wider
``int32[N,768]`` or ``float32[N,256]`` too; a ``fori_loop`` is unrolled
whole or not at all.  So a 16-bit D=128 table has to be STORED as
``int32[ceil(N/2),128]`` word rows to be fetched this way
(:func:`pack_word_rows`: word ``(r, c)`` holds row ``2r`` at column ``c``
in its low half and row ``2r + 1`` in its high half, the layout the chip
gives ``bf16[N,128]`` anyway; viewing the table so inside a program makes
two whole temporaries of it), and a lookup takes its half of the fetched
word row by an elementwise shift (:func:`pick_word_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANES, check_word_rows

__all__ = ["gather_rows", "pack_word_rows", "pick_word_rows"]

BLOCK = 2048    # slots per grid program
WINDOW = 128    # row DMAs outstanding per program
UNROLL = 8      # slots per trip of a program's scalar loop


def _make_kernel(block: int, window: int, unroll: int):
    def kernel(idx_ref, zeros_ref, table_ref, out_ref, sem):
        # idx_ref: SMEM [1, 1, block] int32, -1 where dead; table_ref,
        # out_ref: HBM [N, D], [M, D]; zeros_ref is out_ref (aliased);
        # sem: one DMA semaphore
        del zeros_ref
        base = pl.program_id(0) * block

        def wait_one():
            # every row copy signals the same byte count on the one
            # semaphore, so any row's descriptor waits for one of them
            pltpu.make_async_copy(table_ref.at[0], out_ref.at[0],
                                  sem.at[0]).wait()

        def slot(i, started):
            row = idx_ref[0, 0, i]
            live = row >= 0

            @pl.when(live)
            def _():
                @pl.when(started >= window)
                def _():
                    wait_one()

                pltpu.make_async_copy(table_ref.at[row],
                                      out_ref.at[base + i],
                                      sem.at[0]).start()

            return started + live.astype(jnp.int32)

        def trip(t, started):       # Mosaic unrolls a loop whole or not
            for u in range(unroll):
                started = slot(t * unroll + u, started)
            return started

        def drain(_, carry):
            wait_one()
            return carry

        started = jax.lax.fori_loop(0, block // unroll, trip, jnp.int32(0))
        jax.lax.fori_loop(0, jnp.minimum(started, window), drain, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block", "window", "unroll",
                                    "interpret"))
def gather_rows(table: jax.Array, idx: jax.Array, mask=None, *,
                block: int = BLOCK, window: int = WINDOW,
                unroll: int = UNROLL, interpret: bool = False) -> jax.Array:
    """``jnp.where(mask[:, None], table[idx], 0)`` for ``table [N, D]``,
    ``idx [M]`` (in range wherever ``mask``), ``mask [M]`` bool (None:
    every slot live).  Compiled for a TPU, ``table`` must be rows of 128
    32-bit words (:func:`check_word_rows`)."""
    m = idx.shape[0]
    d = table.shape[1]
    assert block % unroll == 0, (block, unroll)
    if not interpret:
        check_word_rows("gather_rows", d, table.dtype)
    idx = idx.astype(jnp.int32)
    if mask is not None:
        idx = jnp.where(mask, idx, -1)
    pad = (-m) % block
    if pad:
        idx = jnp.concatenate([idx, jnp.full((pad,), -1, jnp.int32)])
    nb = (m + pad) // block
    out = pl.pallas_call(
        _make_kernel(block, window, unroll),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 1, block), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        out_shape=jax.ShapeDtypeStruct((m + pad, d), table.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(idx.reshape(nb, 1, block), jnp.zeros((m + pad, d), table.dtype),
      table)
    return out[:m] if pad else out


# ---------------------------------------------------------------- word rows
# table rows per packing step: 128 MiB of 16-bit rows, so that packing a
# 7 GB table never holds more than a few hundred MB beside it
CHUNK_ROWS = 1 << 19


@functools.partial(jax.jit, donate_argnums=0)
def _write_words(words, part, r0):
    """Pack ``part [2c, 128]`` (16-bit) into ``words[r0 : r0 + c]``."""
    u = jax.lax.bitcast_convert_type(part, jnp.uint16).astype(jnp.uint32)
    u = u.reshape(-1, 2, LANES)
    w = jax.lax.bitcast_convert_type(u[:, 0] | (u[:, 1] << 16), jnp.int32)
    return jax.lax.dynamic_update_slice(words, w, (r0, 0))


def pack_word_rows(rows_np: np.ndarray,
                   chunk_rows: int = CHUNK_ROWS) -> jax.Array:
    """A host table of 16-bit ``[N, 128]`` rows as ``int32[ceil(N/2),
    128]`` word rows on the default device, packed ON the device a chunk
    at a time into one donated buffer: never a second whole table in HBM,
    never a host pass over it (7.1 GB in 1.7 s on the chip's host, where
    ``jnp.asarray`` of the whole table took 10.8)."""
    n, dim = rows_np.shape
    assert dim == LANES and rows_np.dtype.itemsize == 2, rows_np.shape
    assert chunk_rows % 2 == 0, chunk_rows

    def upload(r):
        part = rows_np[r:r + chunk_rows]
        if part.shape[0] % 2:       # the last row of an odd table
            part = np.concatenate([part, np.zeros((1, dim), part.dtype)])
        return jnp.asarray(part)

    words = jnp.zeros(((n + 1) // 2, LANES), jnp.int32)
    nxt = upload(0)
    for r in range(0, n, chunk_rows):
        part = nxt
        if r + chunk_rows < n:      # one chunk ahead of the pack, no more
            nxt = upload(r + chunk_rows)
        words = _write_words(words, part, r // 2)
        # quiverlint: ignore[QT001] -- placement at build time, never a
        # lookup: at most two chunks are in flight beside the table
        words.block_until_ready()
    return words


def pick_word_rows(fetched: jax.Array, idx: jax.Array, dtype) -> jax.Array:
    """Rows ``idx`` (16-bit ``dtype``) out of their ``fetched`` word rows
    ``idx >> 1``; a zero word row gives a zero row."""
    w = jax.lax.bitcast_convert_type(fetched, jnp.uint32)
    sh = ((idx & 1) << 4).astype(jnp.uint32)[:, None]
    half = ((w >> sh) & 0xFFFF).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(half, dtype)
