"""Pallas TPU kernel: fully-fused scalar gather with per-element row DMA.

The sampling hop's bottleneck op is ``table[idx]`` for huge 1-D ``table``
(indptr/indices) and ~10^4 scattered ``idx``.  The three formulations:

  * XLA gather: serialized dynamic-slice loop — latency-bound, slow.
  * ``lanes`` (ops/fastgather.py): row-gather ``[M, 128]`` blocks to HBM,
    then lane-select — near-bandwidth but moves 128x the payload TWICE
    (write + read of the intermediate).
  * **this kernel**: each element's covering 128-lane row is DMA'd
    HBM->VMEM directly (double-buffered groups of 128 outstanding copies,
    the CUDA-warp-per-element analogue of ``cuda_random.cu.hpp:8-69``'s
    coalesced loads), lane-selected on the VPU, and only the ``[M]``
    payload ever returns to HBM.  128x less HBM write traffic than lanes.

Used by ``gather_mode="pallas"`` in the samplers; falls back to lanes on
backends without mosaic support.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_element_gather"]

LANES = 128
GROUP = 128   # rows DMA'd per pipeline stage ([GROUP, 128] VMEM scratch)
NBUF = 2      # double buffering
GPB = 8       # groups per grid program -> BLOCK elements per program
BLOCK = GPB * GROUP


def _kernel(row_ref, lane_ref, table_ref, out_ref, rows_ref, sem):
    # row_ref: [1, GPB, GROUP] int32 covering-row ids — a per-program SMEM
    #   block (NOT whole-array scalar prefetch: at hop-3 index counts the
    #   full array is ~3.6 MB, 3.5x the 1 MB SMEM — measured OOM on v5e;
    #   3-D because Mosaic requires the trailing block dims be (8k, 128k))
    # lane_ref/out_ref: [GPB, GROUP] int32 VMEM blocks
    # table_ref: [R, 128] in HBM (ANY)
    # rows_ref: [NBUF, GROUP, 128] scratch; sem: [NBUF, GROUP] DMA sems

    def copies(buf, g):
        return [
            pltpu.make_async_copy(
                table_ref.at[row_ref[0, g, e]],
                rows_ref.at[buf, e],
                sem.at[buf, e],
            )
            for e in range(GROUP)
        ]

    for c in copies(0, 0):
        c.start()
    for g in range(GPB):  # static unroll: buffers/slices all literal
        buf = g % NBUF
        if g + 1 < GPB:
            for c in copies((g + 1) % NBUF, g + 1):
                c.start()
        for c in copies(buf, g):
            c.wait()
        rows = rows_ref[buf]                       # [GROUP, 128]
        lanes = lane_ref[g][:, None]               # [GROUP, 1]
        iota = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        out_ref[g] = jnp.sum(jnp.where(iota == lanes, rows, 0), axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_element_gather(table2d: jax.Array, idx: jax.Array,
                          interpret: bool = False) -> jax.Array:
    """``table2d.reshape(-1)[idx]`` — fused row-DMA + lane-select.

    ``table2d``: [R, 128] (``fastgather.prepare_table``); ``idx``: any
    shape of flat element indices (< R*128).  Pads internally to BLOCK.
    """
    shape = idx.shape
    flat = idx.reshape(-1).astype(jnp.int32)
    m = flat.shape[0]
    mp = -(-m // BLOCK) * BLOCK
    if mp != m:
        flat = jnp.concatenate(
            [flat, jnp.zeros((mp - m,), jnp.int32)]
        )
    row = jax.lax.shift_right_logical(flat, 7).reshape(-1, GPB, GROUP)
    lane = jnp.bitwise_and(flat, LANES - 1).reshape(-1, GROUP)
    out = pl.pallas_call(
        _kernel,
        grid=(mp // BLOCK,),
        in_specs=[
            pl.BlockSpec((1, GPB, GROUP), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((GPB, GROUP), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((GPB, GROUP), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((NBUF, GROUP, LANES), table2d.dtype),
            pltpu.SemaphoreType.DMA((NBUF, GROUP)),
        ],
        out_shape=jax.ShapeDtypeStruct((mp // GROUP, GROUP),
                                       table2d.dtype),
        interpret=interpret,
    )(row, lane, table2d)
    return out.reshape(-1)[:m].reshape(shape)
