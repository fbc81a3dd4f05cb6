"""Pallas TPU kernel: feature-row gather with pipelined DMA.

The TPU-native ``quiver_tensor_gather`` (reference:
``srcs/cpp/include/quiver/shard_tensor.cu.hpp:7-61`` — warp-per-row byte
copy walking a device-pointer table).  Here there is one memory space to
walk (HBM) and the kernel's job is purely to keep many row DMAs in flight:
each grid program owns a block of output rows and round-robins NBUF
outstanding HBM->VMEM copies selected by the scalar-prefetched index
vector.

For very wide rows XLA's own gather is already near-bandwidth; this kernel
wins on mid-width rows (64-512 floats) where per-row launch overhead
dominates XLA's emitter.  Benchmarked against ``jnp.take`` in
``benchmarks/bench_feature.py``; ``Feature`` picks whichever is faster.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_lane_width, check_scalar_prefetch

__all__ = ["gather_rows"]

NBUF = 4  # outstanding DMAs per program


def _kernel(idx_ref, table_ref, out_ref, sem):
    blk = out_ref.shape[0]
    base = pl.program_id(0) * blk

    def get_dma(slot, i):
        return pltpu.make_async_copy(
            table_ref.at[idx_ref[base + i]],
            out_ref.at[i],
            sem.at[slot],
        )

    # warm-up: fill the pipeline
    for w in range(NBUF):
        @pl.when(w < blk)
        def _(w=w):
            get_dma(w, w).start()

    def body(i, _):
        # wait i FIRST: its semaphore slot (i % NBUF) is the same slot
        # DMA i+NBUF will use, so the slot must drain before reuse
        get_dma(i % NBUF, i).wait()

        @pl.when(i + NBUF < blk)
        def _():
            get_dma((i + NBUF) % NBUF, i + NBUF).start()

        return 0

    jax.lax.fori_loop(0, blk, body, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gather_rows(table: jax.Array, idx: jax.Array, block: int = 256,
                interpret: bool = False) -> jax.Array:
    """``table[idx]`` for 2-D ``table [N, D]``, ``idx [M]`` (M % block == 0,
    pad with 0s and slice if needed)."""
    m = idx.shape[0]
    assert m % block == 0, (m, block)
    d = table.shape[1]
    if not interpret:
        check_lane_width("gather_rows", d)
        check_scalar_prefetch("gather_rows", 4 * m)
    grid = (m // block,)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (block, d), lambda i, idx_ref: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.SemaphoreType.DMA((NBUF,))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table)
