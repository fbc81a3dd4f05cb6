"""Fast scalar gather for TPU — the element-gather that graph sampling
lives on.

XLA lowers a 1-D ``table[idx]`` gather on TPU to a serialized
dynamic-slice loop (~tens of ns per element) — that was the measured
bottleneck of the sampling hop.  HBM, however, serves 512-byte transactions
regardless, and *row* gathers of ``[*, 128]`` blocks run at near-bandwidth.
So: reshape the table to ``[N/128, 128]``, row-gather the covering block of
each element, then select the lane on the VPU with a one-hot reduction.
Bandwidth cost is 128x the payload, but on products-scale sampling that is
still ~30x faster than the serialized scalar gather.

This is the TPU counterpart of the coalesced reads the reference's CUDA
kernels get from warp-wide loads (``cuda_random.cu.hpp:8-69``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["element_gather", "prepare_table", "pad_table_128"]

LANES = 128


def prepare_table(table: jax.Array) -> jax.Array:
    """Pad a 1-D table to a multiple of 128 and reshape to [rows, 128].

    Do this ONCE at graph-build time (CSRTopo.to_device) so the hot path
    pays no reshape.
    """
    n = table.shape[0]
    pad = (-n) % LANES
    if pad:
        table = jnp.concatenate(
            [table, jnp.zeros((pad,), table.dtype)]
        )
    return table.reshape(-1, LANES)


def pad_table_128(table, fill=None):
    """Pad a 1-D table to a multiple of 128 (host numpy or jnp).

    ``fill=None`` zero-pads; otherwise pads with ``fill`` (e.g. the last
    cumulative weight so clipped probes read a harmless value).  The
    ``blocked`` gather path REQUIRES 128-multiple tables — ``_gather``
    rejects anything else rather than silently truncating.
    """
    n = table.shape[0]
    pad = (-n) % 128
    if not pad:
        return table
    val = fill if fill is not None else 0
    return jnp.concatenate(
        [table, jnp.full((pad,), val, table.dtype)]
    )


def element_gather(table2d: jax.Array, idx: jax.Array) -> jax.Array:
    """``table.reshape(-1)[idx]`` via row gather + lane select.

    Args:
      table2d: ``[rows, 128]`` (from :func:`prepare_table`).
      idx: any-shape int32 flat element indices (must be < rows*128).
    """
    shape = idx.shape
    flat = idx.reshape(-1)
    row = jax.lax.shift_right_logical(flat, 7)
    lane = jnp.bitwise_and(flat, LANES - 1)
    rows = jnp.take(table2d, row, axis=0)              # [M, 128] row gather
    onehot = (
        lane[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    )
    out = jnp.sum(jnp.where(onehot, rows, 0), axis=1, dtype=table2d.dtype)
    return out.reshape(shape)
