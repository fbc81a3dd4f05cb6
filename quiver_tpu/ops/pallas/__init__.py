"""Pallas TPU kernels of the feature store (variants of its XLA gathers).

* ``gather_kernel`` — masked DMA row gather: a row is fetched only for a
  live slot of a frontier.  Timed on the chip and turned down (a skipped
  slot costs what a fetched one does; PERF.md, PR 33): no caller here.
* ``page_gather_kernel`` — ragged whole-page gather for the paged
  feature store (``ops/paged.py``): pipelined page DMA, no pow2
  padding, one executable per batch size.

All kernels carry an ``interpret=`` escape hatch so CPU CI executes
the exact kernel logic under the Pallas interpreter.  Interpret mode
accepts shapes Mosaic refuses; the refusals the chip's compiler gave at
the widths the repo benchmarks (``tests/test_aot_compile.py``) are
checked here, so that asking for such a kernel on a TPU raises a
:class:`KernelConstraintError` that names the constraint instead of a
compiler internal error.
"""

__all__ = ["KernelConstraintError", "check_lane_width",
           "check_scalar_prefetch", "check_word_rows"]

LANES = 128
SMEM_BYTES = 1 << 20    # v5e scalar memory, as its compiler reports it


class KernelConstraintError(ValueError):
    """A Pallas kernel was asked to compile for the TPU (``interpret=
    False``) at a shape Mosaic refuses."""


def check_lane_width(kernel: str, dim: int) -> None:
    """Row DMAs slice the lane dimension whole: it must be a multiple of
    128 (Mosaic: "Slice shape along dimension N must be aligned to
    tiling (128)")."""
    if dim % LANES:
        raise KernelConstraintError(
            f"{kernel}: row width {dim} is not a multiple of {LANES} "
            f"lanes — Mosaic refuses the row DMA on a TPU (pad the "
            f"table's lane dimension to {-(-dim // LANES) * LANES}, or "
            f"use the XLA gather)")


def check_scalar_prefetch(kernel: str, nbytes: int) -> None:
    """Scalar-prefetched operands live in SMEM whole, for the entire
    grid (XLA: "would exceed memory ... space=smem ... prefetched SMEM
    operand")."""
    if nbytes > SMEM_BYTES:
        raise KernelConstraintError(
            f"{kernel}: {nbytes} bytes of scalar-prefetched indices "
            f"exceed the {SMEM_BYTES}-byte SMEM of a v5e — the index "
            f"vectors are prefetched whole, not per block (split the "
            f"batch, or use the XLA gather)")


def check_word_rows(kernel: str, dim: int, dtype) -> None:
    """A one-row DMA is accepted only out of a table whose row is exactly
    one 128-lane row of 32-bit words.  Out of a 16-bit table a row is
    half a sublane tile (Mosaic: "Slice shape along dimension 0 must be
    aligned to tiling (8), but is 1"; bitcast to int32 in the kernel,
    "... tiling (4)"), and out of a row wider than 128 words Mosaic asks
    for whole 8-row tiles too ("... tiling (8), but is 1")."""
    import numpy as np

    check_lane_width(kernel, dim)
    bits = 8 * np.dtype(dtype).itemsize
    if bits != 32:
        raise KernelConstraintError(
            f"{kernel}: a one-row DMA out of a {bits}-bit table is "
            f"refused by Mosaic on a TPU — a row is not a whole tile of "
            f"32-bit words (store the table as word rows, "
            f"gather_kernel.pack_word_rows, or use the XLA gather)")
    if dim != LANES:
        raise KernelConstraintError(
            f"{kernel}: a one-row DMA out of a row of {dim} words is "
            f"refused by Mosaic on a TPU — only a row of exactly {LANES} "
            f"words is a tile of its own (use the XLA gather)")
