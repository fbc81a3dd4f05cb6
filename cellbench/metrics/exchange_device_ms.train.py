"""Device milliseconds per traced step under ``qt.exchange`` as the LAST
``qt.`` name (``qt.sampler.hop<n>/qt.exchange``, ``qt.feature.gather/
qt.exchange``): owner search, slot ranks, request buckets, both
``all_to_all``s and the unpacking of a sharded sampler and feature store,
mean over the cell's devices (cellbench/scope_parts.py).  None on a program
without the scope."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.exchange")
    return None if s is None else 1e3 * s
