"""Host milliseconds per traced step inside ``qt.feature.lookup``: how long
the sharded feature store holds the caller's thread (ids and mask put onto
the mesh, the launch of ``jit_qt_dist_lookup``, its bookkeeping), not how
long the device fetches (cellbench/host_spans.py).  None on a program
without the span."""

import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "qt.feature.lookup")
