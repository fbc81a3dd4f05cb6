"""Host milliseconds per traced step inside ``qt.sampler.sample``: how long
the sharded sampler holds the caller's thread (its arguments put onto the
mesh, the launch of ``jit_qt_dist_sample``, its bookkeeping), not how long
the device samples (cellbench/host_spans.py).  None on a program without
the span."""

import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "qt.sampler.sample")
