"""Host milliseconds per traced step inside ``qt.step.train``: the call of
the jitted train step (``jit_qt_fused_train_step``, ``jit_qt_dp_train_
step``) until it returns to Python, which is how long the launch holds the
caller's thread; it grows where the runtime holds the caller because the
device's queue is full (cellbench/host_spans.py).  None on a program
without the span."""

import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "qt.step.train")
