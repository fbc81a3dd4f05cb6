"""Device milliseconds per traced step of the forward pass under
``qt.model`` (model apply and loss), from the trace's operations joined with
the program's scope table (cellbench/scope_split.py)."""

import scope_split


def read(ctx):
    return scope_split.scope_ms(ctx, "qt.model", "forward")
