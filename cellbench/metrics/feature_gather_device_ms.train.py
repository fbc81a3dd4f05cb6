"""Device milliseconds per traced step of operations under
``qt.feature.gather`` (rows out of the hot table), from the trace's
operations joined with the program's scope table
(cellbench/scope_split.py)."""

import scope_split


def read(ctx):
    return scope_split.scope_ms(ctx, "qt.feature.gather")
