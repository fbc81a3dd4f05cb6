"""Device milliseconds per traced step under ``qt.model.attention``, both
passes: the scores, the relation's masked softmax and the weighted sum
(cellbench/scope_parts.py)."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.model.attention")
    return None if s is None else 1e3 * s
