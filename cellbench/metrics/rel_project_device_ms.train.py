"""Device milliseconds per traced step under ``qt.model.project``, both
passes: the grouped per-relation projections of the sources with their
sort and permutes, the targets' ``W_dst`` and ``skip``
(cellbench/scope_parts.py)."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.model.project")
    return None if s is None else 1e3 * s
