"""Roofline share of the relation projections: the least time the chip
could take for them (``least_project_seconds`` of the configuration's work
model: one product per source, ``skip`` and ``W_dst`` per target, forward
and backward; FLOPs or bytes, whichever is larger) over the device seconds
a traced step spends under ``qt.model.project``.  None, never 0, where the
program has no such scope or the work model no such function."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.model.project")
    least = getattr(ctx["work"], "least_project_seconds", None)
    if not s or least is None:
        return None
    seconds, _ = least(ctx["facts"]["batch"], ctx["cfg"], ctx["peak"],
                       backward=True)
    return 100.0 * seconds / s
