"""Roofline share of the attention: the least time the chip could take for
it (``least_attention_seconds`` of the configuration's work model: the
projection read once per pass, scores and weights per slot, the layer's
output; FLOPs or bytes, whichever is larger) over the device seconds a
traced step spends under ``qt.model.attention``, both passes.  None, never
0, where the program has no such scope or the work model no such
function."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.model.attention")
    least = getattr(ctx["work"], "least_attention_seconds", None)
    if not s or least is None:
        return None
    seconds, _ = least(ctx["facts"]["batch"], ctx["cfg"], ctx["peak"],
                       backward=True)
    return 100.0 * seconds / s
