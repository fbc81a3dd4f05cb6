"""Roofline share of the typed hop: the least time the chip could take for
it (``least_typed_hop_seconds`` of the configuration's work model: per
relation block target its window, per slot its draw, the frontier
written; bytes-bound) over the device seconds a traced step spends under
``qt.sampler`` and its hops (``sampler_device_ms.train``'s).  None, never
0, where the program has no such scope or the work model no such
function."""

import scope_split


def read(ctx):
    ms = scope_split.scope_ms(ctx, "qt.sampler")
    least = getattr(ctx["work"], "least_typed_hop_seconds", None)
    if not ms or least is None:
        return None
    seconds, _ = least(ctx["facts"]["batch"], ctx["cfg"], ctx["peak"],
                       backward=True)
    return 100.0 * 1e3 * seconds / ms
