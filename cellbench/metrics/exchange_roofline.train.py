"""Roofline share of the exchange: the least time a chip's interconnect
could take for the bytes a step must move between chips
(``least_exchange_seconds`` of the configuration's work model: ids, draws
and rows of the requests the program COUNTED in its checked steps, three
quarters of them owned elsewhere, both directions, over the chip's ICI
bandwidth) over the device seconds a traced step spends under
``qt.exchange``.  None, never 0, where the program has no such scope or
the work model no such function, or the kind counted no requests."""

import scope_parts


def read(ctx):
    s = scope_parts.part_seconds(ctx, "qt.exchange")
    least = getattr(ctx["work"], "least_exchange_seconds", None)
    floor = least and least(ctx["facts"], ctx["cfg"])
    if not s or not floor:
        return None
    return 100.0 * floor / s
