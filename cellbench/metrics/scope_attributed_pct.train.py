"""Share of all operation seconds of the traced window that fell to any
``qt.`` scope of the program's table (cellbench/scope_split.py): the check
on the five ``*_device_ms.train`` metrics, which split only this share."""

import scope_split


def read(ctx):
    s = scope_split.split(ctx)
    return None if s is None else s["attributed_pct"]
