"""Device time of a PART of a layer: a scope nested under another
(``qt.model.project`` under ``qt.model``).  ``scope_split.classify`` takes
the FIRST ``qt.`` name of an ``op_name``, which is right for the readers of
whole layers; a part is the LAST one.  Same join, same table
(``scope_split.scope_table`` and ``instruction_of``): an operation of the
reduced trace belongs to a part when the program's table gives its
instruction an ``op_name`` whose last ``qt.`` name is the part's.  Where
the program has no such scope every reader over this file returns None."""

import scope_split


def part_seconds(ctx, scope):
    """Device seconds a traced step spent under ``scope`` as the innermost
    ``qt.`` name, both passes; None where nothing is found."""
    f, t = ctx["facts"], ctx["trace"]
    if f["kind"] != "train" or t is None or not f.get("traced_steps"):
        return None
    table = scope_split.scope_table()
    if table is None:
        return None
    found = [seconds for line, seconds in t["ops"].items()
             if (scope_split.SCOPE.findall(
                 table.get(scope_split.instruction_of(line)) or "")
                 or [None])[-1] == scope]
    return sum(found) / f["traced_steps"] if found else None
