"""Host milliseconds a lookup of the toy, from the program's own host span
``qt.toy.lookup`` as the reduced trace carries it."""


def read(ctx):
    t = ctx["trace"]
    span = t["host_spans"].get("qt.toy.lookup") if t else None
    if ctx["facts"]["kind"] != "lookup" or not span:
        return None
    return 1e3 * span["seconds"] / span["count"]
