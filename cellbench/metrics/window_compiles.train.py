"""Programs built (compiled, or read from the persistent cache) inside the
measured window, from JAX's monitoring events.  Should be 0."""


def read(ctx):
    if ctx["facts"]["kind"] != "train":
        return None
    return ctx["facts"]["window_compiles"]
