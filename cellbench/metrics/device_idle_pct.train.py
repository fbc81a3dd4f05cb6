"""1 - (union of the device's operation intervals) / (traced window)."""


def read(ctx):
    t = ctx["trace"]
    if ctx["facts"]["kind"] != "train" or t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
