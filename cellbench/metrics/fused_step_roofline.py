"""Roofline share of the fused sample + gather + conv step: the least time
one step could take on this chip (the larger of FLOPs over peak FLOP/s and
least bytes over peak bytes/s, both from shapes) over ALL the time in which
the device ran anything in the traced window, per step.  Whatever programs
a step is made of, and however many, they are all in the denominator."""


def read(ctx):
    f, t = ctx["facts"], ctx["trace"]
    if f["kind"] != "train" or t is None or not f.get("traced_steps"):
        return None
    least, _ = ctx["work"].least_step_seconds(
        f["batch"], ctx["cfg"], ctx["peak"], backward=True)
    per_step = t["busy_s"] / f["traced_steps"]
    return 100.0 * least / per_step if per_step > 0 else None
