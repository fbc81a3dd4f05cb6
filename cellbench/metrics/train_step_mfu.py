"""The whole training step's share of the chip's bf16 peak: the FLOPs that
forward and backward of the configuration's model need for one batch (from
shapes, by the configuration's work model, cellbench/work/<name>.py), times
steps per second of the traced window, over the peak."""


def read(ctx):
    f = ctx["facts"]
    if f["kind"] != "train" or not f.get("traced_steps") or not f["traced_s"]:
        return None
    flops = ctx["work"].step_flops(f["batch"], ctx["cfg"], backward=True)
    rate = f["traced_steps"] / f["traced_s"]
    return 100.0 * flops * rate / ctx["peak"]["flops_per_s"]
