"""The work model ``sage`` (a configuration names it under ``"work"``).
What one batch NEEDS, from shapes alone: the FLOPs of GraphSAGE's
forward and backward passes, and the least bytes a sample + gather + conv
step has to move.  Never what an implementation happens to move, so a PR
that replaces a kernel is read on the same yardstick."""


def model_dims(cfg):
    return ([cfg["feature_dim"]] + [cfg["hidden"]] * (cfg["num_layers"] - 1)
            + [cfg["classes"]])


def frontier(batch, fanout):
    """Targets per hop, seeds outward, with no deduplication (the widest a
    frontier can be): ``[B, B(1+f1), B(1+f1)(1+f2), ...]``."""
    t = [batch]
    for f in fanout:
        t.append(t[-1] * (1 + f))
    return t


def step_flops(batch, cfg, backward):
    """FLOPs of GraphSAGE on one batch: two products per layer and the
    neighbour sums; backward adds the weight gradients and, past the first
    layer (the features are not trained), the input gradients."""
    dims = model_dims(cfg)
    t = frontier(batch, cfg["fanout"])
    n = len(cfg["fanout"])
    total = 0
    for i in range(n):                      # conv i, outermost first
        targets, k = t[n - 1 - i], cfg["fanout"][n - 1 - i]
        prod = 4 * targets * dims[i] * dims[i + 1]
        agg = targets * k * dims[i]
        total += prod + agg
        if backward:
            total += prod + (prod + agg if i > 0 else 0)
    return total


def step_bytes(batch, cfg, peak, backward):
    """Least HBM bytes of one sample + gather + conv step: one transaction
    per draw, every gathered row once, every layer's output written and
    read once per pass, the weights (and Adam's state) once."""
    dims = model_dims(cfg)
    t = frontier(batch, cfg["fanout"])
    n = len(cfg["fanout"])
    draws = sum(t[i] * cfg["fanout"][i] for i in range(n))
    row_bytes = cfg["feature_dim"] * (2 if cfg["feature_dtype"] ==
                                      "bfloat16" else 4)
    acts = sum(t[n - 1 - i] * dims[i + 1] * 4 for i in range(n))
    weights = sum(2 * a * b + b for a, b in zip(dims[:-1], dims[1:])) * 4
    passes = 2 if backward else 1
    return (draws * peak["hbm_transaction_bytes"] + t[n] * row_bytes
            + 2 * acts * passes + weights * (7 if backward else 1))


def least_step_seconds(batch, cfg, peak, backward):
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s.  Returns ``(seconds, which_bound)``."""
    f = step_flops(batch, cfg, backward) / peak["flops_per_s"]
    b = step_bytes(batch, cfg, peak, backward) / peak["hbm_bytes_per_s"]
    return max(f, b), ("flops" if f >= b else "bytes")
