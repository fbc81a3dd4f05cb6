"""The work model ``rgat`` (a configuration names it under ``"work"``).
What one batch of the published R-GAT NEEDS, from shapes alone: every
source position projected ONCE (under its edge's relation), every target
once each for ``skip`` and ``W_dst``, the attention's scores and weighted
sums, the head; and the least bytes a sample + gather + conv step has to
move.  Never what an implementation happens to move: a program that
projects every source under all five relations, or every target's
``W_dst`` under all five, is read on the same yardstick."""

import importlib

frontier = importlib.import_module("work.sage").frontier    # positions a hop

ELEMENT_BYTES = {"float16": 2, "bfloat16": 2, "float32": 4}


def layers(batch, cfg):
    """``(targets, sources, d_in)`` of each layer, outermost first: a
    target has ``fanout`` source positions."""
    t = frontier(batch, cfg["fanout"])
    n = len(cfg["fanout"])
    return [(t[n - 1 - i], t[n - 1 - i] * cfg["fanout"][n - 1 - i],
             cfg["feature_dim"] if i == 0 else cfg["hidden"])
            for i in range(n)]


def project_flops(batch, cfg, backward):
    """The projections alone (what runs under ``qt.model.project``): one
    product per source, ``skip`` and ``W_dst`` per target; backward adds
    the weight gradients and, past the first layer (the features are not
    trained), the input gradients."""
    total = 0
    for i, (targets, sources, d_in) in enumerate(layers(batch, cfg)):
        prod = 2 * (sources + 2 * targets) * d_in * cfg["hidden"]
        total += prod
        if backward:
            total += prod * (2 if i > 0 else 1)
    return total


def project_bytes(batch, cfg, backward):
    """Least bytes of the projections: every operand row read once per
    pass (the first layer's as the table stores them), the weights read
    once and their gradients written once.  A result row need not reach
    HBM (a kernel may consume it where it is made), so none is counted."""
    total = 0
    for i, (targets, sources, d_in) in enumerate(layers(batch, cfg)):
        elem = ELEMENT_BYTES[cfg["feature_dtype"]] if i == 0 else 4
        weights = 4 * (2 * cfg["num_relations"] + 1) * d_in * cfg["hidden"]
        passes = 2 if backward else 1
        total += passes * ((sources + targets) * d_in * elem + weights)
    return total


def step_flops(batch, cfg, backward):
    """FLOPs of the published R-GAT on one batch: the projections, per
    edge the score and the weighted sum (2 x hidden each), the head."""
    hidden = cfg["hidden"]
    attention = sum(2 * (2 * sources + targets) * hidden
                    for targets, sources, _ in layers(batch, cfg))
    head = 2 * batch * hidden * (hidden + cfg["classes"])
    passes = 3 if backward else 1
    return (project_flops(batch, cfg, backward)
            + passes * (attention + head))


def step_bytes(batch, cfg, peak, backward):
    """Least HBM bytes of one sample + gather + conv step: one transaction
    per draw, every gathered row once, every layer's output written and
    read once per pass, the weights (and Adam's state) once."""
    t = frontier(batch, cfg["fanout"])
    n = len(cfg["fanout"])
    hidden = cfg["hidden"]
    draws = sum(t[i] * cfg["fanout"][i] for i in range(n))
    row_bytes = cfg["feature_dim"] * ELEMENT_BYTES[cfg["feature_dtype"]]
    acts = 4 * (sum(targets for targets, _, _ in layers(batch, cfg)) * hidden
                + batch * (hidden + cfg["classes"]))
    weights = 4 * (sum((2 * cfg["num_relations"] + 1) * d_in * hidden
                       for _, _, d_in in layers(batch, cfg))
                   + hidden * (hidden + cfg["classes"]))
    passes = 2 if backward else 1
    return (draws * peak["hbm_transaction_bytes"] + t[n] * row_bytes
            + 2 * acts * passes + weights * (7 if backward else 1))


def least_step_seconds(batch, cfg, peak, backward):
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s.  Returns ``(seconds, which_bound)``."""
    f = step_flops(batch, cfg, backward) / peak["flops_per_s"]
    b = step_bytes(batch, cfg, peak, backward) / peak["hbm_bytes_per_s"]
    return max(f, b), ("flops" if f >= b else "bytes")


def least_project_seconds(batch, cfg, peak, backward):
    """The same for the projections alone."""
    f = project_flops(batch, cfg, backward) / peak["flops_per_s"]
    b = project_bytes(batch, cfg, backward) / peak["hbm_bytes_per_s"]
    return max(f, b), ("flops" if f >= b else "bytes")
