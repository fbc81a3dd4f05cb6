"""The work model ``gat`` (a configuration names it under ``"work"``).
What one batch of the published GAT NEEDS, from shapes alone: every
frontier row of a layer projected ONCE (PyG's shared ``lin``: a target's
own row is its self-loop's source and its target side alike), ``skip``
once per target, the attention's scores and weighted sums over a target's
``k + 1`` slots (its sampled neighbours and itself), the head; and the
least bytes a sample + gather + conv step has to move.  Never what an
implementation happens to move: a program that projects a target twice, or
copies the projection to put the self-loop beside the neighbours, is read
on the same yardstick."""

import importlib

rgat = importlib.import_module("work.rgat")
frontier, layers, ELEMENT_BYTES = rgat.frontier, rgat.layers, rgat.ELEMENT_BYTES


def project_flops(batch, cfg, backward):
    """The projections alone (what runs under ``qt.model.project``): one
    product per frontier row (sources and targets), ``skip`` per target;
    backward adds the weight gradients and, past the first layer (the
    features are not trained), the input gradients."""
    total = 0
    for i, (targets, sources, d_in) in enumerate(layers(batch, cfg)):
        prod = 2 * (sources + 2 * targets) * d_in * cfg["hidden"]
        total += prod
        if backward:
            total += prod * (2 if i > 0 else 1)
    return total


def project_bytes(batch, cfg, backward):
    """Least bytes of the projections: every operand row read once per
    pass (the first layer's as the table stores them), the two weights
    (``lin``, ``skip``) read once and their gradients written once.  A
    result row need not reach HBM (a kernel may consume it where it is
    made), so none is counted."""
    total = 0
    for i, (targets, sources, d_in) in enumerate(layers(batch, cfg)):
        elem = ELEMENT_BYTES[cfg["feature_dtype"]] if i == 0 else 4
        weights = 4 * 2 * d_in * cfg["hidden"]
        passes = 2 if backward else 1
        total += passes * ((sources + targets) * d_in * elem + weights)
    return total


def attention_flops(batch, cfg):
    """Per slot a score and a weighted sum (2 x hidden each), per target
    its own side of the score."""
    return sum(2 * (2 * (sources + targets) + targets) * cfg["hidden"]
               for targets, sources, _ in layers(batch, cfg))


def attention_bytes(batch, cfg, backward):
    """Least bytes of the attention (what runs under
    ``qt.model.attention``): a softmax over a target's slots needs every
    slot's score before any weight, so the projection (float32, one row a
    slot) is read ONCE in a pass that keeps a target's slots on the chip,
    and once more backward (the weights' gradient is a product with it);
    scores and weights per slot and head, written and read; the layer's
    output, or its cotangent, once."""
    hidden, heads = cfg["hidden"], cfg["heads"]
    passes = 2 if backward else 1
    return passes * sum(
        4 * ((sources + targets) * (hidden + 2 * heads) + targets * hidden)
        for targets, sources, _ in layers(batch, cfg))


def step_flops(batch, cfg, backward):
    """FLOPs of the published GAT on one batch: the projections, the
    attention, the head."""
    hidden = cfg["hidden"]
    head = 2 * batch * hidden * (hidden + cfg["classes"])
    passes = 3 if backward else 1
    return (project_flops(batch, cfg, backward)
            + passes * (attention_flops(batch, cfg) + head))


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
    weights = 4 * (sum(2 * d_in * hidden
                       for _, _, d_in in layers(batch, cfg))
                   + hidden * (hidden + cfg["classes"]))
    passes = 2 if backward else 1
    return (draws * peak["hbm_transaction_bytes"] + t[n] * row_bytes
            + 2 * acts * passes + weights * (7 if backward else 1))


def _least(flops, nbytes, peak):
    f, b = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return max(f, b), ("flops" if f >= b else "bytes")


def least_step_seconds(batch, cfg, peak, backward):
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s.  Returns ``(seconds, which_bound)``."""
    return _least(step_flops(batch, cfg, backward),
                  step_bytes(batch, cfg, peak, backward), peak)


def least_project_seconds(batch, cfg, peak, backward):
    """The same for the projections alone."""
    return _least(project_flops(batch, cfg, backward),
                  project_bytes(batch, cfg, backward), peak)


def least_attention_seconds(batch, cfg, peak, backward):
    """The same for the attention alone."""
    return _least((3 if backward else 1) * attention_flops(batch, cfg),
                  attention_bytes(batch, cfg, backward), peak)
