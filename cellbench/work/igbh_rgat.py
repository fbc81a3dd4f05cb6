"""The work model ``igbh_rgat`` (a configuration names it under ``"work"``).
What one batch of MLPerf's R-GAT over a typed tree NEEDS, from shapes
alone: per layer, every relation block's sources and targets projected
once by the relation's ``W_r``, the attention's scores and weighted sums,
the head; the least bytes a sample + gather + conv step has to move; and
the least the typed hop has to move.  Never what an implementation happens
to move.

Like ``work/rgat.py`` it counts EVERY slot of a block, live or not: a
relation block holds ``min(degree, k)`` draws of a target in ``k`` slots,
and here most slots are dead (a paper has one journal at most, against
``k`` of 15, 10 or 5): on one v5e at the cell's size 9.3% of the
836,800 relation slots of a step hold a draw (PERF.md section 5).  So
``train_step_mfu``, ``fused_step_roofline`` and ``rel_project_roofline``
read the work the program does, not the work the batch needs, and a
program that drops the dead slots does less than this counts: its shares
fall while its seeds per second rise.  That debt is the benchmark's
(ROADMAP D1), and a ``benchmark`` PR that counts the live draws
(``relation_live_slots_pct`` says how many) pays it for every work model
at once.
"""

from run import load_named

igbh = load_named("references", "igbh_rgat")
ELEMENT_BYTES = {"float16": 2, "bfloat16": 2, "float32": 4}


def blocks(batch, cfg):
    """Per layer, outermost first: ``(targets, slots, d_in)`` of each
    relation block, from the typed tree's shapes."""
    hops, _ = igbh.tree(batch, tuple(cfg["fanout"]))
    n = len(hops)
    out = []
    for i in range(n):
        k = cfg["fanout"][n - 1 - i]
        d_in = cfg["feature_dim"] if i == 0 else cfg["hidden"]
        out.append([(len(pos), len(pos) * k, d_in)
                    for _, pos, _ in hops[n - 1 - i]])
    return out


def project_flops(batch, cfg, backward):
    """The projections alone (what runs under ``qt.model.project``): each
    block's slots and targets by ``W_r``; backward adds the weight
    gradients and, past the first layer (the features are not trained),
    the input gradients."""
    total = 0
    for i, layer in enumerate(blocks(batch, cfg)):
        prod = sum(2 * (slots + targets) * d_in * cfg["hidden"]
                   for targets, slots, d_in in layer)
        total += prod
        if backward:
            total += prod * (2 if i > 0 else 1)
    return total


def project_bytes(batch, cfg, backward):
    """Least bytes of the projections: every operand row read once per
    pass (the first layer's as the table stores them), each relation's
    weight read once and its gradient written once."""
    total = 0
    r = len(igbh.RELATIONS)
    for i, layer in enumerate(blocks(batch, cfg)):
        elem = ELEMENT_BYTES[cfg["feature_dtype"]] if i == 0 else 4
        d_in = layer[0][2]
        rows = sum(slots + targets for targets, slots, _ in layer)
        passes = 2 if backward else 1
        total += passes * (rows * d_in * elem
                           + 4 * r * d_in * cfg["hidden"])
    return total


def attention_flops(batch, cfg):
    """Per slot a score and a weighted sum (2 x hidden each), per target
    its own side of the score."""
    return sum(2 * (2 * slots + targets) * cfg["hidden"]
               for layer in blocks(batch, cfg)
               for targets, slots, _ in layer)


def attention_bytes(batch, cfg, backward):
    """Least bytes of the attention (what runs under
    ``qt.model.attention``): each block's projection (float32, a row a
    slot) read once a pass, a score and a weight per slot and head written
    and read, the targets' side and the block's output once."""
    hidden, heads = cfg["hidden"], cfg["heads"]
    passes = 2 if backward else 1
    return passes * sum(
        4 * (slots * (hidden + 2 * heads) + 2 * targets * hidden)
        for layer in blocks(batch, cfg) for targets, slots, _ in layer)


def step_flops(batch, cfg, backward):
    """FLOPs of the model on one batch: the projections, the attention,
    the head."""
    head = 2 * batch * cfg["hidden"] * cfg["classes"]
    passes = 3 if backward else 1
    return (project_flops(batch, cfg, backward)
            + passes * (attention_flops(batch, cfg) + head))


def hop_bytes(batch, cfg, peak):
    """Least bytes of the typed hop: per block target its window (a pair
    of int32, one transaction), per slot one transaction for its draw, and
    the frontier's id and validity written."""
    t = peak["hbm_transaction_bytes"]
    return sum(targets * t + slots * (t + 4 + 1)
               for layer in blocks(batch, cfg)
               for targets, slots, _ in layer)


def step_bytes(batch, cfg, peak, backward):
    """Least HBM bytes of one sample + gather + conv step: the hop's, every
    gathered row once, every layer's output written and read once per
    pass, the weights (and Adam's state) once."""
    _, ptype = igbh.tree(batch, tuple(cfg["fanout"]))
    hidden = cfg["hidden"]
    row_bytes = cfg["feature_dim"] * ELEMENT_BYTES[cfg["feature_dtype"]]
    layers = blocks(batch, cfg)
    rows, t = batch, len(ptype)         # the head's input, each layer's
    for layer in layers:                # output: the frontier before its
        t -= sum(s for _, s, _ in layer)    # hop
        rows += t
    acts = 4 * hidden * rows
    r = len(igbh.RELATIONS)
    weights = 4 * (sum(r * layer[0][2] * hidden for layer in layers)
                   + hidden * cfg["classes"])
    passes = 2 if backward else 1
    return (hop_bytes(batch, cfg, peak) + len(ptype) * row_bytes
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


def least_typed_hop_seconds(batch, cfg, peak, backward=True):
    """The same for the typed hop (no FLOPs to speak of; the same in
    either pass, for it runs once a step)."""
    return _least(0, hop_bytes(batch, cfg, peak), peak)
