"""The kind ``train_relations``: the kind ``train`` over a typed tree drawn
per relation.  The window, the loader thread, the checked steps and the
comparison are ``kinds/train.run``'s, called, not copied;
``facts["kind"]`` stays ``"train"``, so every reader of a training cell
reads this one.  What a typed draw changes is bound around that call:

  * seeds are drawn among the labelled type, which comes first in the id
    space (``datagen.train_order`` over ``papers`` ids);
  * the reference needs the configuration (the schema) to hold a draw to
    the host CSR relation by relation, which ``train`` does not hand to
    ``check_sample``: :class:`Bound` keeps it, and the CSR's degree of
    every node under every source type, worked out once;
  * program and reference no longer run the same instructions (a slice
    and one product per relation against a gather and a scatter-add by
    position), so the first gradient is also compared as a vector,
    ``grad_dist``, as ``kinds/train_typed`` does; the model carries no
    state, so there is no ``stats_gap``;
  * rounding the products' results to bfloat16 hardly moves the gradient
    and the loss, which are sums that average it away, so the first
    step's logits are compared too, element by element: ``logit_dist``,
    the model's forward at the seed's weights over the first checked
    draw with no dropout (``Program.first_logits``) against the
    reference's (``first_logits``), ``||prog - ref|| / ||ref||``; the
    number that tells the stated precision from one below it here;
  * the sampler counts, per relation, the slots of its blocks and the live
    draws in them (``sampler_relation_slots_total``,
    ``sampler_relation_live_slots_total``); what the replay of the checked
    steps added to them goes to ``facts`` for ``relation_live_slots_pct``.
"""

import numpy as np

from run import load_named, log

train = load_named("kinds", "train")
FAULTS = train.FAULTS


class Bound:
    """The reference bound to the configuration, under the names
    ``kinds/train`` calls."""

    def __init__(self, ref, cfg):
        self.ref, self.cfg = ref, cfg
        self.train_follow = ref.train_follow
        self.degrees = None
        self.grad_dist = None

    def leaf_norm_gap(self, prog, ref, skip_below=None):
        """``train`` compares the first gradient (no ``skip_below``), then
        the parameters' change; the gradient is measured as a vector too,
        and logged leaf by leaf: each leaf's distance and its share of the
        whole's squared one."""
        if skip_below is None:
            self.grad_dist = self.ref.tree_distance(prog, ref)
            log("first gradient by leaf: " + leaf_distances(prog, ref))
        return self.ref.leaf_norm_gap(prog, ref, skip_below)

    def check_sample(self, indptr, indices, fanout, seeds, n_id, n_mask,
                     layers):
        if self.degrees is None:
            self.degrees = self.ref.relation_degrees(
                indptr, indices, self.ref.type_offsets(self.cfg))
        return self.ref.check_sample(indptr, indices, fanout, seeds, n_id,
                                     n_mask, layers, self.cfg, self.degrees)


class Watched:
    """The program, with the seeds and key of its step's first call kept:
    the first checked step's."""

    def __init__(self, prog):
        self.prog, self.first = prog, None

    def __getattr__(self, name):
        return getattr(self.prog, name)

    def fused_train_step(self):
        state, inner = self.prog.fused_train_step()

        def step(state, seeds, labels, mask, key):
            if self.first is None:
                self.first = (seeds, key)
            return inner(state, seeds, labels, mask, key)

        return state, step


def leaf_distances(prog, ref):
    """``path ||p - r|| / ||r|| (share of sum ||p - r||^2)`` per leaf."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(ref)[0]
    mine = jax.tree_util.tree_leaves(prog)
    d2 = [float(np.sum((np.asarray(p, np.float64) - np.asarray(r)) ** 2))
          for p, (_, r) in zip(mine, flat)]
    total = sum(d2) or 1.0
    return ", ".join(
        f"{jax.tree_util.keystr(path)} "
        f"{np.sqrt(d) / max(float(np.linalg.norm(r)), 1e-30):.3g} "
        f"({100 * d / total:.1f}%)"
        for (path, r), d in zip(flat, d2))


def run(prog, ref, cfg, traffic, data, seed, seconds, tracer, watch):
    bound = Bound(ref, cfg)
    watched = Watched(prog)
    end_to_end, facts, (replay, numbers) = train.run(
        watched, bound, dict(cfg, nodes=cfg["papers"]), traffic, data, seed,
        seconds, tracer, watch)
    logits = {}

    def replay_relations():
        seeds, key = watched.first
        logits["program"] = prog.first_logits(
            np.asarray(seeds), prog.step_keys(key)[0])
        slots0, live0 = prog.relation_counts()
        replayed = replay()
        slots, live = prog.relation_counts()
        facts["relation_slots"] = slots - slots0
        facts["relation_live_slots"] = live - live0
        return replayed

    def numbers_relations(replayed, matmul, fault=None, stand_in=None):
        """``train``'s numbers, ``grad_dist`` and ``logit_dist``; with
        ``stand_in`` the reference at that precision takes the program's
        place in the logits too, and with ``fault`` the reference so
        altered, whose forward at the seed's weights is the reference's."""
        got = numbers(replayed, matmul, fault=fault, stand_in=stand_in)
        got["grad_dist"] = bound.grad_dist
        first = replayed[0][0]

        def forward(precision):
            return ref.first_logits(data["params"], first["rows"],
                                    first["layers"], cfg, precision)

        theirs = forward(matmul)
        ours = (logits["program"] if fault is None and stand_in is None
                else forward(stand_in or matmul))
        got["logit_dist"] = ref.tree_distance(ours, theirs)
        return got

    return end_to_end, facts, (replay_relations, numbers_relations)
