"""The kind ``train_typed``: the kind ``train`` over a typed graph.  The
window, the loader thread, the checked steps and the comparison are
``kinds/train.run``'s, called, not copied; ``facts["kind"]`` stays
``"train"``, so every reader of a training cell reads this one.  What a
typed deployment changes is bound around that call:

  * seeds are drawn among the labelled type, which comes first in the id
    space (``datagen.train_order`` over ``papers`` ids);
  * the reference needs the frontier's ids (a node's type is its id's
    range) and the configuration, which ``train`` hands to neither
    ``check_sample`` nor ``train_follow``: :class:`Bound` keeps both;
  * the model carries state that is no parameter (BatchNorm's running
    averages): :class:`Watched` reads it off the program after the checked
    steps, the reference follows it, and ``stats_gap`` compares the two;
  * program and reference no longer run the same instructions (a grouped
    kernel against five whole products), so the first gradient is also
    compared as a vector, ``grad_dist``: the number that tells the stated
    precision from one below it in this cell (PERF.md, PR 30).
"""

from run import load_named

train = load_named("kinds", "train")
FAULTS = train.FAULTS


class Bound:
    """The reference bound to the configuration and to the frontiers the
    replay reads back, under the names ``kinds/train`` calls."""

    def __init__(self, ref, cfg, data):
        self.ref, self.cfg, self.data = ref, cfg, data
        self.offsets = ref.type_offsets(cfg)
        self.frontiers = []     # (n_id, n_mask) per checked step
        self.states = []        # the model state each train_follow ended in
        self.grad_dist = None

    def leaf_norm_gap(self, prog, ref, skip_below=None):
        """``train`` compares the first gradient (no ``skip_below``), then
        the parameters' change; the gradient is measured as a vector too."""
        if skip_below is None:
            self.grad_dist = self.ref.tree_distance(prog, ref)
        return self.ref.leaf_norm_gap(prog, ref, skip_below)

    def check_sample(self, indptr, indices, fanout, seeds, n_id, n_mask,
                     layers):
        self.frontiers.append((n_id, n_mask))
        return self.ref.check_sample(indptr, indices, fanout, seeds, n_id,
                                     n_mask, layers, self.offsets)

    def train_follow(self, params0, batches, cfg, matmul, fault=None):
        batches = [dict(b, n_id=n_id, n_mask=n_mask)
                   for b, (n_id, n_mask) in zip(batches, self.frontiers)]
        losses, grad, params, state = self.ref.train_follow(
            params0, self.data["model_state"], batches, self.cfg, matmul,
            fault)
        self.states.append(state)
        return losses, grad, params


class Watched:
    """The program, with the model state read off after the ``n``-th call
    of its step (the last checked one)."""

    def __init__(self, prog, n):
        self.prog, self.n, self.model_state = prog, n, None

    def __getattr__(self, name):
        return getattr(self.prog, name)

    def fused_train_step(self):
        import jax
        import numpy as np

        state, inner = self.prog.fused_train_step()
        calls = 0

        def step(state, seeds, labels, mask, key):
            nonlocal calls
            state, loss = inner(state, seeds, labels, mask, key)
            calls += 1
            if calls == self.n:
                self.model_state = jax.tree_util.tree_map(
                    np.asarray, state.model_state)
            return state, loss

        return state, step


def run(prog, ref, cfg, traffic, data, seed, seconds, tracer, watch):
    bound = Bound(ref, cfg, data)
    watched = Watched(prog, traffic["checked_steps"])
    end_to_end, facts, (replay, numbers) = train.run(
        watched, bound, dict(cfg, nodes=cfg["papers"]), traffic, data, seed,
        seconds, tracer, watch)

    def numbers_typed(replayed, matmul, fault=None, stand_in=None):
        """``train``'s numbers and ``stats_gap``: the running averages
        after the checked steps against the reference's (or, with ``fault``
        / ``stand_in``, the altered reference's in the program's place)."""
        bound.states.clear()
        got = numbers(replayed, matmul, fault=fault, stand_in=stand_in)
        theirs = bound.states[0]
        ours = (bound.states[1] if len(bound.states) > 1
                else watched.model_state)
        got["stats_gap"] = ref.leaf_norm_gap(ours, theirs)
        got["grad_dist"] = bound.grad_dist
        return got

    return end_to_end, facts, (replay, numbers_typed)
