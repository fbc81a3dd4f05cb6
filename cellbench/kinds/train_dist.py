"""The kind ``train_dist``: the kind ``train`` over a graph and a table
divided over the ranks of one host.  The window, the loader thread, the
checked steps and the comparison are ``kinds/train.run``'s, called, not
copied; ``facts["kind"]`` stays ``"train"``, so every reader of a training
cell reads this one.  What a sharded deployment changes is bound around
that call:

  * a step of the host takes ``ranks`` x ``batch`` seeds of
    ``datagen.train_order``'s epoch order (one row at the host's batch),
    cut into the ranks' batches in order; ``train_seeds_per_s`` counts them
    all and ``facts["batch"]`` is the host's;
  * what the replay reads back is stacked over the ranks, and the
    reference's ``check_sample`` / ``train_follow`` take it so; the rows
    the feature store answers with are read back too and held to the host
    table (:class:`Bound`);
  * the exchange may drop nothing: the sampler's and the feature store's
    overflow counters are read after each checked step and once more after
    the window (:class:`Watched`), their sum is ``exchange_drops`` (limit
    0), and the checked steps' slot counts (shipped, and of those holding
    a request; the latter by layer too: each hop's targets, the feature
    store's rows) go to ``facts`` for the exchange's readers.

``FAULTS`` are the kind ``train``'s, planted in program or reference.  One
more exists only because the ranks exchange, and only the reference plants
it (``numbers(..., fault=EXCHANGE_FAULT)``; no test plants it in the
program): the ranks' average left out, the step taken from rank 0's loss
and gradient alone.
"""

from run import load_named

train = load_named("kinds", "train")
FAULTS = train.FAULTS
EXCHANGE_FAULT = "rank0_alone"


class Bound:
    """The reference under the names ``kinds/train`` calls, with the
    program's looked-up rows read back beside each draw."""

    def __init__(self, ref, prog, data):
        self.ref, self.prog, self.data = ref, prog, data  # prog: Watched
        self.leaf_norm_gap = ref.leaf_norm_gap
        self.train_follow = ref.train_follow

    def check_sample(self, indptr, indices, fanout, seeds, n_id, n_mask,
                     layers):
        bad, edges = self.ref.check_sample(indptr, indices, fanout, seeds,
                                           n_id, n_mask, layers)
        bad["bad_rows"] = self.ref.check_rows(
            self.data["features"], n_id, n_mask,
            self.prog.replay_rows(n_id, n_mask))
        self.prog.read_drops()      # the replay's own exchanges count too
        return bad, edges


class Watched:
    """The program, with the exchange's counters read after each of the
    first ``n`` calls of its step (the checked ones: reading them waits
    for the step) and once more when asked."""

    def __init__(self, prog, n):
        self.prog, self.n = prog, n
        self.drops, self.slots, self.live = 0, 0, 0
        self.live_hops, self.live_rows = None, 0

    def __getattr__(self, name):
        return getattr(self.prog, name)

    def read_drops(self):
        self.drops += self.prog.exchange_drops()

    def fused_train_step(self):
        state, inner = self.prog.fused_train_step()
        calls = 0

        def step(state, seeds, labels, mask, key):
            nonlocal calls
            out = inner(state, seeds, labels, mask, key)
            calls += 1
            if calls <= self.n:
                self.read_drops()
                slots, live = self.prog.exchange_slots()
                self.slots += slots
                self.live += live
                by = self.prog.exchange_live()
                self.live_hops = [a + b for a, b in zip(
                    self.live_hops or [0] * len(by["hops"]), by["hops"])]
                self.live_rows += by["rows"]
            return out

        return state, step


def run(prog, ref, cfg, traffic, data, seed, seconds, tracer, watch):
    watched = Watched(prog, traffic["checked_steps"])
    host = dict(cfg, batch=cfg["batch"] * cfg["ranks"])
    end_to_end, facts, (replay, numbers) = train.run(
        watched, Bound(ref, watched, data), host, traffic, data, seed, seconds,
        tracer, watch)
    watched.read_drops()        # the window's last step
    facts["exchange_slots"] = watched.slots
    facts["exchange_live_slots"] = watched.live
    facts["exchange_live_hops"] = watched.live_hops
    facts["exchange_live_rows"] = watched.live_rows
    facts["checked_steps"] = traffic["checked_steps"]
    facts["ranks"] = cfg["ranks"]

    def replay_dist():
        replayed = replay()
        facts["exchange_drops"] = watched.drops
        return replayed

    def numbers_dist(replayed, matmul, fault=None, stand_in=None):
        got = numbers(replayed, matmul, fault=fault, stand_in=stand_in)
        got["exchange_drops"] = float(watched.drops)
        return got

    return end_to_end, facts, (replay_dist, numbers_dist)
