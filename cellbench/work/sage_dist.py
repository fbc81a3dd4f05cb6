"""The work model ``sage_dist`` (a configuration names it under
``"work"``): what one step of the host NEEDS, stated as ONE chip's share:
the model's work from shapes alone, the exchange's from the requests the
program counted.

The readers divide by ONE chip's peak (``ctx["peak"]``) and by the MEAN
busy time of the cell's devices, so ``step_flops`` and
``least_step_seconds`` return a ``1 / ranks`` share of the host's step
(``batch`` is the host's: ``ranks`` x the rank's): ``train_step_mfu``
then reads as a share of the FOUR chips' peak and ``fused_step_roofline``
as the least time a chip could take for its rank's batch over the time it
was busy.  The model's work is ``work/sage.py``'s, loaded by name.

``least_exchange_seconds`` is this deployment's own: the bytes that must
leave and enter a chip in a step because three quarters of the rows are
another chip's, from the requests the program counted, over the chip's
interconnect.
"""

from run import load_named

sage = load_named("work", "sage")

# Google Cloud documentation, "TPU v5e" (system architecture): 1,600 Gbit/s
# of inter-chip interconnect bandwidth per chip
ICI_BYTES_PER_S = 1600e9 / 8


def step_flops(batch, cfg, backward):
    """A chip's share: one rank's batch."""
    return sage.step_flops(batch // cfg["ranks"], cfg, backward)


def least_step_seconds(batch, cfg, peak, backward):
    """A chip's share: one rank's batch out of its own memory.  (What the
    exchange adds is ``least_exchange_seconds``'s, not this floor's.)"""
    return sage.least_step_seconds(batch // cfg["ranks"], cfg, peak,
                                   backward)


def exchange_bytes(facts, cfg):
    """Bytes that must leave AND enter one chip in a step: for every
    target a rank asked a hop of, its id out and its ``k`` draws (4 B
    each) back; for every frontier row it asked of the feature store, its
    id out and the row back.  Requests are COUNTED, not reckoned: the
    program's own live slots of the checked steps, by layer
    (``kinds/train_dist.py``: ``exchange_live_hops``, ``exchange_live_rows``,
    summed over steps and ranks), so a dead slot and an empty bucket add
    nothing.  Of a rank's requests ``(ranks - 1) / ranks`` are held to
    cross the interconnect, which is what ids spread evenly over the
    ranges give (the counters do not tell a request to oneself from one to
    another rank: PERF.md section 7); both directions are counted, a chip
    answers as much as it asks.  None where the kind counted nothing."""
    hops = facts.get("exchange_live_hops")
    if not hops or len(hops) != len(cfg["fanout"]):
        return None
    ranks = cfg["ranks"]
    row_bytes = cfg["feature_dim"] * (2 if cfg["feature_dtype"] ==
                                      "bfloat16" else 4)
    asked = sum(live * (4 + 4 * k) for live, k in zip(hops, cfg["fanout"]))
    asked += facts["exchange_live_rows"] * (4 + row_bytes)
    a_rank_a_step = asked / (facts["checked_steps"] * ranks)
    return 2 * (ranks - 1) / ranks * a_rank_a_step


def least_exchange_seconds(facts, cfg):
    b = exchange_bytes(facts, cfg)
    return None if b is None else b / ICI_BYTES_PER_S
