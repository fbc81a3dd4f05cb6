"""Share of the exchange's request slots that held a request: the
program's own counts over the checked steps, sampler and feature store
together (``dist_exchange_live_slots_total`` over
``dist_exchange_slots_total``, as ``kinds/train_dist.py`` reads them).  At
exact caps a bucket per destination is as long as the whole frontier, so
at most one slot in ``ranks`` is live.  None where the kind counted
nothing."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("exchange_slots"):
        return None
    return 100.0 * f["exchange_live_slots"] / f["exchange_slots"]
