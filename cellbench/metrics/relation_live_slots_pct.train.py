"""Share of the relation blocks' draw slots that hold a draw: the
program's own counts over the checked steps (the sampler's
``sampler_relation_live_slots_total`` over ``sampler_relation_slots_total``,
all relations, as ``kinds/train_relations.py`` reads them).  A relation
block has ``k`` slots a target and a target draws ``min(its degree under
the relation, k)``, so a relation a target has few edges of (a paper's
journal) leaves most of its slots dead.  None where the kind counted
nothing."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("relation_slots"):
        return None
    return 100.0 * f["relation_live_slots"] / f["relation_slots"]
