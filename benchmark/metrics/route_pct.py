"""Share of the traced slice's device busy time spent routing rows to
their child leaf while a tree grows: the scope ``h2o.tree.route`` (tree
engine: models/tree/jit_engine.py, the "route rows" block of both
builders).  Read by benchmark/scopes.py."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "tree engine", "train_rate", \
    "device_trace"


def read(ctx):
    return scopes.share_pct(ctx, "h2o.tree.route")
