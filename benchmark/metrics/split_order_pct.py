"""Share of the traced slice's device busy time spent ordering a node's
bins for the split search: the scope ``h2o.tree.split.order`` (tree
engine: models/tree/shared_tree.py ``find_splits``: the ``argsort`` of
every (leaf, column)'s bins by mean gradient and the takes that follow
it; real work only where a column is an enum).  Read by
benchmark/scopes.py; a program that names no such scope leaves the
metric out."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "tree engine", "train_rate", \
    "device_trace"

_SCOPE = "h2o.tree.split.order"


def read(ctx):
    named = scopes.window_scopes(ctx) or {}
    mine = [s for name, s in named.items() if name.startswith(_SCOPE)]
    if not mine:
        return None
    return 100.0 * sum(mine) / sum(named.values())
