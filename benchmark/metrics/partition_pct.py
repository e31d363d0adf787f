"""Share of the traced slice's device busy time spent putting a deep
level's rows in node order: the scope ``h2o.tree.partition`` (tree
engine: ops/histogram.py ``histogram_window_traced``, the sort of the
rows by node, the node offsets, and each block's gather of its rows'
bins and statistics).  Read by benchmark/scopes.py; a program without
the scope leaves the metric out."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "tree engine", "train_rate", \
    "device_trace"


def read(ctx):
    s = scopes.window_scopes(ctx)
    if s is None or "h2o.tree.partition" not in s:
        return None
    return scopes.share_pct(ctx, "h2o.tree.partition")
