"""Share of the traced slice's device busy time spent descending trees
that are already built: ``h2o.tree.predict`` (the F update after each
tree) and ``h2o.score.*`` (the per-block scorer and the final scoring:
models/tree/driver.py, shared_tree.forest_score, the metric kernels).
Read by benchmark/scopes.py."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "tree driver", "train_rate", \
    "device_trace"


def read(ctx):
    return scopes.share_pct(ctx, "h2o.tree.predict", "h2o.score.")
