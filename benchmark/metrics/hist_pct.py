"""Share of the traced slice's device busy time spent on the histogram:
the scopes ``h2o.tree.hist.*`` (one-hot build, bucket mapping, operand
relayout, contraction, sibling subtraction, dequantize) and the table's
cross-chip combine ``h2o.coll.hist.table`` (kernels: ops/histogram.py,
ops/hist_pallas.py).  Read by benchmark/scopes.py."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_rate", "device_trace"


def read(ctx):
    return scopes.share_pct(ctx, "h2o.tree.hist.", "h2o.coll.hist.table")
