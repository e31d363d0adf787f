"""Share of the traced slice's device busy time under no ``h2o.`` scope:
how much of what the chip ran the program has not named.  Left out (not
0) where the program names nothing at all.  Read by benchmark/scopes.py."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "device", "train_rate", "device_trace"


def read(ctx):
    return scopes.share_pct(ctx, scopes.UNSCOPED)
