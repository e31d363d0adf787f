"""Of the traced slice's collective time, the share under no
``h2o.coll.`` scope: the collectives the partitioner inserted (an
operand gathered, a reduction completed) rather than the program's own
helpers.  Left out where the slice holds no collective.  Read by
benchmark/collectives.py."""

from benchmark import collectives

UNIT, LAYER, MOVES, SOURCE = "%", "collectives", "train_rate", "device_trace"


def read(ctx):
    colls = collectives.collectives(ctx)
    if not colls:
        return None
    spent = sum(c.seconds for c in colls)
    if spent <= 0:
        return None
    return 100.0 * sum(c.seconds for c in colls
                       if not c.scope.startswith("h2o.coll.")) / spent
