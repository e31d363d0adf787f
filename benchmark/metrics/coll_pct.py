"""Share of the traced slice's device self time spent in collective
operations (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute, synchronous or as start / done halves), whoever put
them there: the interconnect's part of the window.  Read by
benchmark/collectives.py."""

from benchmark import collectives

UNIT, LAYER, MOVES, SOURCE = "%", "collectives", "train_rate", "device_trace"


def read(ctx):
    colls = collectives.collectives(ctx)
    total = collectives.total_seconds(ctx)
    if colls is None or not total:
        return None
    return 100.0 * sum(c.seconds for c in colls) / total
