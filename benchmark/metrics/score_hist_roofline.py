"""The sharded binomial metric kernel against its roofline: a pass reads
each of a chip's rows once (``p``, ``y``, ``w`` in float32 and the
``valid`` byte: 13 B a row, ``shapes.rows_per_chip`` rows a chip) at the
HBM peak; its device time is the self time of the operations under
``h2o.score.metrics`` and of its own reductions, ``h2o.coll.score.*``.
A pass is one ``score.*`` all-reduce a device plane: the chip's compiler
combines the kernel's two (``score.hist``, ``score.sums``) into one
tuple all-reduce that carries one of the two scopes, so the larger event
count of the two is the passes', summed over the planes as the seconds
are.  Read by benchmark/collectives.py; left out where the slice holds
no metric pass."""

from benchmark import collectives, scopes
from benchmark.peaks import least_seconds

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_rate", "device_trace"

ROW_BYTES = 3 * 4 + 1


def read(ctx):
    got = collectives.slice_ops(ctx)
    if got is None:
        return None
    ops, paths = got
    spent = 0.0
    for name, (seconds, _) in ops.items():
        scope = scopes.scope_of(paths.get(name))
        if scope == "h2o.score.metrics" or scope.startswith("h2o.coll.score."):
            spent += seconds
    events = {}
    for c in collectives.collectives(ctx) or ():
        if c.scope.startswith("h2o.coll.score.") and c.half != "-done":
            events[c.scope] = events.get(c.scope, 0) + c.events
    passes = max(events.values(), default=0)
    if passes == 0 or spent <= 0:
        return None
    rows = int(ctx["shapes"]["rows_per_chip"])
    least = passes * least_seconds(0.0, rows * ROW_BYTES,
                                   ctx["device_kind"])["seconds"]
    return 100.0 * least / spent
