"""The histogram table's cross-chip reduction against the interconnect's
roofline: the least time of the ``h2o.coll.hist.table`` all-reduces in
the traced slice (a ring moves 2 (n - 1) / n of each call's payload
through a chip's interconnect; the payload is the table's bytes, what
the byte ledger notes for the call) over their device time (every half
of them), in per cent.  Peak: benchmark/ici_peaks.py.  Read by
benchmark/collectives.py."""

from benchmark import collectives
from benchmark.ici_peaks import ring_all_reduce_seconds

UNIT, LAYER, MOVES, SOURCE = "%", "collectives", "train_rate", "device_trace"


def read(ctx):
    colls = collectives.collectives(ctx)
    chips = int(ctx["shapes"].get("chips", 1))
    if not colls or chips <= 1:
        return None
    mine = [c for c in colls if c.scope == "h2o.coll.hist.table"]
    spent = sum(c.seconds for c in mine)
    least = sum(c.events * ring_all_reduce_seconds(
        c.nbytes, chips, ctx["device_kind"])
        for c in mine if c.half != "-done")
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
