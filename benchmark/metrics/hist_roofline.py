"""The histogram kernel's share of its roofline, from the device trace.

The kernel is found by what it reads, not by a name the program gives
it (it gives none yet): a convolution fusion whose one-hot operand is
``pred[rows, cols * buckets]`` (ops/histogram.py builds one row block's
table as one contraction).  Each event is one row block of one level;
its least time is that block's share of the algorithm's level work
(benchmark/work.py: bin indices, node id and two statistics read once)
over the chip's HBM peak.  The share is the sum of least times over the
sum of the events' device time in the traced slice.  A kernel that no
longer matches leaves the reader silent.
"""

import re

from benchmark.peaks import least_seconds
from benchmark.work import level_work

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_rate", "device_trace"

_ONE_HOT = re.compile(
    r"^%convolution[\w.\-]* = .*?fusion\(pred\[(\d+),(\d+)\]")


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    s = ctx["shapes"]
    least = spent = 0.0
    for name, (seconds, events) in tr["ops"].items():
        m = _ONE_HOT.match(name)
        if not m or seconds <= 0:
            continue
        rows_blk, width = int(m.group(1)), int(m.group(2))
        if width % s["cols"]:
            continue
        w = level_work(rows_blk, s["cols"],
                       max(s["nbins"], s.get("fine_nbins", 0)))
        least += events * least_seconds(w["ops"], w["bytes"],
                                        ctx["device_kind"])["seconds"]
        spent += seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
