"""The whole step's share of the chip's peak: the least time the chip
could take for the trees built (benchmark/work.py over
benchmark/peaks.py) over the window's seconds, in per cent."""

from benchmark.peaks import least_seconds
from benchmark.work import tree_work

UNIT, LAYER, MOVES, SOURCE = "%", "tree engine", "train_rate", "host_clock"


def read(ctx):
    s, c = ctx["shapes"], ctx["counters"]
    if not c.get("trees") or not ctx["clocks"].get("window_s"):
        return None
    w = tree_work(s["rows"], s["cols"], s["nbins"], s["max_depth"],
                  s.get("fine_nbins", 0))
    least = least_seconds(w["ops"] * c["trees"], w["bytes"] * c["trees"],
                          ctx["device_kind"], s.get("chips", 1))
    return 100.0 * least["seconds"] / ctx["clocks"]["window_s"]
