"""The window form of the histogram's share of its roofline, from the
device trace.

The kernel is what the program runs under the scope
``h2o.tree.hist.window`` (kernels: ops/histogram.py
``histogram_window_traced``: each row block's one-hot, its contraction
against the block's node window, the table's update).  Each deep level
sorts its rows once under ``h2o.tree.partition``, so the sort events in
the slice count the window levels it holds (on a v5e the sort of a
level's 5.25M keys is ONE ``sort`` operation: a slice of 4 such events
held 4 x 811 blocks of the window's loop, a level's in-bag rows over
4,096; ``jnp.searchsorted`` beside it lowers to a ``while`` of gathers
and selects, no sort); a level's least time is the
algorithm's level work (benchmark/work.py ``level_work``: every row's
bin indices, node id and two statistics read once) over the chip's
peaks.  The share is those least times over the device seconds of
``h2o.tree.hist.window``.  Silent where the slice holds no such scope or
no such sort.
"""

import re

from benchmark import harness, scopes, trace
from benchmark.peaks import least_seconds
from benchmark.work import level_work

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "train_rate", "device_trace"

_SORT = re.compile(r"\bsort\(")


def _is_sort(name, tf_op):
    """A sort: by the HLO text, or by the last component of its scope
    path (what the program called it)."""
    last = (tf_op or "").rsplit(":", 1)[0].rsplit("/", 1)[-1]
    return bool(_SORT.search(name)) or last.startswith("sort")


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    xp = trace.find_xplane(harness.OUT_DIR)
    if xp is None:
        return None
    paths = scopes.op_paths(xp)
    spent = levels = 0.0
    for name, (seconds, events) in tr["ops"].items():
        scope = scopes.scope_of(paths.get(name))
        if scope == "h2o.tree.hist.window":
            spent += seconds
        elif scope == "h2o.tree.partition" and _is_sort(name, paths.get(name)):
            levels += events
    if spent <= 0 or not levels:
        return None
    s = ctx["shapes"]
    w = level_work(s["rows"], s["cols"],
                   max(s["nbins"], s.get("fine_nbins", 0)))
    least = least_seconds(w["ops"], w["bytes"], ctx["device_kind"],
                          s.get("chips", 1))["seconds"]
    return 100.0 * levels * least / spent
