"""Share of the traced slice's device busy time that a SECOND frame
costs a training job: the scopes ``h2o.score.adapt`` (its enum codes
carried into the training domains by level string: core/frame.py),
``h2o.score.bin`` (its ``bin_matrix`` on the training split points:
models/tree/shared_tree.py) and ``h2o.score.descent`` (one walk of each
new block's trees over its rows, ``forest_score``: models/tree/driver.py
``IncrementalScorer``).  Read by benchmark/scopes.py; a program that
names no such scope (a parent commit, a job with one frame) leaves the
metric out.

WHAT THE ACCEPTED CELL'S SLICE SEES OF IT: THE DESCENT ONLY.  The sum is
over the three scopes, as ISSUE 34 defines the metric, and a slice that
holds the other two counts them; but in ``gbm-airline-xgbhist-valid
.train`` the 6 s slice (``trace_start_s`` 24) holds block 1's descent
and nothing else of the second frame: its binning (3.6 s of the 5.3 s
the second frame costs a window) ends about 8 s before that descent,
because block 1 is scored behind block 2's program, and the remap runs
in set-up, where its matrix is cached.  A slower validation binning or
remap therefore moves NO per-layer metric of the cell, only
``train_rate``; ``benchmark/tests/spans_on_chip_valid.py`` (the whole
window by scope) is the tool that sees them.  What it waits for: a slice
anchored to a phase of the job instead of a second of the window
(PERF.md section 7 (1)), which then holds the binning's end and the
descent whatever a tree's length."""

from benchmark import scopes

UNIT, LAYER, MOVES, SOURCE = "%", "tree driver", "train_rate", \
    "device_trace"

_SCOPES = ("h2o.score.adapt", "h2o.score.bin", "h2o.score.descent")


def read(ctx):
    named = scopes.window_scopes(ctx) or {}
    mine = [s for name, s in named.items() if name.startswith(_SCOPES)]
    if not mine:
        return None
    return 100.0 * sum(mine) / sum(named.values())
