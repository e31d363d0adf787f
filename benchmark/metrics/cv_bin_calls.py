"""Times the window's job binned a frame: its spans ``train.bin`` (the
training frame: models/tree/shared_tree.py ``prepare_bins``) plus
``train.valid.prepare`` (a frame binned to be scored).  A cross-validated
job whose K + 1 models share one binned frame reads 1; one whose fold
models each bin a weighted copy and a holdout slice reads 2K + 1.  Counted
by benchmark/spans.py from the program's ``TimeLine`` ring; a program
without spans leaves the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "count", "tree driver", "train_rate", \
    "program_counter"


def read(ctx, events=None):
    window = spans.window_spans(events)
    if not window:
        return None
    return sum(1 for e in window if e["kind"] == "train"
               and e["what"] in ("bin", "valid.prepare"))
