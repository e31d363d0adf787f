"""Share of the window's split nodes that sit on an enum column: the
counters ``cat_splits`` over ``num_splits`` of the ``train.block.pull``
spans of the window's job (tree driver: models/tree/driver.py counts
them on the host from the arrays the pull already holds).  Read by
benchmark/spans.py from the program's ``TimeLine`` ring; a program whose
spans carry no such counters leaves the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "%", "tree driver", "train_rate", \
    "program_counter"


def read(ctx, events=None):
    pulls = [e for e in spans.window_spans(events)
             if (e["kind"], e["what"]) == ("train", "block.pull")
             and "num_splits" in e]
    total = sum(e["num_splits"] for e in pulls)
    if not total:
        return None
    return 100.0 * sum(e["cat_splits"] for e in pulls) / total
