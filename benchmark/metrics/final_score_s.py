"""Host seconds of the scoring pass that ends ``train()``: the span
``train.final_metrics`` of the window's job (entry: models/tree/gbm.py
``_fit`` around ``model.model_metrics``).  The device queue is empty when
the span opens, so this host time is the pass's own.  Read by
benchmark/spans.py from the program's ``TimeLine`` ring: the program's
own host clock around the call, hence ``host_clock``."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "s", "entry", "train_rate", "host_clock"


def read(ctx):
    return spans.seconds(spans.window_spans(), "train", "final_metrics")
