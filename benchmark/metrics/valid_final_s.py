"""Host seconds of the validation half of the pass that ends
``train()``: the span ``train.final_metrics.valid`` of the window's job
(entry: models/tree/shared_tree.py ``final_validation_metrics``, a child
of ``train.final_metrics``; its field ``source`` says ``carried_F``, the
metric kernels on the F the per-block scorer carried, or ``rescore``, the
validation frame binned and the whole forest descended again).  Read as
``final_score_s`` is, by benchmark/spans.py from the program's
``TimeLine`` ring: the program's own host clock around the call, hence
``host_clock``.  A program with no such span leaves the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "s", "entry", "train_rate", "host_clock"


def read(ctx, events=None):
    return spans.seconds(spans.window_spans(events), "train",
                         "final_metrics.valid")
