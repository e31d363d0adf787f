"""Host seconds a cross-validated job spends on its holdout rows outside
its models: the spans ``train.cv.holdout`` (a fold's rows of the fold
model's predictions into the combined array; field ``source`` says
``carried_F`` or ``descent``, the whole frame scored again) and
``train.cv.metrics`` (``cross_validation_metrics`` and the per-fold
summary) of the window's job (entry: models/model.py ``_fit_cv``).  Read
by benchmark/spans.py from the program's ``TimeLine`` ring: the program's
own host clock around the calls, hence ``host_clock``.  A job that is not
cross-validated has no such span and leaves the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "s", "entry", "train_rate", "host_clock"


def read(ctx, events=None):
    window = spans.window_spans(events)
    parts = [spans.seconds(window, "train", what)
             for what in ("cv.holdout", "cv.metrics")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
