"""What the orchestration of a cross-validated job costs: the seconds of
the window's root span ``job.run`` outside its binning (``train.bin``) and
its models (the spans ``train.cv.model``, one a fold model and one for the
main model: entry, models/model.py ``_fit_cv``): the fold ids and weights,
the host's turn between two models, the select of the holdout rows, the
cross-validation metrics, the frames kept.  Read by benchmark/spans.py
from the program's ``TimeLine`` ring (host time).  A job with no
``train.cv.model`` span leaves the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "s", "entry", "train_rate", "host_clock"


def read(ctx, events=None):
    window = spans.window_spans(events)
    models = spans.seconds(window, "train", "cv.model")
    root = spans.seconds(window, "job", "run")
    if models is None or root is None:
        return None
    return root - models - (spans.seconds(window, "train", "bin") or 0.0)
