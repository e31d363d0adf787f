"""``GBM._fit`` ends on the F the trainer carried.

The training-frame metrics that end ``train()`` come from the driver's
``F_final`` (f0 + offset + checkpoint forest + every kept tree, on every
row), not from a second binning of the frame and a descent of the whole
forest.  ``model.model_metrics(train)`` still does both, so the two must
agree wherever the carry is the same quantity: every distribution, an
offset, weights, row sampling, a checkpoint resume, an early stop that
throws a speculative block away, and the single-dispatch path.
"""

import time

import numpy as np
import pytest

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT

N, C = 480, 3
XS = [f"x{j}" for j in range(C)]


def _frame(rng, response: str):
    X = rng.normal(size=(N, C)).astype(np.float32)
    X[rng.uniform(size=(N, C)) < 0.03] = np.nan        # NA bucket in play
    z = np.nan_to_num(X[:, 0]) - 0.7 * np.nan_to_num(X[:, 1])
    noise = rng.normal(size=N).astype(np.float32)
    if response == "binomial":
        y = Vec((z + 0.8 * noise > 0).astype(np.int32), T_CAT,
                domain=["no", "yes"])
    elif response == "multinomial":
        y = Vec(np.digitize(z + 0.5 * noise, [-0.6, 0.6]).astype(np.int32),
                T_CAT, domain=["a", "b", "c"])
    else:
        y = Vec((z + 0.3 * noise).astype(np.float32))
    off = (0.25 * rng.normal(size=N)).astype(np.float32)
    w = rng.integers(0, 3, size=N).astype(np.float32)   # zero weights too
    return Frame(XS + ["off", "w", "y"],
                 [Vec(X[:, j]) for j in range(C)] + [Vec(off), Vec(w), y])


def _gbm(**kw):
    from h2o_tpu.models.tree.gbm import GBM
    return GBM(**dict(dict(ntrees=4, max_depth=3, nbins=16, min_rows=2.0,
                           learn_rate=0.3, seed=11), **kw))


def _xgboost(**kw):
    from h2o_tpu.models.tree.xgboost import XGBoost
    return XGBoost(**dict(dict(ntrees=4, max_depth=3, max_bins=16,
                               seed=11), **kw))


def _resumed(**kw):
    """Two trees, then two more on top of them: the carry starts from
    the checkpoint's forest."""
    def build(fr):
        base = _gbm(ntrees=2, **kw).train(y="y", x=XS, training_frame=fr)
        return _gbm(ntrees=4, checkpoint=base, **kw)
    return build


def _early_stop(fr):
    # a high rate on a weak signal: the training log-loss stalls within
    # the tolerance while block t+1 is already queued
    return _gbm(ntrees=40, learn_rate=1.0, max_depth=1, stopping_rounds=1,
                stopping_tolerance=0.2, stopping_metric="logloss",
                score_tree_interval=1)


CASES = {
    "bernoulli": ("binomial", lambda fr: _gbm(score_tree_interval=2)),
    "gaussian": ("regression", lambda fr: _gbm(score_tree_interval=2)),
    "multinomial": ("multinomial", lambda fr: _gbm(score_tree_interval=2)),
    "offset_column": ("binomial", lambda fr: _gbm(
        offset_column="off", score_tree_interval=2)),
    "weights_column": ("binomial", lambda fr: _gbm(
        weights_column="w", score_tree_interval=2)),
    "sample_rate": ("binomial", lambda fr: _gbm(
        sample_rate=0.5, score_tree_interval=2)),
    "checkpoint": ("binomial", _resumed(score_tree_interval=1)),
    "checkpoint_single_dispatch": ("regression", _resumed()),
    "early_stop": ("binomial", _early_stop),
    "single_dispatch": ("binomial", lambda fr: _gbm(score_tree_interval=0)),
    "xgboost": ("binomial", lambda fr: _xgboost(score_tree_interval=2)),
    "xgboost_single_dispatch": ("regression", lambda fr: _xgboost()),
}
KEYS = {"binomial": ("logloss", "AUC", "mse"),
        "multinomial": ("logloss", "mse"),
        "regression": ("mse", "mae")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_training_metrics_are_those_of_a_rescore(cl, rng, case):
    response, build = CASES[case]
    fr = _frame(rng, response)
    TimeLine.clear()
    model = build(fr).train(y="y", x=XS, training_frame=fr)
    if case == "early_stop":
        # the stop came with the next block in flight, and threw it away
        launched = [e for e in TimeLine.snapshot()
                    if e["what"] == "tree_block_launch"]
        assert model.output["ntrees_actual"] < 40
        assert len(launched) == model.output["ntrees_actual"] + 1
    carried = model.output["training_metrics"]
    rescored = model.model_metrics(fr)
    assert carried.kind == rescored.kind
    for k in KEYS[response]:
        assert carried[k] == pytest.approx(rescored[k], rel=1e-6,
                                           abs=1e-9), (case, k)
    assert carried["nobs"] == rescored["nobs"]


def test_train_bins_once_and_rescores_no_forest(cl, rng, monkeypatch):
    """No validation frame: ``train()`` bins the training frame once and
    its last span descends no forest."""
    from h2o_tpu.models.tree import shared_tree as st
    calls = {"bin_matrix": [], "forest_score": []}

    def counted(name):
        inner = getattr(st, name)

        def wrapper(*a, **kw):
            calls[name].append(time.time_ns())
            return inner(*a, **kw)
        monkeypatch.setattr(st, name, wrapper)

    counted("bin_matrix")
    counted("forest_score")
    fr = _frame(rng, "binomial")
    TimeLine.clear()
    _gbm(ntrees=3, score_tree_interval=1).train(y="y", x=XS,
                                                training_frame=fr)
    assert len(calls["bin_matrix"]) == 1
    # the per-block scorer's three, and none for the finished forest
    assert len(calls["forest_score"]) == 3
    final, = [e for e in TimeLine.snapshot() if "dur_ns" in e
              and (e["kind"], e["what"]) == ("train", "final_metrics")]
    assert final["source"] == "carried_F"
    assert not [t for t in calls["forest_score"] + calls["bin_matrix"]
                if final["ns"] <= t <= final["ns"] + final["dur_ns"]]

    # without per-block scoring the whole train() descends nothing
    calls["bin_matrix"].clear()
    calls["forest_score"].clear()
    _gbm(ntrees=3).train(y="y", x=XS, training_frame=fr)
    assert len(calls["bin_matrix"]) == 1 and not calls["forest_score"]


def test_drf_final_span_says_it_rescored(cl, rng):
    from h2o_tpu.models.tree.drf import DRF
    fr = _frame(rng, "binomial")
    TimeLine.clear()
    DRF(ntrees=3, max_depth=3, nbins=16, seed=5).train(
        y="y", x=XS, training_frame=fr)
    final, = [e for e in TimeLine.snapshot() if "dur_ns" in e
              and (e["kind"], e["what"]) == ("train", "final_metrics")]
    assert final["source"] == "rescore"
