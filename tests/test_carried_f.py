"""``GBM._fit`` ends on the F the trainer carried, and the per-block
scorer of a training frame reads it too.

The training-frame metrics that end ``train()`` come from the driver's
``F_final`` (f0 + offset + checkpoint forest + every kept tree, on every
row), not from a second binning of the frame and a descent of the whole
forest.  ``model.model_metrics(train)`` still does both, so the two must
agree wherever the carry is the same quantity: every distribution, an
offset, weights, row sampling, a checkpoint resume, an early stop that
throws a speculative block away, and the single-dispatch path.

The scoring history is held the same way.  With no validation frame the
block loop scores block t on block t's ``f_final`` and descends nothing;
a validation frame still gets a scorer that descends each new block, and
its history holds both frames' numbers at every point.
Handing the training frame in as the validation frame therefore gives the
history of a descending scorer on the same rows: bit-equal with blocks of
one tree (the same additions in the same order), equal to float32
rounding with larger blocks (the trainer adds tree by tree, the scorer
the block's sum).
"""

import time

import numpy as np
import pytest

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT

N, C = 480, 3
XS = [f"x{j}" for j in range(C)]


def _frame(rng, response: str, N: int = N):
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
    # none for the per-block scorer, and none for the finished forest
    assert not calls["forest_score"]
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


class _Descended(Exception):
    """The training program traced a walk down a built tree."""


@pytest.mark.parametrize("algo,valid_rows", [("gbm", 400), ("drf", 336)])
def test_training_traces_no_descent(cl, rng, monkeypatch, algo, valid_rows):
    """Growth hands every row's final node to the F update, so the
    training program holds no ``ops/descend.descend``: patched to raise
    while ``train_forest`` is on the stack, a ``train()`` with no
    validation frame still traces and runs.  With a validation frame the
    descending scorer calls it, outside the training program."""
    from h2o_tpu.models.tree import jit_engine, shared_tree
    from h2o_tpu.ops import descend as descend_mod
    inner, training, calls, grown = descend_mod.descend, [], [], []

    def watched(*a, **kw):
        calls.append(bool(training))
        if training:
            raise _Descended()
        return inner(*a, **kw)
    monkeypatch.setattr(descend_mod, "descend", watched)
    monkeypatch.setattr(shared_tree, "descend", watched)
    monkeypatch.setattr(jit_engine, "descend", watched, raising=False)

    train_forest = jit_engine.train_forest

    def guarded(*a, **kw):
        training.append(1)
        try:
            return train_forest(*a, **kw)
        finally:
            training.pop()
    monkeypatch.setattr(jit_engine, "train_forest", guarded)
    grow = jit_engine.build_tree_traced

    def growing(*a, **kw):
        grown.append(bool(training))
        return grow(*a, **kw)
    monkeypatch.setattr(jit_engine, "build_tree_traced", growing)

    # a static no other test passes: the program is traced here, under
    # the patch, and not taken from an earlier test's trace
    kw = dict(ntrees=3, score_tree_interval=1,
              min_split_improvement=1.25e-7)
    build = (lambda: _gbm(**kw)) if algo == "gbm" else \
        (lambda: _drf(sample_rate=0.632, **kw))
    fr = _frame(rng, "binomial")
    model = build().train(y="y", x=XS, training_frame=fr)
    assert model.output["ntrees_actual"] == 3
    assert grown and all(grown)
    assert True not in calls

    # a row count no other test scores, so the scorer is traced here too
    del calls[:]
    build().train(y="y", x=XS, training_frame=fr,
                  validation_frame=_frame(rng, "binomial", valid_rows))
    assert calls and True not in calls


def test_drf_final_span_says_it_rescored(cl, rng):
    from h2o_tpu.models.tree.drf import DRF
    fr = _frame(rng, "binomial")
    TimeLine.clear()
    DRF(ntrees=3, max_depth=3, nbins=16, seed=5).train(
        y="y", x=XS, training_frame=fr)
    final, = [e for e in TimeLine.snapshot() if "dur_ns" in e
              and (e["kind"], e["what"]) == ("train", "final_metrics")]
    # nothing is scored again: a forest's training metrics are read from
    # the out-of-bag votes the trainer carried, as H2O-3 reports them
    assert final["source"] == "carried_oob"


# ------------------------------------------- the per-block scorer's history

def _drf(**kw):
    from h2o_tpu.models.tree.drf import DRF
    return DRF(**dict(dict(ntrees=4, max_depth=3, nbins=16, min_rows=2.0,
                           seed=5, score_tree_interval=1), **kw))


def _unit(**kw):
    """Blocks of one tree whose leaves go into F unscaled."""
    return _gbm(**dict(dict(learn_rate=1.0, score_tree_interval=1), **kw))


# case -> (response, build(frame) -> builder, H2O_TPU_DONATE, rtol).
# rtol 0 = bit-equal: blocks of one tree AND a leaf scale of exactly 1
# (DRF; GBM at learn_rate 1 off the multinomial (K-1)/K).  Under any other
# scale XLA:CPU contracts the trainer's ``F + (value * scale)[node]`` into
# one fused multiply-add, rounded once, where the descending scorer adds
# the pulled tree's already-rounded leaves: an ulp of F apart in some rows.
# The "donating" cases have no stop path, so at the parent commit block
# t+1's launch gave block t's f_final away.
SCORED = {
    "bernoulli": ("binomial", lambda fr: _unit(), None, 0),
    "bernoulli_learn_rate": ("binomial", lambda fr: _gbm(
        score_tree_interval=1), None, 1e-6),
    "multinomial": ("multinomial", lambda fr: _unit(), None, 1e-6),
    "gaussian_offset": ("regression", lambda fr: _unit(
        offset_column="off"), None, 0),
    "weights_column": ("binomial", lambda fr: _unit(weights_column="w"),
                       None, 0),
    "sample_rate": ("binomial", lambda fr: _unit(sample_rate=0.5), None, 0),
    "checkpoint": ("binomial", _resumed(learn_rate=1.0,
                                        score_tree_interval=1), None, 0),
    "early_stop": ("binomial", _early_stop, None, 0),
    "runtime_budget": ("binomial", lambda fr: _unit(
        max_runtime_secs=3600.0), None, 0),
    "interval_1_donating": ("binomial", lambda fr: _unit(), "1", 0),
    "interval_3_donating": ("binomial", lambda fr: _unit(
        ntrees=6, score_tree_interval=3), "1", 1e-6),
    "interval_3_multinomial": ("multinomial", lambda fr: _gbm(
        ntrees=6, score_tree_interval=3), None, 1e-6),
    "xgboost": ("binomial", lambda fr: _xgboost(
        learn_rate=1.0, score_tree_interval=1), None, 0),
    # a forest with no bag (sample_rate 1) reports every tree's votes; a
    # bagged one its out-of-bag votes, which no descent of the whole
    # forest reproduces: both of its jobs report the same carried ones
    "drf_binomial": ("binomial", lambda fr: _drf(sample_rate=1.0), None, 0),
    "drf_multinomial": ("multinomial", lambda fr: _drf(sample_rate=1.0),
                        None, 0),
    "drf_regression_donating": ("regression",
                                lambda fr: _drf(sample_rate=1.0), "1", 0),
    "drf_sample_rate_interval_2": ("binomial", lambda fr: _drf(
        sample_rate=0.5, score_tree_interval=2), None, 1e-6),
}
OUT_OF_BAG = {"drf_sample_rate_interval_2"}


def _count_forest_score(monkeypatch):
    """Times (ns) of every ``shared_tree.forest_score`` call from here."""
    from h2o_tpu.models.tree import shared_tree as st
    inner, calls = st.forest_score, []

    def wrapper(*a, **kw):
        calls.append(time.time_ns())
        return inner(*a, **kw)
    monkeypatch.setattr(st, "forest_score", wrapper)
    return calls


def _score_spans():
    """Spans ``train.block.score`` of the newest job on the ring."""
    spans = [e for e in TimeLine.snapshot() if "dur_ns" in e]
    job = [e["job"] for e in spans
           if (e["kind"], e["what"]) == ("job", "run")][-1]
    return [e for e in spans if e["job"] == job
            and (e["kind"], e["what"]) == ("train", "block.score")]


def _inside(calls, spans):
    return [t for t in calls for e in spans
            if e["ns"] <= t <= e["ns"] + e["dur_ns"]]


def _history(model, prefix):
    """The scoring history's numbers for the frame ``prefix`` names,
    without the prefix and the clock (a job with a validation frame
    reports both frames at every point)."""
    other = {"training_": "validation_", "validation_": "training_"}[prefix]
    return [{k[len(prefix):] if k.startswith(prefix) else k: v
             for k, v in row.items()
             if k != "timestamp" and not k.startswith(other)}
            for row in model.output["scoring_history"]]


@pytest.mark.parametrize("case", sorted(SCORED))
def test_scoring_history_is_that_of_a_descending_scorer(
        cl, rng, monkeypatch, case):
    response, build, donate, rtol = SCORED[case]
    if donate is not None:
        monkeypatch.setenv("H2O_TPU_DONATE", donate)
    fr = _frame(rng, response)
    calls = _count_forest_score(monkeypatch)

    TimeLine.clear()
    carried = build(fr).train(y="y", x=XS, training_frame=fr)
    spans = _score_spans()
    assert spans and {e["source"] for e in spans} == {
        "carried_oob" if case in OUT_OF_BAG else "carried_F"}
    # the block loop descended no finished tree
    assert not _inside(calls, spans)

    TimeLine.clear()
    del calls[:]
    descended = build(fr).train(y="y", x=XS, training_frame=fr,
                                validation_frame=fr)
    spans = _score_spans()
    assert spans and {e["source"] for e in spans} == {"descent"}
    assert len(_inside(calls, spans)) == len(spans)

    got = _history(carried, "training_")
    want = _history(descended, "validation_")
    assert len(got) == len(want) == len(spans) and len(got) >= 2
    # beside the validation frame's numbers the job reports the training
    # frame's at every point, from the same carried F as the job without
    for g, both in zip(got, descended.output["scoring_history"]):
        for k in g:
            if "training_" + k in both:
                assert both["training_" + k] == g[k], (case, k)
        assert "training_" + ("logloss" if response != "regression"
                              else "mse") in both
    for g, w in zip(got, want):
        assert set(g) == set(w) and len(g) >= 2
        assert g["number_of_trees"] == w["number_of_trees"]
        if case in OUT_OF_BAG:
            # each row scored only by the trees that did not see it: worse
            # than every tree's votes on the rows they were grown on
            assert g["logloss"] > w["logloss"], (case, g, w)
            continue
        for k in g:
            if rtol:
                assert g[k] == pytest.approx(w[k], rel=rtol, abs=1e-9), k
            else:
                assert g[k] == w[k], (case, k)
    if case == "early_stop":
        # the stop threw block t+1 away: the last row is block t's
        assert carried.output["ntrees_actual"] < 40
        assert got[-1]["number_of_trees"] == carried.output["ntrees_actual"]
    np.testing.assert_array_equal(carried.output["split_col"],
                                  descended.output["split_col"])
    np.testing.assert_array_equal(carried.output["value"],
                                  descended.output["value"])


class _Crash(BaseException):
    """Process-death stand-in (not an Exception: nothing may absorb it)."""


@pytest.mark.parametrize("old_checkpoint", [False, True],
                         ids=["checkpoint", "checkpoint_with_scorer_F"])
def test_killed_after_block_two_resumes_to_the_same_history(
        cl, rng, tmp_path, monkeypatch, old_checkpoint):
    """The training-frame scorer has no F of its own to save: the
    checkpoint's ``F`` is it.  A checkpoint written when the scorer still
    kept one (``scorer_F``) loads, and that array is left alone."""
    from h2o_tpu.core import recovery as rec
    from h2o_tpu.core.recovery import auto_recover, pending_recoveries
    from h2o_tpu.models.tree import jit_engine
    fr = _frame(rng, "binomial")

    def build(where, **kw):
        return _gbm(ntrees=5, score_tree_interval=1, checkpoint_interval=1,
                    recovery_dir=str(tmp_path / where), **kw)

    whole = build("whole").train(y="y", x=XS, training_frame=fr)

    saved = []
    save = rec.Recovery.save_iteration

    def spy(self, state, meta=None):
        saved.append(state["scorer_F"])
        if old_checkpoint:
            state = dict(state, scorer_F=np.full_like(state["F"], 7.0))
        return save(self, state, meta=meta)
    monkeypatch.setattr(rec.Recovery, "save_iteration", spy)

    launches = {"n": 0}
    train_forest = jit_engine.train_forest

    def dies_at_the_third_launch(*a, **kw):
        launches["n"] += 1
        if launches["n"] == 3:
            raise _Crash("killed with block 2 on the device")
        return train_forest(*a, **kw)
    monkeypatch.setattr(jit_engine, "train_forest", dies_at_the_third_launch)
    with pytest.raises(_Crash):
        build("killed", model_id="gbm_killed").train(
            y="y", x=XS, training_frame=fr)
    monkeypatch.setattr(jit_engine, "train_forest", train_forest)

    pend, = pending_recoveries(str(tmp_path / "killed"))
    assert pend["iteration"]["trees_done"] == 2
    # F is written once: the scorer hands save_iteration none of its own
    assert saved == [None, None]

    TimeLine.clear()
    resumed, = auto_recover(str(tmp_path / "killed"))
    assert resumed.output["ntrees_actual"] == 5
    assert {e["source"] for e in _score_spans()} == {"carried_F"}
    assert len(_score_spans()) == 3
    assert _history(resumed, "training_") == _history(whole, "training_")
    np.testing.assert_array_equal(resumed.output["value"],
                                  whole.output["value"])
