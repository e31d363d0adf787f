"""A cross-validated job as ONE job: the fold models and the main model
on one binned frame, every holdout prediction read from the F its fold
model carried (``ModelBuilder._fit_cv``, ``_cv_shared``).

The specification is the path this replaced, kept here as the oracle and
written out plainly (``oracle_cv``): per fold a weighted COPY of the
frame and a holdout SLICE as its validation frame, each fold model
binning for itself, the whole frame scored again by ``predict_raw`` and
the fold's rows picked out on the host.

(i)   GBM, DRF and XGBoost, ``nfolds`` 3 and 5, Modulo / Random /
      Stratified / a fold column / a user weights column: every fold
      forest and the main forest bit-equal to the oracle's, the holdout
      predictions equal to ``predict_raw`` of the fold model on its
      rows, ``cross_validation_metrics`` and the summary equal;
(ii)  one ``train.bin`` span a job, nothing binned again and no tree
      descended while it runs, no compile on a second cross-validated
      ``train()`` whose Random folds have other sizes;
(iii) a builder off the tree path (GLM) cross-validates through the same
      orchestrator, on the generic arm;
(iv)  the planted faults of ``benchmark/tests/readings_cv.py`` each come
      out ``correct: false`` through ``benchmark/reference/gbm_cv.py``
      at this size, a sound run ``correct: true``.
"""

import numpy as np
import pytest

from benchmark.data import higgs_like
from benchmark.kinds import train_cv
from benchmark.reference.gbm_cv import GbmCvReference
from benchmark.tests import readings_cv
from h2o_tpu.core.diag import DispatchStats, TimeLine
from h2o_tpu.core.frame import Frame, T_CAT, Vec
from h2o_tpu.core.job import Job
from h2o_tpu.models import model as model_mod
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.drf import DRF
from h2o_tpu.models.tree.gbm import GBM
from h2o_tpu.models.tree.xgboost import XGBoost

ROWS, COLS = 1500, 5
FOREST = ("split_col", "bitset", "value", "thr_bin", "na_left", "node_w",
          "split_points")
BUILDERS = {
    "gbm": (GBM, dict(ntrees=3, max_depth=3, nbins=16, learn_rate=0.3,
                      min_rows=5, histogram_type="QuantilesGlobal",
                      score_tree_interval=1)),
    # every row sampled: a fold model's carried votes are its forest's
    "drf": (DRF, dict(ntrees=3, max_depth=3, nbins=16, min_rows=5,
                      histogram_type="QuantilesGlobal",
                      score_tree_interval=1)),
    # the engine's own name for the rate: a fold model's builder must not
    # translate the default ``eta`` over it
    "xgboost": (XGBoost, dict(ntrees=3, max_depth=3, max_bins=16,
                              learn_rate=0.2, min_rows=5,
                              score_tree_interval=1)),
}
# (builder, scheme, nfolds): each builder under Modulo and one drawn
# scheme, GBM under every scheme
CASES = [("gbm", "modulo", 5), ("gbm", "random", 3),
         ("gbm", "stratified", 3), ("gbm", "fold_column", 3),
         ("gbm", "weights", 3), ("drf", "modulo", 3),
         ("drf", "random", 5), ("xgboost", "modulo", 3),
         ("xgboost", "stratified", 5)]


def make_frame(seed: int, rows: int = ROWS, extra=()):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, COLS)).astype(np.float32)
    z = 1.1 * X[:, 0] - 0.7 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.int32)
    names = [f"x{j}" for j in range(COLS)] + ["y"]
    vecs = [Vec(X[:, j]) for j in range(COLS)]
    vecs.append(Vec(y, T_CAT, domain=["n", "p"]))
    if "fold" in extra:
        names.append("fold")
        vecs.append(Vec(rng.choice([2.0, 5.0, 9.0], rows)
                        .astype(np.float32)))
    if "w" in extra:
        names.append("w")
        vecs.append(Vec(rng.choice([0.5, 1.0, 2.0], rows)
                        .astype(np.float32)))
    return Frame(names, vecs), y


def plain_folds(scheme: str, n: int, seed: int, fr: Frame, y):
    """The fold of every row, by the rule as H2O-3's documentation
    states it."""
    rows = fr.nrows
    if scheme == "fold_column":
        _, codes = np.unique(fr.vec("fold").to_numpy(), return_inverse=True)
        return codes
    if scheme in ("modulo", "weights"):
        return np.arange(rows) % n
    rng = np.random.default_rng(seed)
    if scheme == "stratified":
        fold = np.zeros(rows, np.int64)
        for k in np.unique(y):
            idx = np.flatnonzero(y == k)
            rng.shuffle(idx)
            fold[idx] = np.arange(len(idx)) % n
        return fold
    return rng.integers(0, n, rows)


def oracle_cv(cls, params, fr: Frame, fold, x, weights=None):
    """The path this PR replaced.  Returns (fold models, main model,
    combined raw holdout predictions (host), cv metrics, fold metrics)."""
    job = Job(description="oracle")
    rows = fr.nrows
    user_w = np.asarray(fr.vec(weights).to_numpy(), np.float32) \
        if weights else np.ones(rows, np.float32)
    models, combined = [], None
    for i in range(int(fold.max()) + 1):
        hold = fold == i
        fr_i = Frame(fr.names + ["__w"], fr.vecs + [
            Vec(np.where(hold, 0.0, user_w).astype(np.float32))])
        fr_hold = fr.slice_rows(hold)
        fr_hold.add("__w", Vec(user_w[hold]))
        sub = cls(**dict(params, weights_column="__w"))
        sub.params["response_column"] = "y"
        m = sub._fit(job, x, "y", fr_i, fr_hold)
        models.append(m)
        raw = np.asarray(m.predict_raw(fr))
        combined = np.zeros_like(raw) if combined is None else combined
        pm = np.pad(hold, (0, raw.shape[0] - rows))
        combined = np.where(pm[:, None], raw, combined)
    main = cls(**dict(params, weights_column=weights))
    main.params["response_column"] = "y"
    model = main._fit(job, x, "y", fr, None)
    cvm = model.metrics_from_raw(combined, fr)
    pad = combined.shape[0] - rows
    fold_mms = [model.metrics_from_raw(
        combined, fr, w=np.pad(np.where(fold == i, user_w, 0.0), (0, pad)))
        for i in range(int(fold.max()) + 1)]
    return models, model, combined, cvm, fold_mms


@pytest.fixture(scope="module", params=CASES,
                ids=["-".join(map(str, c)) for c in CASES])
def job_and_oracle(request, cl):
    """One cross-validated ``train()`` and the oracle on the same frame."""
    algo, scheme, n = request.param
    cls, params = BUILDERS[algo]
    extra = {"fold_column": ("fold",), "weights": ("w",)}.get(scheme, ())
    fr, y = make_frame(11, extra=extra)
    x = [f"x{j}" for j in range(COLS)]
    cv_params = dict(params, seed=7, keep_cross_validation_predictions=True,
                     keep_cross_validation_fold_assignment=True)
    if scheme == "fold_column":
        cv_params["fold_column"] = "fold"
    else:
        cv_params.update(nfolds=n, fold_assignment="Modulo"
                         if scheme == "weights" else scheme.capitalize())
    weights = "w" if scheme == "weights" else None
    if weights:
        cv_params["weights_column"] = "w"
    TimeLine.clear()
    model = cls(**cv_params).train(x=x, y="y", training_frame=fr)
    events = TimeLine.snapshot()
    fold = plain_folds(scheme, n, 7, fr, y)
    oracle = oracle_cv(cls, dict(params, seed=7), fr, fold, x, weights)
    return dict(model=model, events=events, fold=fold, oracle=oracle,
                frame=fr, cl=cl, algo=algo)


def _dkv(cl, key):
    return cl.dkv.get(str(key))


def test_folds_are_the_plain_rule(job_and_oracle):
    j = job_and_oracle
    ff = _dkv(j["cl"], j["model"].output[
        "cross_validation_fold_assignment_frame_id"])
    assert np.array_equal(ff.vec("fold_assignment").to_numpy(), j["fold"])


@pytest.mark.parametrize("name", FOREST)
def test_forests_bit_equal_the_oracles(job_and_oracle, name):
    j = job_and_oracle
    o_models, o_main = j["oracle"][0], j["oracle"][1]
    keys = j["model"].output["cross_validation_models"]
    assert len(keys) == len(o_models)
    pairs = [(_dkv(j["cl"], k), o) for k, o in zip(keys, o_models)]
    for got, want in pairs + [(j["model"], o_main)]:
        a, b = got.output[name], want.output[name]
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
        assert got.output["ntrees_actual"] == want.output["ntrees_actual"]
        assert np.array_equal(np.asarray(got.output.get("f0", 0)),
                              np.asarray(want.output.get("f0", 0)))


def test_holdout_predictions_are_the_fold_models_own(job_and_oracle):
    """Each row's holdout prediction is ``predict_raw`` of the one model
    that never saw it; against the oracle's combined array to an ulp of
    the trainer's fused multiply-add (PERF.md section 6, PR 31)."""
    j = job_and_oracle
    fr, rows = j["frame"], j["frame"].nrows
    pf = _dkv(j["cl"], j["model"].output[
        "cross_validation_holdout_predictions_frame_id"])
    got = pf.vec("p").to_numpy()
    for i, key in enumerate(j["model"].output["cross_validation_models"]):
        own = np.asarray(_dkv(j["cl"], key).predict_raw(fr))[:rows, 2]
        hold = j["fold"] == i
        np.testing.assert_allclose(got[hold], own[hold], rtol=3e-7,
                                   atol=1e-7)
    np.testing.assert_allclose(got, j["oracle"][2][:rows, 2], rtol=3e-7,
                               atol=1e-7)


@pytest.mark.parametrize("key", ["logloss", "AUC", "mse"])
def test_cv_metrics_and_summary_equal_the_oracles(job_and_oracle, key):
    j = job_and_oracle
    cvm, fold_mms = j["oracle"][3], j["oracle"][4]
    out = j["model"].output
    assert out["cross_validation_metrics"][key] == \
        pytest.approx(cvm[key], rel=2e-6)
    vals = out["cross_validation_metrics_summary"][key]["values"]
    assert vals == pytest.approx([m[key] for m in fold_mms], rel=2e-6)


def test_fold_models_score_their_holdout_from_the_carried_F(job_and_oracle):
    """A fold model's history holds both metrics at every point, its
    ``validation_*`` the holdout rows', equal to the oracle's descent of
    a holdout slice."""
    j = job_and_oracle
    for key, want in zip(j["model"].output["cross_validation_models"],
                         j["oracle"][0]):
        got = _dkv(j["cl"], key)
        h, ho = got.output["scoring_history"], want.output["scoring_history"]
        assert len(h) == len(ho) > 0
        for r, ro in zip(h, ho):
            for k in ("training_logloss", "validation_logloss",
                      "validation_auc"):
                assert r[k] == pytest.approx(ro[k], rel=2e-6)
        assert got.output["validation_metrics"]["logloss"] == \
            pytest.approx(want.output["validation_metrics"]["logloss"],
                          rel=2e-6)
    scores = [e for e in j["events"] if e["what"] == "block.score"]
    folds = [e for e in scores if e["holdout_rows"]]
    # a random forest's in-fold metrics are its out-of-bag votes'; the
    # holdout's come from the same carry
    src = "carried_oob" if j["algo"] == "drf" else "carried_F"
    assert folds and all(e["source"] == src for e in folds)
    assert sorted({e["holdout_rows"] for e in folds}) == sorted(
        set(np.bincount(j["fold"]).tolist()))


def test_one_job_one_binning_and_the_cv_spans(job_and_oracle):
    j = job_and_oracle
    n = int(j["fold"].max()) + 1
    ev = [e for e in j["events"] if e["kind"] == "train"]
    roots = {e["job"] for e in ev}
    assert len(roots) == 1                      # one job
    what = [e["what"] for e in ev]
    assert what.count("bin") == 1 and what.count("valid.prepare") == 0
    assert what.count("cv.folds") == 1 and what.count("cv.metrics") == 1
    models = [e for e in ev if e["what"] == "cv.model"]
    assert [e["fold"] for e in models] == list(range(1, n + 1)) + ["main"]
    assert all(e["bins"] == "shared" for e in models)
    counts = np.bincount(j["fold"]).tolist()
    assert [e["rows_out"] for e in models] == counts + [0]
    holds = [e for e in ev if e["what"] == "cv.holdout"]
    assert len(holds) == n and all(e["source"] == "carried_F"
                                   for e in holds)
    assert what.count("final_metrics.valid") == 0


def test_nothing_binned_again_nothing_descended(cl, monkeypatch):
    """While a cross-validated GBM trains, the frame is binned once and
    no built tree is walked: every metric and every holdout prediction
    comes from a carried F."""
    calls = {"bin": 0, "descent": 0}
    real_bin, real_score = st.bin_matrix, st.forest_score

    def counted_bin(*a, **k):
        calls["bin"] += 1
        return real_bin(*a, **k)

    def counted_score(*a, **k):
        calls["descent"] += 1
        return real_score(*a, **k)

    monkeypatch.setattr(st, "bin_matrix", counted_bin)
    monkeypatch.setattr(st, "forest_score", counted_score)
    fr, _ = make_frame(12)
    cls, params = BUILDERS["gbm"]
    cls(**dict(params, nfolds=3, fold_assignment="Random", seed=3,
               stopping_rounds=2,
               keep_cross_validation_predictions=True)).train(
        y="y", training_frame=fr)
    assert calls == {"bin": 1, "descent": 0}


def test_second_job_with_other_random_folds_compiles_nothing(cl):
    """No shape depends on a fold's size: a second cross-validated job
    whose Random folds hold other row counts runs the first one's
    programs."""
    fr, _ = make_frame(13)
    cls, params = BUILDERS["gbm"]
    DispatchStats.install_xla_listener()

    def run(seed):
        m = cls(**dict(params, nfolds=3, fold_assignment="Random",
                       seed=seed,
                       keep_cross_validation_fold_assignment=True)).train(
            y="y", training_frame=fr)
        ff = cl.dkv.get(m.output[
            "cross_validation_fold_assignment_frame_id"])
        return np.bincount(ff.vec("fold_assignment").to_numpy()
                           .astype(int)).tolist()

    first = run(5)
    before = DispatchStats.xla_compiles()
    second = run(6)
    assert first != second
    assert DispatchStats.xla_compiles() == before


def test_stopping_rule_reads_the_holdout_and_reaches_the_main_model(cl):
    """``cv_computeAndSetOptimalParameters``: the main model builds the
    mean of the fold models' tree counts, each stopped on its holdout."""
    fr, _ = make_frame(14)
    cls, params = BUILDERS["gbm"]
    m = cls(**dict(params, ntrees=40, nfolds=3, seed=1, learn_rate=0.8,
                   stopping_rounds=1, stopping_tolerance=0.05,
                   stopping_metric="logloss")).train(
        y="y", training_frame=fr)
    counts = [cl.dkv.get(k).output["ntrees_actual"]
              for k in m.output["cross_validation_models"]]
    assert max(counts) < 40
    assert m.output["ntrees_actual"] == max(1, int(round(np.mean(counts))))


def test_a_builder_off_the_tree_path_uses_the_same_orchestrator(cl):
    from h2o_tpu.models.glm import GLM
    fr, _ = make_frame(15, rows=900)
    TimeLine.clear()
    m = GLM(family="binomial", nfolds=3, fold_assignment="Modulo",
            keep_cross_validation_predictions=True).train(
        y="y", training_frame=fr)
    ev = [e for e in TimeLine.snapshot() if e["kind"] == "train"]
    models = [e for e in ev if e["what"] == "cv.model"]
    assert [e["fold"] for e in models] == [1, 2, 3, "main"]
    assert all(e["bins"] == "own" for e in models)
    assert [e["source"] for e in ev if e["what"] == "cv.holdout"] == \
        ["descent"] * 3
    assert len(m.output["cross_validation_models"]) == 3
    assert 0.5 < m.output["cross_validation_metrics"]["AUC"] <= 1.0
    pf = cl.dkv.get(
        m.output["cross_validation_holdout_predictions_frame_id"])
    assert pf.nrows == fr.nrows
    # each row's prediction is its own fold model's
    fold = np.arange(fr.nrows) % 3
    got = pf.vec("p").to_numpy()
    for i, key in enumerate(m.output["cross_validation_models"]):
        own = np.asarray(cl.dkv.get(key).predict_raw(fr))[:fr.nrows, 2]
        np.testing.assert_allclose(got[fold == i], own[fold == i],
                                   rtol=1e-6)


def test_user_weights_column_is_no_predictor_in_any_model(cl):
    """The parent handed its fold models the user's weights column as a
    predictor (only the main model's ``DataInfo`` left it out)."""
    fr, _ = make_frame(16, extra=("w",))
    cls, params = BUILDERS["gbm"]
    m = cls(**dict(params, nfolds=3, weights_column="w")).train(
        y="y", training_frame=fr)
    for key in m.output["cross_validation_models"]:
        assert "w" not in cl.dkv.get(key).output["x"]
    assert "w" not in m.output["x"]


# -- (iv) the job against the plain reference, and its planted faults -------

REF_ROWS, REF_COLS, NFOLDS, REF_SEED = 3000, 6, 5, 23
REF_PARAMS = dict(max_depth=3, nbins=64, learn_rate=0.1, min_rows=10,
                  min_split_improvement=1e-5,
                  histogram_type="QuantilesGlobal", nfolds=NFOLDS,
                  fold_assignment="Modulo",
                  keep_cross_validation_predictions=True,
                  keep_cross_validation_fold_assignment=True)
# Tolerances at this size: float32 sums of a few thousand rows against
# float64 read 2e-8 to 3e-7 on sound runs (tests/test_validation_frame_gbm.py
# has the same readings for one model); a row scored by a model that saw it,
# or by another fold's, moves its probability by 1e-3 or more and a fold's
# log-loss by 1e-4 or more.  The counts are exact.
TOL = {"rank_gap": 0.0, "split_gap": 1e-6, "update_gap": 1e-5,
       "median_leaf_gap": 1e-5, "logloss_gap": 1e-6,
       "main_logloss_gap": 1e-6, "holdout_pred_gap": 1e-6,
       "holdout_logloss_gap": 1e-6, "cv_logloss_gap": 1e-6,
       "cv_fold_gap": 1e-6}
REF_CONFIG = {"params": REF_PARAMS}
REF_TRAFFIC = {"limits": TOL, "search_trees": 2}


def _verdict(nums):
    """``train_cv.compare``'s rule on a dict of numbers."""
    over = [k for k, v in nums.items()
            if (k in train_cv._EXACT and v != 0)
            or (k in TOL and not v <= TOL[k])]
    return over


def _program_job(cl, monkeypatch=None, fault=None):
    X, y = higgs_like(REF_ROWS, REF_COLS, REF_SEED)
    fr = train_cv.land({"response_domain": ["b", "s"]}, X, y)
    if fault == "leak":
        real = model_mod._fold_weights
        monkeypatch.setattr(
            model_mod, "_fold_weights",
            lambda fold, w, i: (w, real(fold, w, i)[1]))
    elif fault == "next_fold":
        real_sel = model_mod._select_holdout
        monkeypatch.setattr(
            model_mod, "_select_holdout",
            lambda fold, i, raw, comb: real_sel(
                fold, (i + NFOLDS - 1) % NFOLDS, raw, comb))
    m = GBM(**dict(REF_PARAMS, ntrees=2, score_tree_interval=1,
                   seed=REF_SEED)).train(y="y", training_frame=fr)
    out = train_cv.job_outputs(m, REF_ROWS)
    return train_cv.compare(REF_CONFIG, REF_TRAFFIC, X, y, out, REF_SEED, 2)


def test_sound_job_is_correct_by_the_reference(cl):
    v = _program_job(cl)
    assert v["correct"] is True, v["compared"]
    assert set(TOL) | set(train_cv._EXACT) <= set(v["compared"])
    assert v["read_only"]["cv_auc_gap"] < 2e-3     # a 400-bin AUC


@pytest.mark.parametrize("fault,number", [("leak", "root_cover_gap"),
                                          ("next_fold", "holdout_pred_gap")])
def test_fault_planted_in_the_program_is_not_correct(cl, monkeypatch,
                                                     fault, number):
    v = _program_job(cl, monkeypatch, fault)
    assert v["correct"] is False
    value, limit = v["compared"][number]
    assert not value <= limit


@pytest.fixture(scope="module")
def fault_readings():
    X, y = higgs_like(REF_ROWS, REF_COLS, REF_SEED)
    ref = GbmCvReference(X, y, train_cv.spec_of(REF_CONFIG), NFOLDS)
    ref.prepare()
    return dict(readings_cv.readings(ref, 2, REF_SEED % NFOLDS, 2))


@pytest.mark.parametrize("mode", readings_cv.MODES)
def test_planted_fault_fails_a_limit(fault_readings, mode):
    over = _verdict(fault_readings[mode])
    if mode == "sound":
        assert over == []
    else:
        assert over, fault_readings[mode]
