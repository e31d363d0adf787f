"""A GBM job with a SECOND frame, against the plain reference
(``benchmark/reference/gbm_valid.py``: numpy float64, no program import).

Two seeded "files" of 3,000 and 1,200 rows (numeric columns with missing
values beside enum columns of 3 / 29 / 352 levels) are landed as two
frames, each with the enum domains of its own file: the validation file
lacks training's first levels and holds levels past training's last, so
its codes are shifted against training's, its domains are shorter (c3:
two of training's three levels and one new), longer (c29, c352) or in
another order (``permuted``).  H2O-3's ``adaptTestForTrain`` contract
then says what every validation row scores: levels are matched by their
STRING, a level training never saw is a missing value.

(a) ``validation_logloss`` a tree, the validation metrics that end
    ``train()``, ``model_metrics(valid)``, ``predict(valid)`` and the MOJO
    scorer agree with rows the reference maps and routes itself;
(b) THE PARENT COMMIT FAILS (a): it scored the validation frame by that
    frame's own codes.  Its readings on the same files (NA share 0.02,
    sorted domains; this PR's files laid over commit 7e169fe, its
    history holds ``validation_*`` only): ``valid_logloss_gap`` 0.0397,
    ``valid_final_gap`` 0.0397, ``unseen_route_gap`` 0.177 against limits
    of 1e-6, no count of unseen rows and no ``training_logloss``;
(c) the validation metrics at the end are the last scoring point's, from
    the scorer's carried F: one descent a block, none for the whole
    forest, the validation frame binned once;
(d) the history holds ``training_*`` and ``validation_*`` at every point,
    the first bit-equal to a train without a validation frame;
(e) a validation log-loss that turns stops the job where the reference's
    statement of ``ScoreKeeper.stopEarly`` says;
(f) each planted fault of the second frame fails a limit at this size;
(g) the bfloat16 histogram path fails a tolerance; DRF and XGBoost
    ``predict`` a frame in another domain order as the same rows in
    training's.
"""

import tempfile
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.data_airline import RESPONSE
from benchmark.data_airline_split import (AirlineSplit, Part,
                                          as_frame_columns, level_name)
from benchmark.kinds import train_validated as tv
from benchmark.reference.gbm_mixed import Spec
from benchmark.reference.gbm_valid import GbmValidReference, stops_at
from benchmark.tests import readings_valid
from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.model import adapt_frame
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.gbm import GBM

ROWS, VROWS, DEPTH, NBINS, MIN_ROWS = 3000, 1200, 4, 255, 10
NAMES = ["n0", "c3", "n1", "c29", "c352", "n2"]
ENUM = {"c3": 3, "c29": 29, "c352": 352}
# training draws levels [0, card), validation [lo, card + extra): levels
# below ``lo`` only training holds, levels from ``card`` up only validation
LO = {"c3": 1, "c29": 2, "c352": 20}
EXTRA = {"c3": 1, "c29": 2, "c352": 10}
PARAMS = dict(max_depth=DEPTH, nbins=NBINS, nbins_cats=1024, learn_rate=0.1,
              min_rows=MIN_ROWS, min_split_improvement=1e-5,
              histogram_type="QuantilesGlobal")

# Tolerances.  The training side's are tests/test_mixed_frame_gbm.py's,
# for its reasons (float32 sums of a few thousand rows against float64:
# sound readings 2e-7 to 8e-7, the bfloat16 path two decades over).  The
# second frame's: the program's validation F is a float32 sum of two or
# three leaf values a row and its log-loss a float32 mean of 1,200 terms,
# the reference's are float64: sound readings 1e-8 to 2e-7 relative, and
# 6e-8 absolute on a probability.  A row routed down another branch
# moves its probability by 1e-2 and the log-loss of 1,200 rows by 1e-5 or
# more, so 1e-6 stands a decade over the sound readings and a decade or
# more under one mis-routed row.
TOL = {"trees_missing": 0, "rank_gap": 0.0, "split_gap": 1e-6,
       "median_leaf_gap": 1e-5, "leaf_value_gap": 1e-5, "update_gap": 1e-5,
       "logloss_gap": 1e-6, "valid_logloss_gap": 1e-6,
       "valid_final_gap": 1e-6, "unseen_route_gap": 1e-6, "probe_gap": 1e-6}
SECOND_FRAME = ("valid_points_missing", "unseen_rows_gap",
                "unseen_rows_unprobed", "valid_logloss_gap",
                "valid_final_gap", "unseen_route_gap", "probe_gap")
CONFIG = {"params": PARAMS}
TRAFFIC = {"limits": TOL, "check_trees": 2, "search_trees": 2,
           "score_tree_interval": 1, "probe_rows": 256}


def two_files(seed: int, na_share: float, rows=ROWS, vrows=VROWS):
    """Two parts of one population, enum columns as level identities."""
    rng = np.random.default_rng(seed)
    eff = {n: rng.normal(0.0, s, ENUM[n] + EXTRA[n])
           for n, s in zip(ENUM, (0.7, 0.6, 0.8))}

    def part(n_rows, lo, hi):
        num = [rng.normal(size=n_rows).astype(np.float32) for _ in range(3)]
        miss = rng.random(n_rows) < na_share
        num[0][miss] = np.nan
        cat = {n: rng.integers(lo(n), hi(n), n_rows).astype(np.int32)
               for n in ENUM}
        z = 0.6 * np.nan_to_num(num[0]) + 0.8 * miss - 0.5 * num[1] + sum(
            eff[n][cat[n]] for n in ENUM)
        if na_share:
            cat["c29"][rng.random(n_rows) < na_share / 2] = -1
        y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.int32)
        return Part([num[0], cat["c3"], num[1], cat["c29"], cat["c352"],
                     num[2]], y)

    return AirlineSplit(
        list(NAMES), dict(ENUM),
        part(rows, lambda n: 0, lambda n: ENUM[n]),
        part(vrows, lambda n: LO[n], lambda n: ENUM[n] + EXTRA[n]))


def frame_of(split, part, permuted_seed=None):
    """The part as a frame with the domains of its own file: sorted
    level strings, or (``permuted_seed``) the same levels in another
    order.  A missing enum value has no level: code -1."""
    if permuted_seed is None:
        return tv.land(split, part)
    cols, domains = [], {}
    for j, n in enumerate(split.names):
        ids = part.cols[j]
        if n not in split.enum:
            cols.append(ids)
            continue
        present = np.random.default_rng(permuted_seed).permutation(
            np.unique(ids[ids >= 0]))
        code = {int(i): k for k, i in enumerate(present)}
        domains[n] = [level_name(n, int(i)) for i in present]
        cols.append(np.array([code.get(int(i), -1) for i in ids], np.int32))
    return tv.train_mixed.land(SimpleNamespace(
        names=split.names, cols=cols, domains=domains, y=part.y))


def gbm(**kw):
    p = dict(PARAMS, ntrees=2, score_tree_interval=1, seed=1)
    p.update(kw)
    return GBM(**p)


def model_out_of(model, split, valid):
    """What the benchmark's kind hands its comparison."""
    o = model.output
    out = {k: o[k] for k in ("split_points", "nbins", "col_nbins", "is_cat",
                             "split_col", "value", "bitset", "f0",
                             "scoring_history", "ntrees_actual")}
    out["validation_logloss"] = float(o["validation_metrics"]["logloss"])
    prepared = [e for e in TimeLine.snapshot() if "dur_ns" in e and
                (e["kind"], e["what"]) == ("train", "valid.prepare")]
    out["unseen_rows"] = prepared[-1].get("unseen_rows")
    out["probe_rows"] = probe = tv.probe_of(split, TRAFFIC["probe_rows"])
    raw = np.asarray(model.predict_raw(valid))
    out["probe_p1"] = raw[probe, 2]
    return out


@pytest.fixture(scope="module")
def trained(cl):
    """(NA share, domain order) -> everything of one job; trained once."""
    cache = {}

    def get(na_share, order="sorted", **kw):
        key = (na_share, order, tuple(sorted(kw.items())))
        if key not in cache:
            split = two_files(2 ** 31 + 60 + int(100 * na_share), na_share)
            train = frame_of(split, split.train)
            valid = frame_of(split, split.valid,
                             None if order == "sorted" else 9)
            TimeLine.clear()
            model = gbm(**kw).train(y=RESPONSE, training_frame=train,
                                    validation_frame=valid)
            out = model_out_of(model, split, valid)
            verdict = tv.compare(CONFIG, TRAFFIC, split, out,
                                 int(kw.get("ntrees", 2)))
            cache[key] = SimpleNamespace(
                split=split, train=train, valid=valid, model=model,
                out=out, compared=verdict["compared"],
                read_only=verdict["read_only"])
        return cache[key]
    return get


# -------------------------------------------- (a) program against reference

@pytest.mark.parametrize("number", SECOND_FRAME)
@pytest.mark.parametrize("na_share,order", [(0.0, "sorted"),
                                            (0.02, "sorted"),
                                            (0.02, "permuted"),
                                            (0.3, "sorted")])
def test_second_frame_scores_as_the_reference_says(trained, na_share,
                                                   order, number):
    t = trained(na_share, order)
    # the files differ as the docstring says: there are rows whose level
    # training never saw
    assert t.read_only["unseen_rows"] > 50
    value, limit = t.compared[number]
    assert value <= limit, (number, t.compared)


@pytest.mark.parametrize("number", ["rank_gap", "split_gap", "update_gap",
                                    "median_leaf_gap", "logloss_gap"])
def test_training_side_is_held_as_in_the_mixed_cell(trained, number):
    value, limit = trained(0.02).compared[number]
    assert value <= limit, (number, value)


@pytest.mark.parametrize("how", ["model_metrics", "predict", "mojo"])
@pytest.mark.parametrize("order", ["sorted", "permuted"])
def test_every_scoring_entry_matches_levels_by_string(trained, order, how):
    t = trained(0.02, order)
    split, model = t.split, t.model
    ref = GbmValidReference(
        split.train.cols, split.valid.cols, [n in ENUM for n in NAMES],
        split.train.y, split.valid.y,
        Spec(DEPTH, NBINS, 1024, 0.1, float(MIN_ROWS), 1e-5))
    trees = tv.train_mixed.program_trees(model.output, 2)
    F, losses = ref.follow_valid(trees, float(model.output["f0"][0]))
    p1 = 1.0 / (1.0 + np.exp(-F))
    if how == "model_metrics":
        got = model.model_metrics(t.valid)["logloss"]
        assert abs(got - losses[-1]) / losses[-1] <= 1e-6
        return
    if how == "predict":
        got = np.asarray(model.predict_raw(t.valid))[:VROWS, 2]
        if order == "sorted":
            # the benchmark's probe is such a predict, on a cut of the file
            probe = t.out["probe_rows"]
            np.testing.assert_array_equal(
                tv.predict_probe(model, split, probe), got[probe])
    else:
        from h2o_tpu import mojo
        from h2o_tpu.models.generic import GenericModel
        with tempfile.TemporaryDirectory() as d:
            gen = GenericModel.from_mojo(
                mojo.load_mojo(mojo.export_mojo(model, f"{d}/m.zip")))
        got = np.asarray(gen.predict_raw(t.valid))[:VROWS, 2]
    assert np.max(np.abs(got - p1)) <= 1e-6


def test_shared_domain_costs_nothing_and_scores_bit_equal(cl):
    """A validation frame CUT from the training frame shares its domains:
    the adapted matrix is the frame's own cached ``as_matrix`` (no table,
    no program), and the same rows handed in under a re-ordered domain
    score bit-equal to it."""
    split = two_files(2 ** 31 + 61, 0.02)
    train = frame_of(split, split.train)
    cut = Part([c[:VROWS] for c in split.train.cols], split.train.y[:VROWS])
    # the cut's codes are training's, under training's whole domain
    cols, _ = as_frame_columns(split, split.train)
    doms = {n: list(train.vec(n).domain) for n in ENUM}
    shared = tv.train_mixed.land(SimpleNamespace(
        names=NAMES, cols=[c[:VROWS] for c in cols], domains=doms,
        y=cut.y))
    ad = adapt_frame(shared, NAMES, doms)
    assert ad.remapped == () and ad.unseen_rows is None
    assert ad.matrix is shared.as_matrix(NAMES)
    permuted = frame_of(split, cut, permuted_seed=4)
    # (three levels may come back in training's own order)
    assert {"c29", "c352"} <= set(
        adapt_frame(permuted, NAMES, doms).remapped)
    a = gbm().train(y=RESPONSE, training_frame=train,
                    validation_frame=shared)
    b = gbm().train(y=RESPONSE, training_frame=train,
                    validation_frame=permuted)
    assert [r["validation_logloss"] for r in a.output["scoring_history"]] \
        == [r["validation_logloss"] for r in b.output["scoring_history"]]
    np.testing.assert_array_equal(
        np.asarray(a.predict_raw(shared))[:VROWS],
        np.asarray(a.predict_raw(permuted))[:VROWS])


def test_absent_column_scores_as_missing_with_one_warning(trained):
    t = trained(0.02)
    fr = t.valid
    lacking = Frame([n for n in fr.names if n != "c29"],
                    [fr.vec(n) for n in fr.names if n != "c29"])
    blank = Frame(list(fr.names), [
        Vec(np.full(VROWS, -1, np.int32), T_CAT, domain=["c000"])
        if n == "c29" else fr.vec(n) for n in fr.names])
    said = []
    ad = adapt_frame(lacking, NAMES, t.model.output["domains"],
                     warn=said.append)
    assert ad.absent == ("c29",) and len(said) == 1 and "c29" in said[0]
    np.testing.assert_array_equal(
        np.asarray(t.model.predict_raw(lacking))[:VROWS],
        np.asarray(t.model.predict_raw(blank))[:VROWS])


# ------------------------------------ (c) the end of train(), (d) the history

def _counted(monkeypatch, name):
    inner, calls = getattr(st, name), []

    def wrapper(*a, **kw):
        calls.append(time.time_ns())
        return inner(*a, **kw)
    monkeypatch.setattr(st, name, wrapper)
    return calls


def test_validation_metrics_end_on_the_carried_F(cl, monkeypatch):
    split = two_files(2 ** 31 + 62, 0.02)
    train, valid = frame_of(split, split.train), frame_of(split, split.valid)
    descents = _counted(monkeypatch, "forest_score")
    binnings = _counted(monkeypatch, "bin_matrix")
    TimeLine.clear()
    model = gbm(ntrees=3).train(y=RESPONSE, training_frame=train,
                                validation_frame=valid)
    spans = [e for e in TimeLine.snapshot() if "dur_ns" in e]

    def of(what):
        return [e for e in spans if (e["kind"], e["what"]) == ("train", what)]

    # one descent a block, none for the whole forest; each frame binned once
    assert len(descents) == 3 and len(binnings) == 2
    scores = of("block.score")
    assert len(scores) == 3 and {e["valid_rows"] for e in scores} == {VROWS}
    final, = of("final_metrics")
    half, = of("final_metrics.valid")
    assert half["source"] == "carried_F" and half["parent"] == final["id"]
    assert not [t for t in descents + binnings
                if final["ns"] <= t <= final["ns"] + final["dur_ns"]]
    last = model.output["scoring_history"][-1]
    vm = model.output["validation_metrics"]
    for k in ("logloss", "mse", "AUC"):
        assert vm[k] == last["validation_" + k.lower()], k
    prepared, = of("valid.prepare")
    assert prepared["rows"] == VROWS and prepared["cat_cols"] == 3
    assert prepared["remapped_cols"] == 3
    assert prepared["unseen_levels"] > 10 and prepared["unseen_rows"] > 50


def test_no_scorer_means_a_rescore_that_says_so(cl):
    split = two_files(2 ** 31 + 62, 0.02)
    TimeLine.clear()
    model = gbm(score_tree_interval=0).train(
        y=RESPONSE, training_frame=frame_of(split, split.train),
        validation_frame=frame_of(split, split.valid))
    half, = [e for e in TimeLine.snapshot()
             if e.get("what") == "final_metrics.valid"]
    assert half["source"] == "rescore"
    assert model.output["validation_metrics"]["logloss"] > 0


@pytest.mark.parametrize("key", ["logloss", "mse", "auc"])
def test_history_holds_both_frames_at_every_point(trained, key):
    t = trained(0.02)
    alone = gbm().train(y=RESPONSE, training_frame=t.train)
    both = t.model.output["scoring_history"]
    assert len(both) == len(alone.output["scoring_history"]) == 2
    for row, row_alone in zip(both, alone.output["scoring_history"]):
        assert "validation_" + key in row
        assert row["training_" + key] == row_alone["training_" + key]
    # the stopping rule's own column reads the validation frame
    assert [r["logloss"] for r in both] == \
        [r["validation_logloss"] for r in both]


# ------------------------------------------------------- (e) early stopping

def test_stops_where_the_references_rule_says(cl):
    split = two_files(2 ** 31 + 63, 0.0)
    model = gbm(ntrees=40, learn_rate=0.9, min_rows=2, stopping_rounds=3,
                stopping_metric="logloss", stopping_tolerance=1e-3).train(
        y=RESPONSE, training_frame=frame_of(split, split.train),
        validation_frame=frame_of(split, split.valid))
    built = int(model.output["ntrees_actual"])
    assert 6 <= built < 40
    ref = GbmValidReference(
        split.train.cols, split.valid.cols, [n in ENUM for n in NAMES],
        split.train.y, split.valid.y,
        Spec(DEPTH, NBINS, 1024, 0.9, 2.0, 1e-5))
    _, losses = ref.follow_valid(
        tv.train_mixed.program_trees(model.output, built),
        float(model.output["f0"][0]))
    # the log-loss turned, and the rule fires first at the last kept tree
    assert min(losses) < losses[-1]
    assert stops_at(losses, 3, 1e-3) == built
    assert stops_at(losses[:-1], 3, 1e-3) is None


# ------------------------------------------------------ (f) planted faults

FAULTS = {"unmapped": ("valid_logloss_gap", "unseen_rows_gap"),
          "unseen_last": ("unseen_route_gap",),
          "stale": ("valid_logloss_gap", "valid_final_gap"),
          "train_metric": ("valid_logloss_gap", "valid_final_gap")}


@pytest.fixture(scope="module")
def fault_readings():
    split = two_files(2 ** 31 + 64, 0.02)
    ref = GbmValidReference(
        split.train.cols, split.valid.cols, [n in ENUM for n in NAMES],
        split.train.y, split.valid.y,
        Spec(DEPTH, NBINS, 1024, 0.1, float(MIN_ROWS), 1e-5))
    ref.prepare()
    ids = {j: split.valid.cols[j] for j in ref.domains}
    return dict(readings_valid.readings(
        ref, ids, 2, tv.probe_of(split, TRAFFIC["probe_rows"])))


@pytest.mark.parametrize("mode", sorted(FAULTS))
def test_planted_fault_fails_a_limit(fault_readings, mode):
    limits = dict(TOL, valid_points_missing=0, unseen_rows_gap=0,
                  unseen_rows_unprobed=0)
    sound = fault_readings["sound"]
    assert all(sound[k] <= limits[k] for k in SECOND_FRAME), sound
    for number in FAULTS[mode]:
        assert fault_readings[mode][number] > limits[number], (
            mode, number, fault_readings[mode])


# -------------------------- (g) the control, and the other builders' predict

def test_bfloat16_histograms_fail_a_tolerance(trained):
    compared = trained(0.02, bf16_histograms=True).compared
    over = [k for k, (v, lim) in compared.items() if v > lim]
    assert {"update_gap", "median_leaf_gap"} <= set(over), compared
    # the second frame follows the artifact, whatever made it
    assert not set(over) & set(SECOND_FRAME), compared


@pytest.mark.parametrize("algo", ["drf", "xgboost"])
def test_other_builders_predict_by_level_string(cl, algo):
    split = two_files(2 ** 31 + 65, 0.02)
    train = frame_of(split, split.train)
    if algo == "drf":
        from h2o_tpu.models.tree.drf import DRF
        builder = DRF(ntrees=3, max_depth=DEPTH, nbins=32, seed=5)
    else:
        from h2o_tpu.models.tree.xgboost import XGBoost
        builder = XGBoost(ntrees=3, max_depth=DEPTH, max_bins=32, seed=5)
    model = builder.train(y=RESPONSE, training_frame=train)
    # the validation rows twice: under their file's own (re-ordered)
    # domains, and in training's codes, an unseen level missing
    own = frame_of(split, split.valid, permuted_seed=3)
    ref = GbmValidReference(
        split.train.cols, split.valid.cols, [n in ENUM for n in NAMES],
        split.train.y, split.valid.y,
        Spec(DEPTH, 32, 1024, 0.1, 1.0, 1e-5))
    doms = model.output["domains"]
    mapped = tv.train_mixed.land(SimpleNamespace(
        names=NAMES, cols=ref.valid.cols, domains=doms, y=split.valid.y))
    assert adapt_frame(mapped, NAMES, doms).remapped == ()
    np.testing.assert_array_equal(
        np.asarray(model.predict_raw(own))[:VROWS],
        np.asarray(model.predict_raw(mapped))[:VROWS])


def test_imported_mojo_reads_a_time_column_in_float64(cl, monkeypatch):
    """The adapted matrix is the device's float32; epoch-ms there is two
    minutes coarse.  An imported artifact's scorer is handed a time
    column's exact float64 host copy, as before the remap moved to the
    device, and the enum column beside it still arrives in the
    artifact's codes."""
    from h2o_tpu.core.frame import T_TIME
    from h2o_tpu.models.generic import GenericModel
    rng = np.random.default_rng(5)
    n = 400
    ms = 1.6e12 + np.arange(n, dtype=np.float64) * 1001.0   # a second apart
    assert np.any(ms.astype(np.float32).astype(np.float64) != ms)
    lev = ["a", "b", "c"]
    codes = rng.integers(0, 3, n).astype(np.int32)
    y = (codes + rng.normal(size=n) > 1).astype(np.int32)

    def frame(domain, c):
        return Frame(["t", "e", "y"], [
            Vec(ms, T_TIME), Vec(c, T_CAT, domain=list(domain)),
            Vec(y, T_CAT, domain=["0", "1"])])
    model = GBM(ntrees=2, max_depth=2, min_rows=5, seed=1).train(
        y="y", training_frame=frame(lev, codes))
    from h2o_tpu import mojo
    with tempfile.TemporaryDirectory() as d:
        artifact = mojo.load_mojo(mojo.export_mojo(model, f"{d}/m.zip"))
    gen = GenericModel.from_mojo(artifact)
    seen = {}
    real = type(artifact).score_matrix

    def spy(self, X):
        seen["X"] = np.array(X)
        return real(self, X)
    monkeypatch.setattr(type(artifact), "score_matrix", spy)
    # the same rows under a reversed domain
    gen.predict_raw(frame(lev[::-1], 2 - codes))
    cols = list(artifact.columns)
    np.testing.assert_array_equal(seen["X"][:, cols.index("t")], ms)
    np.testing.assert_array_equal(seen["X"][:, cols.index("e")], codes)
