"""GBM on a mixed-type frame, against the plain reference
(``benchmark/reference/gbm_mixed.py``: numpy float64, no program import).

- the program's trees on seeded frames of 4,000 rows (numeric columns
  with missing values beside enum columns of 3 / 29 / 352 levels, depth
  4) followed by the reference: every split's gain, the leaf values, the
  carried F and the log-loss, each within a tolerance that the bfloat16
  histogram path fails;
- numeric columns hold ``nbins - 1`` thresholds beside a 352-level enum;
- a response that only a non-contiguous set of levels separates is
  split there at the root;
- missing values and a level the model never saw follow the node's NA
  side, in ``predict`` and in the MOJO scorer as in training;
- a numeric-only frame builds the forest the parent commit built;
- the spans and scopes the mixed path adds are there.
"""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from _frames import CARD, CARDS, frame_of, mixed_columns
from benchmark.reference.gbm_mixed import (GbmMixedReference, Spec,
                                           trees_from_artifact)
from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.gbm import GBM

ROWS, DEPTH, NBINS, MIN_ROWS = 4000, 4, 255, 10

# Tolerances.  The program sums float32 statistics of 4,000 rows on the
# CPU mesh (exact float32 products, eight shards added in float32); the
# reference sums float64.  A sum of n float32 terms is off by about
# sqrt(n) * 6e-8 relative, 4e-6 here; the sound readings are 2e-7 to
# 8e-7 (split gains: 1e-13, the same candidates compared).  The limits
# stand a decade above the sound readings and two decades under the
# bfloat16 path's (3e-3 on the leaf values, 1e-4 on the log-loss, 5e-5
# on a split's gain), so a histogram contracted in lower precision than
# the float32 the parameters state fails every one of them.
TOL = {"rank_gap": 0.0, "split_gap": 1e-6, "median_leaf_gap": 1e-5,
       "leaf_value_gap": 1e-5, "update_gap": 1e-5, "logloss_gap": 1e-6,
       "f0_gap": 1e-6}


def gbm(**kw):
    p = dict(ntrees=2, max_depth=DEPTH, nbins=NBINS, min_rows=MIN_ROWS,
             histogram_type="QuantilesGlobal", score_tree_interval=1,
             seed=1)
    p.update(kw)
    return GBM(**p)


def follow(model, cols, card, y, depth=DEPTH):
    """The reference's numbers for the trees ``model`` built."""
    o = model.output
    ref = GbmMixedReference(cols, card, y, Spec(
        depth, NBINS, 1024, 0.1, float(MIN_ROWS), 1e-5))
    nums = ref.prepare(o["split_points"])
    trees = trees_from_artifact(
        o["split_col"][:, 0], o["bitset"][:, 0], o["value"][:, 0],
        o["split_points"], o["is_cat"], o["col_nbins"])
    history = {int(r["number_of_trees"]): float(r["training_logloss"])
               for r in o["scoring_history"]}
    nums.update(ref.check_forest(trees, float(o["f0"][0]), history,
                                 search_trees=len(trees)))
    return nums, trees


@pytest.fixture(scope="module")
def followed(cl):
    """NA share -> (model, columns, y, numbers); each trained once."""
    cache = {}

    def get(na_share, **kw):
        key = (na_share, tuple(sorted(kw.items())))
        if key not in cache:
            cols, y = mixed_columns(2 ** 31 + 40 + int(100 * na_share),
                                    na_share)
            model = gbm(**kw).train(y="y", training_frame=frame_of(cols, y))
            cache[key] = (model, cols, y, follow(model, cols, CARD, y)[0])
        return cache[key]
    return get


# ------------------------------------------- program against reference

@pytest.mark.parametrize("number", sorted(TOL))
@pytest.mark.parametrize("na_share", [0.0, 0.02, 0.3])
def test_program_follows_the_reference(followed, na_share, number):
    """Every split's gain is the reference's best (``split_gap``), every
    leaf its Newton value (``median_leaf_gap``, ``leaf_value_gap``), the
    tree's update of F (``update_gap``) and the log-loss after each tree
    (``logloss_gap``: the program's own routing of enum and NA rows
    against the reference's) within tolerance."""
    model, _, _, nums = followed(na_share)
    assert nums["logloss_points"] == 2
    assert nums[number] <= TOL[number], (number, nums)
    # the job was a mixed one: enum splits, and with NAs an NA side taken
    sc = model.output["split_col"]
    assert model.output["is_cat"][sc[sc >= 0]].any()


def test_bfloat16_histograms_fail_the_tolerances(followed):
    nums = followed(0.02, bf16_histograms=True)[3]
    over = [k for k in TOL if nums[k] > TOL[k]]
    assert {"update_gap", "median_leaf_gap", "logloss_gap"} <= set(over), \
        nums


# ----------------------------------------------------------- the table

def test_numeric_columns_hold_their_nbins_beside_a_352_level_enum(followed):
    """The table is as wide as the widest enum; a numeric column fills
    its stated ``nbins`` of it and no more (the parent binned numeric
    columns on 351 thresholds here)."""
    out = followed(0.02)[0].output
    assert out["nbins"] == 352 and out["bitset"].shape[-1] == 353
    assert list(out["col_nbins"]) == [255, 3, 255, 29, 352, 255]
    held = np.sum(~np.isnan(out["split_points"]), axis=1)
    assert list(held) == [NBINS - 1, 0, NBINS - 1, 0, 0, NBINS - 1]
    # the thresholds are the column's own order statistics
    assert followed(0.02)[3]["rank_gap"] == 0.0


@pytest.mark.parametrize("hist_type", ["QuantilesGlobal", "UniformAdaptive"])
def test_col_nbins_caps_at_nbins_cats(cl, hist_type):
    """A level past ``nbins_cats`` has no bin of its own: it shares the
    NA bucket."""
    rng = np.random.default_rng(5)
    code = rng.integers(0, 40, 600).astype(np.int32)
    y = (code % 2).astype(np.int32)
    fr = frame_of([code, rng.normal(size=600).astype(np.float32)], y,
                  names=["c", "n"], card=[40, 0])
    from h2o_tpu.models.model import DataInfo
    bd = st.prepare_bins(DataInfo(fr, ["c", "n"], "y"), 16, 32, hist_type)
    assert list(bd.col_nbins) == [32, 16] and bd.nbins == 32
    b = np.asarray(bd.bins)[:600, 0]
    assert np.array_equal(b[code < 32], code[code < 32])
    assert (b[code >= 32] == bd.fine).all()


# ------------------------------------------------ the subset search

@pytest.mark.parametrize("card", CARDS[1:])
def test_root_splits_on_a_level_set_no_code_range_reaches(cl, card):
    """The response depends on the PARITY of the level alone: only a
    non-contiguous set of levels separates it.  The root splits there,
    with a gain no split on a range of codes comes near."""
    rng = np.random.default_rng(card)
    code = rng.integers(0, card, ROWS).astype(np.int32)
    x = rng.normal(size=ROWS).astype(np.float32)
    y = ((code % 2 == 1) ^ (rng.random(ROWS) < 0.05)).astype(np.int32)
    model = gbm(ntrees=1, max_depth=2).train(
        y="y", training_frame=frame_of([code, x], y, names=["c", "x"],
                                       card=[card, 0]))
    o = model.output
    assert o["split_col"][0, 0, 0] == 0                  # the enum
    left = o["bitset"][0, 0, 0, :card]
    present = np.bincount(code, minlength=card) > 0
    odd = np.arange(card) % 2 == 1
    # the left set is one parity class of the levels that have rows
    assert (left[present] == odd[present]).all() or \
        (left[present] == ~odd[present]).all()
    assert np.sum(left[1:] != left[:-1]) >= card - 2     # non-contiguous
    # the best split the reference finds with the levels in code order
    # (the planted fault) is worth a small part of the true one
    ref = GbmMixedReference([code, x], [card, 0], y, Spec(
        2, NBINS, 1024, 0.1, float(MIN_ROWS), 1e-5))
    ref.prepare()
    cnt, G = ref._level_hist(np.zeros(ROWS, np.int64), 1, y - y.mean())
    by_mean, by_code = (ref._best_splits(cnt, G, cat_by_code=f)[0][0]
                        for f in (False, True))
    assert by_code < 0.1 * by_mean
    nums, _ = follow(model, [code, x], [card, 0], y, depth=2)
    assert nums["split_gap"] <= TOL["split_gap"]


# --------------------------------------------- NA and unseen levels

@pytest.fixture(scope="module")
def na_model(cl):
    """One enum column whose missing rows are all positive, so the root
    learns a side for them; a numeric column with missing values."""
    rng = np.random.default_rng(77)
    code = rng.integers(0, 29, ROWS).astype(np.int32)
    x = rng.normal(size=ROWS).astype(np.float32)
    eff = rng.normal(0.0, 1.0, 29)
    y = (rng.random(ROWS) < 1 / (1 + np.exp(-eff[code]))).astype(np.int32)
    gone = rng.random(ROWS) < 0.1
    code[gone], y[gone] = -1, 1
    x[rng.random(ROWS) < 0.1] = np.nan
    model = gbm(ntrees=2, max_depth=3).train(
        y="y", training_frame=frame_of([code, x], y, names=["c", "x"],
                                       card=[29, 0]))
    return model, code, x, y


def _scores(model, how, code, x):
    if how == "predict":
        fr = Frame(["c", "x"], [
            Vec(code, T_CAT, domain=[f"L{i}" for i in range(40)]), Vec(x)])
        return np.asarray(model.predict_raw(fr))[: len(code), 2]
    from h2o_tpu import mojo
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        m = mojo.load_mojo(mojo.export_mojo(model, f"{d}/m.zip"))
    X = np.stack([np.where(code < 0, np.nan, code), x], axis=1)
    return m.score_matrix(X)[:, 2]


@pytest.mark.parametrize("how", ["predict", "mojo"])
def test_na_and_unseen_levels_take_the_nodes_na_side(na_model, how):
    model, code, x, y = na_model
    o = model.output
    assert o["split_col"][0, 0, 0] == 0
    probe_x = np.linspace(-2, 2, 50).astype(np.float32)
    probe_x[::7] = np.nan

    def score(c):
        return _scores(model, how, np.full(50, c, np.int32), probe_x)

    missing = score(-1)
    # a level past the training domain scores as a missing one does,
    # whatever its code: 29 is the first unseen, 39 the frame's last
    for unseen in (29, 35, 39):
        assert np.array_equal(score(unseen), missing)
    # and not as the domain's last level does (the parent clipped it
    # there)
    assert not np.array_equal(score(28), missing)


def test_training_routes_na_rows_where_the_node_says(na_model):
    """Followed by the reference, which routes missing rows by the
    artifact's NA bit: leaf values and log-loss agree, so the program
    sent them the same way while it trained; and the root's NA bit is
    the side of the positive rows."""
    model, code, x, y = na_model
    nums, trees = follow(model, [code, x], [29, 0], y, depth=3)
    for k in ("split_gap", "update_gap", "logloss_gap", "median_leaf_gap"):
        assert nums[k] <= TOL[k], nums
    root = trees[0]
    assert root.col[0] == 0
    # missing rows are all positive: they go with the child that holds
    # the levels of higher mean gradient (the right one: levels are
    # ordered by ascending mean)
    assert not root.na_left[0]
    # both scorers reproduce the training F on the training rows
    p_train = _scores(model, "predict", code, x)
    p_mojo = _scores(model, "mojo", code, x)
    np.testing.assert_allclose(p_train, p_mojo, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- numeric-only frames

def _numeric_frame():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(3000, 5)).astype(np.float32)
    X[rng.random((3000, 5)) < 0.03] = np.nan
    z = X[:, 0] * np.nan_to_num(X[:, 1]) + np.nan_to_num(X[:, 2])
    y = (rng.random(3000) < 1 / (1 + np.exp(-z))).astype(np.int32)
    X[np.isnan(X[:, 0]), 0] = 0.0
    return frame_of([X[:, j] for j in range(5)], y,
                    names=[f"x{j}" for j in range(5)], card=[0] * 5)


# sha1 over split_points, split_col, bitset, value, thr_bin, na_left of
# the forest the PARENT commit (d7aeaf6) builds from _numeric_frame() on
# this mesh: the mixed path must not move a numeric-only job by a bit.
# The QuantilesGlobal forest is d7aeaf6's as the narrow levels' bf16x3
# contraction (ops/histogram.hist_form) builds it: every split the same,
# 11 of 93 node values 1-2 ulps apart, its tables summed in another order
PARENT_FOREST = {
    ("QuantilesGlobal", 4, 64): "4a2d97de15eb04f4ab9aa18c13704b4a724dea95",
    ("UniformAdaptive", 3, 20): "7b72e2d95772c5240a1f60256c428c3c630c9ec6",
}


@pytest.mark.parametrize("case", sorted(PARENT_FOREST))
def test_numeric_only_frame_builds_the_parents_forest(cl, case):
    hist_type, depth, nbins = case
    TimeLine.clear()
    model = GBM(ntrees=3, max_depth=depth, nbins=nbins, min_rows=5,
                histogram_type=hist_type, score_tree_interval=1,
                seed=7).train(y="y", training_frame=_numeric_frame())
    o = model.output
    digest = hashlib.sha1()
    for k in ("split_points", "split_col", "bitset", "value", "thr_bin",
              "na_left"):
        digest.update(np.ascontiguousarray(o[k]).tobytes())
    assert digest.hexdigest() == PARENT_FOREST[case]
    assert list(o["col_nbins"]) == [nbins] * 5
    # a numeric-only job reports no enum split
    pulls = [e for e in TimeLine.snapshot() if e.get("what") == "block.pull"]
    assert len(pulls) == 3 and all(e["cat_splits"] == 0 for e in pulls)
    assert sum(e["num_splits"] for e in pulls) == int(
        (o["split_col"] >= 0).sum())


# --------------------------------------------------- spans and scopes

def test_spans_carry_the_mixed_fields(followed):
    TimeLine.clear()
    cols, y = mixed_columns(3, 0.3)
    model = gbm().train(y="y", training_frame=frame_of(cols, y))
    spans = {}
    for e in TimeLine.snapshot():
        if "dur_ns" in e:
            spans.setdefault((e["kind"], e["what"]), []).append(e)
    binned, = spans[("train", "bin")]
    assert (binned["cat_cols"], binned["max_card"],
            binned["table_bins"]) == (3, 352, 352)
    # the width the global-grid histogram contracts at for this table:
    # 353 bins a column rounded up to the sublane tile
    assert binned["onehot_bins"] == 360
    pulls = spans[("train", "block.pull")]
    o = model.output
    sc, bs = o["split_col"], o["bitset"]
    assert sum(e["num_splits"] for e in pulls) == int((sc >= 0).sum())
    assert sum(e["cat_splits"] for e in pulls) == int(
        o["is_cat"][sc[sc >= 0]].sum()) > 0
    assert sum(e["na_left_splits"] for e in pulls) == int(
        bs[..., -1][sc >= 0].sum())
    # one job, the fields on the ring event and nowhere else
    assert {e["job"] for e in pulls} == {binned["job"]}


@pytest.mark.parametrize("nbins,onehot", [(255, 256), (64, 72), (20, 24)])
def test_float_frame_says_the_width_its_one_hot_is_built_at(cl, nbins,
                                                            onehot):
    """``onehot_bins`` of span ``train.bin``: ``table_bins + 1`` where
    that is a multiple of 8 (a 255-bin float frame), the next one where it
    is not."""
    from h2o_tpu.models.model import DataInfo
    fr = _numeric_frame()
    TimeLine.clear()
    st.prepare_bins(DataInfo(fr, [f"x{j}" for j in range(5)], "y"), nbins,
                    1024)
    binned, = [e for e in TimeLine.snapshot() if e.get("what") == "bin"]
    assert (binned["table_bins"], binned["onehot_bins"]) == (nbins, onehot)


def test_adaptive_job_says_no_one_hot_width(cl):
    """An adaptive job's histograms go through ``fine_map``, whose arm
    contracts at its own per-level widths: its ``train.bin`` carries no
    ``onehot_bins``, so the field cannot claim a pad that never runs."""
    from h2o_tpu.models.model import DataInfo
    TimeLine.clear()
    st.prepare_bins(DataInfo(_numeric_frame(), [f"x{j}" for j in range(5)],
                             "y"), 64, 1024, "UniformAdaptive")
    binned, = [e for e in TimeLine.snapshot() if e.get("what") == "bin"]
    assert binned["table_bins"] == 64 and "onehot_bins" not in binned


def test_find_splits_names_the_order_and_the_scan(cl):
    import re
    L, C, B = 4, 3, 8
    lowered = st.find_splits.lower(
        jnp.zeros((L, C, B + 1, 4)), jnp.array([False, True, False]),
        jnp.ones((L, C), bool), min_rows=1.0)
    text = lowered.compiler_ir(dialect="stablehlo").operation.get_asm(
        enable_debug_info=True)
    have = set(re.findall(r"h2o\.[\w.]+", text))
    assert {"h2o.tree.split", "h2o.tree.split.order",
            "h2o.tree.split.scan"} <= have
    # the sort belongs to the order, the prefix sums to the scan, and
    # both lie under the parent scope, whose readers see one sum
    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    assert any("h2o.tree.split/h2o.tree.split.order" in p and "sort" in p
               for p in paths)
    assert any("h2o.tree.split/h2o.tree.split.scan" in p and "cumsum" in p
               for p in paths)
