"""DRF's draws, its out-of-bag training metrics, and the sparse-frontier
engine's deep levels, on the CPU mesh.

- the window form of a level's histogram (``histogram_window_traced``:
  rows sorted by node, each block contracted against a window of nodes)
  is the one-hot form's table, on random slots with rows out of the
  level, for narrow and wide levels, global and adaptive grids, int32
  codes up to the 1,024-bin grid's NA bucket in an odd number of
  columns, a level no row is in, and node runs across block ends; a
  window level sorts once and its blocks gather packed words, and the
  launch span counts the window levels;
- the counter-based bag and ``mtries`` draws equal their numpy
  restatement (the rule ``jit_engine.py`` states), and a forest built in
  blocks of one tree is the forest built in one block;
- a forest's training metrics and scoring history are its carried
  out-of-bag votes': each row the mean of the trees that left it out of
  their bag, a row no tree left out at weight 0;
- a small DRF at H2O-3's defaults (depth 20, a frontier of 64 nodes a
  level, so the looped levels and the window histograms run) is held to
  ``benchmark/reference/drf.py``, and the program's cut counters are the
  cut children its trees hold.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, T_CAT, Vec
from h2o_tpu.models.tree import jit_engine as je
from h2o_tpu.ops.histogram import (histogram_build_traced,
                                   histogram_window_traced)


# ---- the window histogram ------------------------------------------------

def _case(L, adaptive, id=None, R=2048, C=4, F=64, dtype=np.int16,
          out=0.2, sdtype=np.float32):
    return pytest.param(L, adaptive, R, C, F, dtype, out, sdtype,
                        id=id or f"{L}-{adaptive}")


@pytest.mark.parametrize("L, adaptive, R, C, F, dtype, out, sdtype", [
    _case(1, False), _case(9, True), _case(64, False), _case(200, True),
    _case(300, False),
    # int32 codes up to the NA bucket of a 1,024-bin fine grid, an odd
    # column count: the packed words' pad column
    _case(200, True, "fine1024-int32-C5", C=5, F=1024, dtype=np.int32),
    # no row in the level: the block loop runs no block
    _case(300, True, "no-row-in-level", F=1024, dtype=np.int32, out=1.0),
    # 12,000 rows a device over 160 nodes: a window of 128 nodes holds
    # three blocks' rows, so blocks end inside a node's run
    _case(160, False, "runs-across-block-ends", R=96000, C=3),
    # quantized statistics (an int16 carrier, an int32 table) beside
    # uint8 codes
    _case(150, False, "int16-statistics", C=7, dtype=np.uint8,
          sdtype=np.int16)])
def test_window_histogram_equals_the_one_hot_histogram(cl, rng, L,
                                                       adaptive, R, C, F,
                                                       dtype, out, sdtype):
    B = 20
    nb = F if adaptive else B
    bins = rng.integers(0, nb + 1, size=(R, C)).astype(dtype)
    slot = rng.integers(0, L, size=R).astype(np.int32)
    slot[rng.uniform(size=R) < out] = -1
    # integer statistics: both forms' sums are exact in float32
    w = rng.integers(0, 3, size=R).astype(np.float32)
    yv = rng.integers(0, 2, size=R).astype(np.float32)
    stats = np.stack([w, w * yv, w * yv, w], axis=1).astype(sdtype)
    fine_map = None
    if adaptive:
        lo = rng.integers(0, F // 2, size=(L, C)).astype(np.int32)
        hi = (lo + rng.integers(4, F // 2, size=(L, C))).astype(np.int32)
        fine_map = (jnp.asarray(lo), jnp.asarray(hi),
                    jnp.zeros((L, C), jnp.int32),
                    jnp.asarray(np.arange(C) == 1), F)
    nbins = B

    def both(b, s, st):
        one = histogram_build_traced(b, s, st, L, nbins, block_rows=256,
                                     fine_map=fine_map)
        win = histogram_window_traced(b, s, st, L, nbins, fine_map=fine_map)
        return one, win

    one, win = jax.jit(both)(jnp.asarray(bins), jnp.asarray(slot),
                             jnp.asarray(stats))
    one, win = np.asarray(one), np.asarray(win)
    assert win.shape == one.shape == (L, C, B + 1, 4)
    np.testing.assert_array_equal(win, one)
    # every row the level sees is in the table once a column
    assert win[..., 0].sum() == w[slot >= 0].sum() * C


def test_a_window_level_sorts_once_and_gathers_packed_words(cl, rng):
    # one window level at cell 6's row width (28 columns of a 1,024-bin
    # fine grid, 4 statistics), compiled on the CPU mesh: ONE sort, under
    # ``h2o.tree.partition`` (``window_hist_roofline`` counts a slice's
    # window levels by those sort events); a block gathers its rows'
    # bins as 14 packed words, not as 28 int32 codes, and its statistics
    R, C, L, F = 2048, 28, 300, 1024
    lo = rng.integers(0, F // 2, size=(L, C)).astype(np.int32)

    def level(b, s, st):
        return histogram_window_traced(
            b, s, st, L, 20, fine_map=(jnp.asarray(lo), jnp.asarray(lo + 8),
                                       jnp.zeros((L, C), jnp.int32),
                                       jnp.zeros((C,), bool), F))
    hlo = jax.jit(level).lower(
        rng.integers(0, F + 1, size=(R, C)).astype(np.int32),
        rng.integers(-1, L, size=R).astype(np.int32),
        np.ones((R, 4), np.float32)).compile().as_text()
    sorts = [ln for ln in hlo.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1 and "h2o.tree.partition" in sorts[0]
    gathers = re.findall(r"(\w+)\[4096(?:,1)?,(\d+)\]\S* gather\(", hlo)
    assert sorted(gathers) == [("f32", "4"), ("u32", "14")]


def test_the_window_levels_of_a_default_forest():
    # H2O-3's DRF at depth 20 on the 1,024-bin fine grid, cap 65,536:
    # levels 0-5 hold 1-32 nodes, under the window's 64; levels 6-19 are
    # window levels
    kw = dict(max_depth=20, nbins=20, kleaves=65536, adaptive=True,
              fine_nbins=1024)
    assert je.window_levels(kw) == 14
    assert je.window_levels(dict(kw, kleaves=0)) == 0


# ---- the draws -------------------------------------------------------------

def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _hash(seed, *words):
    with np.errstate(over="ignore"):
        h = _mix(_mix(np.uint32(0)) ^ np.uint32(seed % 2 ** 32))
        for v in words:
            h = _mix(h ^ np.asarray(v).astype(np.uint32))
    return h


def test_counter_draws_equal_their_numpy_restatement():
    seed = 2 ** 31 + 29
    words = je.seed_words(jax.random.key(seed))
    assert np.asarray(words).tolist() == [0, seed % 2 ** 32]
    got = np.asarray(je.counter_bag(words, jnp.uint32(7), 5000, 0.632))
    want = (_hash(seed, 1, 7, np.arange(5000)) >> 8) < int(0.632 * 2 ** 24)
    np.testing.assert_array_equal(got, want)
    assert 0.6 < got.mean() < 0.66
    got = np.asarray(je.counter_mtries(words, jnp.uint32(3), 5, 40, 28, 5))
    v = (_hash(seed, 2, 3, 5, np.arange(40)[:, None],
               np.arange(28)[None, :]) >> 8).astype(np.int64)
    rank = np.argsort(np.argsort(v * 28 + np.arange(28), axis=1), axis=1)
    np.testing.assert_array_equal(got, rank < 5)
    assert (got.sum(axis=1) == 5).all()
    # another slot, another draw
    assert (got != got[:1]).any()


def _frame(rng, rows=1200, cols=5):
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    z = 1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (z + 0.7 * rng.normal(size=rows) > 0).astype(np.int32)
    return X, y, Frame([f"x{j}" for j in range(cols)] + ["y"],
                       [Vec(X[:, j]) for j in range(cols)] +
                       [Vec(y, T_CAT, domain=["b", "s"])])


def _drf(**kw):
    from h2o_tpu.models.tree.drf import DRF
    return DRF(**dict(dict(ntrees=3, max_depth=5, nbins=16, seed=11,
                           histogram_type="QuantilesGlobal"), **kw))


def test_a_forest_in_blocks_is_the_forest_in_one_block(cl, rng):
    _, _, fr = _frame(rng)
    one = _drf().train(y="y", training_frame=fr).output
    blocks = _drf(score_tree_interval=1).train(y="y",
                                               training_frame=fr).output
    for k in ("split_col", "bitset", "value"):
        np.testing.assert_array_equal(np.asarray(one[k]),
                                      np.asarray(blocks[k]))


# ---- out-of-bag training metrics ---------------------------------------------

def _tree_values(out, X, t):
    """Tree ``t``'s leaf value of every row, descended on raw values (a
    dense heap: a row goes left iff x < the split point of its bin)."""
    sp = np.asarray(out["split_points"])
    col = np.asarray(out["split_col"][t, 0])
    bs = np.asarray(out["bitset"][t, 0])
    val = np.asarray(out["value"][t, 0], np.float64)
    B = int(out["nbins"])
    last_left = bs[:, :B].sum(axis=1) - 1
    node = np.zeros(X.shape[0], np.int64)
    for _ in range(int(out["max_depth"])):
        c = col[node]
        thr = sp[np.maximum(c, 0), np.clip(last_left[node], 0,
                                          sp.shape[1] - 1)]
        right = ~(X[np.arange(X.shape[0]), np.maximum(c, 0)] < thr)
        node = np.where(c >= 0, 2 * node + 1 + right, node)
    return val[node]


def _logloss(p, y):
    p = np.clip(p, 0.0, 1.0)
    return float(-np.mean(np.where(y > 0, np.log(np.maximum(p, 1e-15)),
                                   np.log(np.maximum(1 - p, 1e-15)))))


def test_training_metrics_are_the_out_of_bag_votes(cl, rng):
    X, y, fr = _frame(rng)
    TimeLine.clear()
    model = _drf(ntrees=2, score_tree_interval=1).train(y="y",
                                                        training_frame=fr)
    out = model.output
    votes = np.zeros(len(y))
    count = np.zeros(len(y))
    history = {int(r["number_of_trees"]): r["training_logloss"]
               for r in out["scoring_history"]}
    for t in range(2):
        inbag = (_hash(11, 1, t, np.arange(len(y))) >> 8) < \
            int(0.632 * 2 ** 24)
        v = _tree_values(out, X, t)
        votes[~inbag] += v[~inbag]
        count[~inbag] += 1
        seen = count > 0
        want = _logloss(votes[seen] / count[seen], y[seen])
        np.testing.assert_allclose(history[t + 1], want, rtol=1e-5)
    # two trees leave about 40 % of the rows in both bags: those rows have
    # no out-of-bag prediction and count in no metric
    assert 0.3 < (count == 0).mean() < 0.5
    tm = out["training_metrics"]
    assert tm.get("nobs") == (count > 0).sum()
    np.testing.assert_allclose(tm.get("logloss"), history[2], rtol=1e-6)
    sources = {e.get("source") for e in TimeLine.snapshot()
               if "dur_ns" in e and (e["kind"], e["what"]) in (
                   ("train", "final_metrics"), ("train", "block.score"))}
    assert sources == {"carried_oob"}


# ---- a default forest against the plain reference ---------------------------

def test_a_small_default_forest_is_held_to_the_reference(cl, monkeypatch):
    from benchmark.data import higgs_like
    from benchmark.kinds.train_bagged import pool_trees
    from benchmark.reference.drf import DrfReference, DrfSpec
    from h2o_tpu.models.tree.drf import DRF
    monkeypatch.setenv("H2O_TPU_MAX_LIVE_LEAVES", "64")
    seed = 2 ** 31 + 41
    X, y = higgs_like(3000, 8, seed)
    fr = Frame([f"x{j}" for j in range(8)] + ["y"],
               [Vec(X[j]) for j in range(8)] +
               [Vec(y, T_CAT, domain=["b", "s"])])
    TimeLine.clear()
    out = DRF(ntrees=2, seed=seed, score_tree_interval=1).train(
        y="y", training_frame=fr).output
    assert out["max_depth"] == 20 and out["child"] is not None
    ref = DrfReference(X, y, DrfSpec(max_depth=20, nbins=20, fine=1024,
                                     min_rows=1.0,
                                     min_split_improvement=1e-5, mtries=2,
                                     sample_rate=0.632, cap=64), seed)
    nums = ref.prepare(out["split_points"])
    history = {int(r["number_of_trees"]): r["training_logloss"]
               for r in out["scoring_history"]}
    nums.update(ref.check_forest(
        pool_trees(out), history, out["training_metrics"].get("logloss"),
        out["training_metrics"].get("nobs")))
    for k in ("mtries_gap", "frontier_gap", "bag_gap", "oob_rows_gap",
              "oob_points_missing", "cover_gap_tree1"):
        assert nums[k] == 0, (k, nums)
    assert nums["value_gap"] < 1e-3
    assert nums["split_gap"] < 1e-4
    assert nums["leaf_value_gap"] < 1e-6
    assert nums["oob_logloss_gap"] < 1e-5 and nums["oob_final_gap"] < 1e-5
    # the cap cut at depth: its counters are the cut children the tree holds
    pulls = [e for e in TimeLine.snapshot() if "dur_ns" in e and
             (e["kind"], e["what"]) == ("train", "block.pull")]
    child = np.asarray(out["child"])
    assert [e["frontier_cut"] for e in pulls] == \
        [int((child[t] == -2).sum()) for t in range(2)]
    assert nums["cut_tree1"] == pulls[0]["frontier_cut"] > 0
    assert all(e["frontier_split_children"] > e["frontier_cut"] and
               e["frontier_levels"] > 0 for e in pulls)
    # a cap of 64 nodes: levels 6-19 are window levels, as at 65,536
    launches = [e for e in TimeLine.snapshot() if "dur_ns" in e and
                (e["kind"], e["what"]) == ("train", "block.launch")]
    assert {e["window_levels"] for e in launches} == {14}
