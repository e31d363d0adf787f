"""The one walk down a built tree (``ops/descend.py``) and the one
routing rule of growth (``jit_engine._route_level``).

- ``value[descend(...)]``, summed over the trees, is the numpy MOJO
  scorer's output for every row, over {dense heap, frontier ``child``
  pointers} x {bitset-only model, adaptive ``thr``/``na_l`` model} x {28
  float columns; a frame with enum columns, one past 255 levels, and 2 %
  NA};
- RuleFit's terminal node ids are the numpy walk's;
- the gather form of a level's routing equals its matmul twin bit for
  bit on the tuner's own probe workload, adaptive and not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _frames import frame_of, mixed_columns
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.tree import jit_engine as je
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.gbm import GBM
from h2o_tpu.mojo.scorers import _forest_score
from h2o_tpu.ops.descend import descend

ROWS, DEPTH = 2000, 4


def _float_frame(rng):
    X = rng.normal(size=(ROWS, 28)).astype(np.float32)
    z = 1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (rng.random(ROWS) < 1 / (1 + np.exp(-z))).astype(np.int32)
    return Frame([f"x{j}" for j in range(28)] + ["y"],
                 [Vec(X[:, j]) for j in range(28)] +
                 [Vec(y, T_CAT, domain=["b", "s"])])


def _mixed_frame(rng):
    """Three numeric and three enum columns (3 / 29 / 352 levels), 2 % NA
    in a numeric column, 1 % in an enum."""
    return frame_of(*mixed_columns(int(rng.integers(2 ** 31)), 0.02,
                                   rows=ROWS))


FRAMES = {"float28": _float_frame, "enum_na": _mixed_frame}
# histogram types: one stores bitsets only, one fine-bin thresholds
MODELS = {"bitset": dict(histogram_type="QuantilesGlobal", nbins=32),
          "adaptive": dict(histogram_type="UniformAdaptive", nbins=16,
                           nbins_top_level=64)}


def _numpy_nodes(bins, sc, bs, depth):
    """The MOJO scorer's walk with each node's own id as its value: the
    row's final node, (T, R)."""
    ids = np.arange(sc.shape[-1], dtype=np.float64)[None, None]
    return np.stack([
        _forest_score(bins, sc[t:t + 1], bs[t:t + 1], ids, depth)[:, 0]
        for t in range(sc.shape[0])]).astype(np.int64)


@pytest.mark.parametrize("frame", sorted(FRAMES))
@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("layout", ["dense", "frontier"])
def test_descend_equals_the_mojo_walk(cl, monkeypatch, layout, model, frame):
    if layout == "frontier":
        # a cap under 2^(DEPTH-1) leaves: the pool engine, child pointers
        monkeypatch.setenv("H2O_TPU_MAX_LIVE_LEAVES", "4")
    fr = FRAMES[frame](np.random.default_rng(2 ** 31 + 7))
    m = GBM(ntrees=3, max_depth=DEPTH, min_rows=5, seed=3,
            **MODELS[model]).train(y="y", training_frame=fr)
    out = m.output
    assert (out.get("child") is not None) == (layout == "frontier")
    sc, bs, vl = (np.asarray(out[k]) for k in
                  ("split_col", "bitset", "value"))
    ch = np.asarray(out["child"]) if layout == "frontier" else None
    thr = np.asarray(out["thr_bin"])
    if model == "bitset":
        assert (thr < 0).all()
        thr_kw = dict(thr=None, na_l=None, fine_na=-1)
    else:
        assert (thr >= 0).any()
        thr_kw = dict(thr=thr, na_l=np.asarray(out["na_left"]),
                      fine_na=st.model_fine_na(out))
    if frame == "enum_na":
        # the forest does split on an enum column and routes NA rows
        assert np.asarray(out["is_cat"])[sc[sc >= 0]].any()
    bins = st.bin_matrix_out(fr.as_matrix(out["x"]), out)
    bins_np = np.asarray(bins)
    if frame == "enum_na":
        assert (bins_np == st.model_fine_na(out)).any()
    depth = int(out["max_depth"])
    # the scorer adds each tree's float32 leaf value to a float64 sum, tree
    # by tree: the same sum over descend's nodes is equal to the last bit
    got = np.zeros(bins_np.shape[0], np.float64)     # padded rows too
    for t in range(sc.shape[0]):
        node = descend(bins, *(jnp.asarray(a[t, 0]) for a in (sc, bs)),
                       depth, child=None if ch is None
                       else jnp.asarray(ch[t, 0]),
                       **{k: jnp.asarray(v[t, 0]) if isinstance(v, np.ndarray)
                          else v for k, v in thr_kw.items()})
        assert len(np.unique(np.asarray(node))) > 2      # it did descend
        got += vl[t, 0][np.asarray(node)]
    want = _forest_score(bins_np, sc, bs, vl, depth, child=ch, **thr_kw)
    np.testing.assert_array_equal(got, want[:, 0])


def test_rulefit_terminal_nodes_are_the_walks(cl):
    from h2o_tpu.models.rulefit import _terminal_nodes
    fr = _mixed_frame(np.random.default_rng(2 ** 31 + 9))
    m = GBM(ntrees=3, max_depth=3, min_rows=5, seed=3,
            **MODELS["bitset"]).train(y="y", training_frame=fr)
    out = m.output
    assert out.get("child") is None
    sc, bs = np.asarray(out["split_col"]), np.asarray(out["bitset"])
    bins = st.bin_matrix_out(fr.as_matrix(out["x"]), out)
    depth = int(out["max_depth"])
    got = np.asarray(_terminal_nodes(bins, jnp.asarray(sc[:, 0]),
                                     jnp.asarray(bs[:, 0]), depth))
    want = _numpy_nodes(np.asarray(bins), sc, bs, depth)
    np.testing.assert_array_equal(got, want.T)


@pytest.mark.parametrize("adaptive", [False, True])
def test_gather_route_equals_matmul_route(cl, adaptive):
    from h2o_tpu.core.autotune import _mm_workload
    w = _mm_workload((4096, 8, 16, 32))
    L, Bd = w["L"], w["Bd"]
    s = {"col": w["col"], "bitset": w["bitset"] > 0.5,
         "na_left": w["na_left"] > 0.5}
    thr = w["thr"].astype(jnp.int32)

    @jax.jit
    def both(bins, lf, s, do_split, thr, cat_choice):
        return (je._route_level(bins, lf, s, do_split, Bd,
                                       cat_choice, adaptive, thr, Bd),
                je._mm_route_level(bins, lf, s, do_split, L, Bd,
                                   cat_choice, adaptive, thr, Bd))

    (g_go, g_do), (m_go, m_do) = both(w["bins"], w["lf"], s,
                                      w["do_split"], thr, w["cat_choice"])
    np.testing.assert_array_equal(np.asarray(g_go), np.asarray(m_go))
    np.testing.assert_array_equal(np.asarray(g_do), np.asarray(m_do))
    assert 0 < int(np.asarray(g_go).sum()) < g_go.shape[0]
    # NA rows (bin Bd) reached both routers
    assert (np.asarray(w["bins"]) == Bd).any()
