"""Growth hands every row's final node to the F update.

``build_tree_traced`` / ``build_tree_frontier`` route all R rows while
they grow a tree and return each row's final node (``pos``); the
trainer's F update is ``value[pos]``, no descent of the tree just grown.
Held here, for every engine and routing flavour a ``train_forest`` call
can take (the select form of ``_route_level`` at these shapes, its
gather form with the crossover pinned low, the matmul router):

- the positions growth returns are the nodes ``ops/descend.descend``
  reaches over the arrays the same call stored, ON EVERY ROW: the rows a
  tree is grown on, the rows sampled out of it and the inactive ones
  (padding, a missing response);
- every block's ``f_final`` is its ``F0`` plus, tree by tree,
  ``value[descend(...)]``: bit for bit where the leaves go into F
  unscaled, to 1e-6 under a leaf scale (XLA:CPU fuses the trainer's
  ``F + (value * scale)[node]`` into one multiply-add, as in
  ``test_carried_f.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _frames import frame_of, mixed_columns
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.tree import jit_engine as je
from h2o_tpu.ops.descend import descend

ROWS = 1501                    # not a multiple of the mesh: padded rows


def _float_frame(rng, response="binomial"):
    X = rng.normal(size=(ROWS, 6)).astype(np.float32)
    X[rng.uniform(size=X.shape) < 0.03] = np.nan
    z = 1.2 * np.nan_to_num(X[:, 0]) - 0.8 * np.nan_to_num(X[:, 1]) \
        + np.nan_to_num(X[:, 2] * X[:, 3])
    noise = rng.normal(size=ROWS)
    if response == "binomial":
        y = Vec((z + 0.6 * noise > 0).astype(np.int32), T_CAT,
                domain=["b", "s"])
    elif response == "multinomial":
        y = Vec(np.digitize(z + 0.5 * noise, [-0.7, 0.7]).astype(np.int32),
                T_CAT, domain=["a", "b", "c"])
    else:
        yy = (z + 0.3 * noise).astype(np.float32)
        yy[rng.uniform(size=ROWS) < 0.04] = np.nan     # inactive rows
        y = Vec(yy)
    return Frame([f"x{j}" for j in range(6)] + ["y"],
                 [Vec(X[:, j]) for j in range(6)] + [y])


def _mixed_frame(rng):
    return frame_of(*mixed_columns(int(rng.integers(2 ** 31)), 0.08,
                                   rows=ROWS))


def _gbm(**kw):
    from h2o_tpu.models.tree.gbm import GBM
    return GBM(**dict(dict(ntrees=3, max_depth=4, nbins=32, min_rows=3.0,
                           learn_rate=1.0, seed=11, score_tree_interval=1,
                           histogram_type="QuantilesGlobal"), **kw))


def _drf(**kw):
    from h2o_tpu.models.tree.drf import DRF
    return DRF(**dict(dict(ntrees=3, max_depth=4, nbins=32, min_rows=3.0,
                           seed=5, score_tree_interval=1,
                           histogram_type="QuantilesGlobal"), **kw))


# case -> (frame(rng), builder(), environment, rtol of F; 0 = bit for bit)
CASES = {
    "dense_global": (_float_frame, _gbm, {}, 0),
    "dense_global_learn_rate": (
        _float_frame, lambda: _gbm(learn_rate=0.1, score_tree_interval=0),
        {}, 1e-6),
    "dense_adaptive": (
        _float_frame, lambda: _gbm(histogram_type="UniformAdaptive",
                                   nbins=20, nbins_top_level=1024), {}, 0),
    "mixed_enum_na": (_mixed_frame, lambda: _gbm(nbins_cats=512), {}, 0),
    "mixed_enum_na_adaptive": (
        _mixed_frame, lambda: _gbm(histogram_type="UniformAdaptive",
                                   nbins=20, nbins_top_level=64,
                                   nbins_cats=512), {}, 0),
    "frontier_capped": (
        _float_frame, lambda: _gbm(max_depth=5, min_rows=1.0),
        {"H2O_TPU_MAX_LIVE_LEAVES": "4"}, 0),
    "frontier_capped_adaptive": (
        _mixed_frame, lambda: _gbm(max_depth=5, min_rows=1.0,
                                   histogram_type="UniformAdaptive",
                                   nbins=20, nbins_top_level=64),
        {"H2O_TPU_MAX_LIVE_LEAVES": "4"}, 0),
    "gbm_sample_rate": (_float_frame, lambda: _gbm(sample_rate=0.5), {}, 0),
    "drf_out_of_bag": (_float_frame, _drf, {}, 0),
    "drf_frontier_adaptive_regression": (
        functools.partial(_float_frame, response="regression"),
        lambda: _drf(max_depth=5, min_rows=1.0,
                     histogram_type="UniformAdaptive", nbins=20,
                     nbins_top_level=64),
        {"H2O_TPU_MAX_LIVE_LEAVES": "4"}, 0),
    "multinomial": (
        functools.partial(_float_frame, response="multinomial"), _gbm, {},
        1e-6),
    "monotone": (
        functools.partial(_float_frame, response="regression"),
        lambda: _gbm(monotone_constraints={"x0": 1, "x1": -1}), {}, 0),
    "matmul_route": (_float_frame, _gbm, {"H2O_TPU_MATMUL_ROUTE": "1"}, 0),
    "matmul_route_frontier_adaptive": (
        _mixed_frame, lambda: _gbm(max_depth=5, min_rows=1.0,
                                   histogram_type="UniformAdaptive",
                                   nbins=20, nbins_top_level=64),
        {"H2O_TPU_MATMUL_ROUTE": "1", "H2O_TPU_MAX_LIVE_LEAVES": "4"}, 0),
    # the routing crossover pinned low: every level gathers (min_rows
    # 4.0 keeps these programs apart from the select arm's in jit's cache)
    "mixed_enum_na_gather_route": (
        _mixed_frame, lambda: _gbm(nbins_cats=512, min_rows=4.0), {}, 0),
    "mixed_enum_na_adaptive_gather_route": (
        _mixed_frame, lambda: _gbm(histogram_type="UniformAdaptive",
                                   nbins=20, nbins_top_level=64,
                                   nbins_cats=512, min_rows=4.0), {}, 0),
}


def _recorded_blocks(monkeypatch):
    """Every ``train_forest`` call from here on: (its keywords, the F it
    was handed, what it returned)."""
    inner, blocks = je.train_forest, []

    def wrapper(**kw):
        f0 = np.array(kw["F0"])            # read before it can be donated
        tf = inner(**kw)
        blocks.append((kw, f0, tf))
        return tf
    monkeypatch.setattr(je, "train_forest", wrapper)
    return blocks


_descend = jax.jit(descend, static_argnames=("depth", "fine_na"))


def _descended(bins, kw, sc, bs, ch, th, na):
    """The nodes the one walk over a built tree reaches, as training's F
    update descended the tree it had just grown."""
    return np.asarray(_descend(
        bins, jnp.asarray(sc), jnp.asarray(bs), depth=kw["max_depth"],
        child=None if ch is None else jnp.asarray(ch),
        thr=jnp.asarray(th), na_l=jnp.asarray(na),
        fine_na=int(kw.get("fine_nbins") or kw["nbins"])))


def _engine_cfg(kw):
    """``_train_forest_impl``'s ``cfg`` for these keywords."""
    return dict(max_depth=kw["max_depth"], nbins=kw["nbins"],
                k_cols=kw["k_cols"], newton=kw["newton"],
                min_rows=kw["min_rows"],
                min_split_improvement=kw["min_split_improvement"],
                block_rows=kw.get("block_rows", 8192), bf16=False,
                reg_lambda=kw.get("reg_lambda", 0.0),
                use_mono=kw.get("use_mono", False),
                max_live_leaves=kw["kleaves"], sibling=kw["sibling"],
                adaptive=kw["adaptive"], fine_nbins=kw["fine_nbins"],
                hist_random=kw["hist_random"], pallas=False,
                mm_route=kw["mm_route"])


def _grower(kw):
    """The growth function these keywords pick, jitted once a case:
    (f0, seed) -> (the tree's arrays by name, the rows it was grown on).
    Each tree is grown on the residual of ``f0``, with rows sampled out
    of it besides the inactive ones."""
    cfg = _engine_cfg(kw)
    frontier = kw["kleaves"] > 0
    build = je.build_tree_frontier if frontier else je.build_tree_traced
    grow = jax.jit(lambda stats, leaf0, key: build(
        kw["bins"], stats, leaf0, key, kw["is_cat"], cfg, None,
        mono=kw.get("mono")))
    active, yv = np.asarray(kw["active"]), np.asarray(kw["yv"])

    def one(f0, seed):
        on = active & (np.random.default_rng(seed).uniform(
            size=active.size) < 0.6)
        g = np.where(on, yv - f0[:, 0], 0.0).astype(np.float32)
        wa = on.astype(np.float32)
        out = [np.asarray(o) for o in grow(
            jnp.stack([wa, wa * g, wa * g * g, wa], axis=1),
            jnp.where(jnp.asarray(on), 0, -1).astype(jnp.int32),
            jax.random.PRNGKey(seed))]
        sc, bs = out[:2]
        th, na, pos = out[-3:]
        return dict(sc=sc, bs=bs, ch=out[3] if frontier else None, th=th,
                    na=na, pos=pos), on
    return one


def _pool_depths(ch):
    """Depth of every pool node reached from the root (-1: unused)."""
    depth = np.full(ch.shape, -1)
    depth[0] = 0
    for n in np.flatnonzero(ch >= 0):      # a child's id is past its parent's
        if depth[n] >= 0:
            depth[ch[n]] = depth[ch[n] + 1] = depth[n] + 1
    return depth


@pytest.mark.parametrize("case", sorted(CASES))
def test_growth_leaves_every_row_where_a_descent_would(
        cl, rng, monkeypatch, case):
    frame, build, env, rtol = CASES[case]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    if case.endswith("_gather_route"):
        monkeypatch.setattr(je, "ROUTE_SELECT_MAX", 0)
    fr = frame(rng)
    blocks = _recorded_blocks(monkeypatch)
    model = build().train(y="y", training_frame=fr)
    assert blocks and sum(kw["ntrees"] for kw, _, _ in blocks) == 3
    kw0 = blocks[0][0]
    frontier = kw0["kleaves"] > 0
    assert frontier == ("H2O_TPU_MAX_LIVE_LEAVES" in env)
    assert kw0["mm_route"] == ("H2O_TPU_MATMUL_ROUTE" in env)
    assert kw0["adaptive"] == ("adaptive" in case)
    # which levels the select form routes: all but the matmul router's
    # (every level of a 32-bin table; none past its 128-bin limit, as the
    # mixed frame's) and the pinned gather's
    levels, selects = je.route_plan(kw0)
    assert levels == kw0["max_depth"]
    assert selects == (0 if case == "matmul_route" or
                       case.endswith("_gather_route") else levels)
    active = np.asarray(kw0["active"])
    assert (~active).any() and active.sum() >= ROWS * 0.9

    # ---- every block's F is F0 + value[descend], tree by tree
    na_bits = []
    for kw, f0, tf in blocks:
        sc, bs, vl, th, na = (np.asarray(a) for a in (
            tf.split_col, tf.bitset, tf.value, tf.thr_bin, tf.na_left))
        ch = None if tf.child is None else np.asarray(tf.child)
        assert (ch is not None) == frontier
        want = f0.copy()
        for t in range(kw["ntrees"]):
            want = want + np.stack([
                vl[t, k][_descended(kw["bins"], kw, sc[t, k], bs[t, k],
                                    None if ch is None else ch[t, k],
                                    th[t, k], na[t, k])]
                for k in range(kw["K"])], axis=1)
        got = np.asarray(tf.f_final)
        assert got.shape == want.shape == (active.size, kw["K"])
        if rtol:
            np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-6)
        else:
            np.testing.assert_array_equal(got, want)
        assert (sc[:, :, 0] >= 0).all()           # every tree split
        na_bits.append((bs[..., -1], sc))

    # ---- growth's own positions, on rows it was grown on and the rest
    grow_one = _grower(kw0)
    for i, (kw, f0, _) in enumerate(blocks):
        tree, on = grow_one(f0, seed=100 + i)
        want = _descended(kw["bins"], kw, tree["sc"], tree["bs"],
                          tree["ch"], tree["th"], tree["na"])
        np.testing.assert_array_equal(tree["pos"], want)
        # rows of every kind went below the root
        for rows in (on, active & ~on, ~active):
            assert rows.any() and (tree["pos"][rows] > 0).any()
        if frontier:
            # the cap bound: at some depth more children exist than the
            # frontier holds, so rows ended AT children that fell off it
            widths = je.frontier_plan(kw["max_depth"], kw["kleaves"])
            depths = _pool_depths(tree["ch"])
            per_depth = np.bincount(depths[depths >= 0])
            fell_off = [d for d, width in enumerate(widths)
                        if per_depth[d] > width]
            assert fell_off
            # ... and rows stand on leaves of such a depth, short of the
            # tree's last level
            assert np.isin(depths[np.unique(tree["pos"])], fell_off).any()

    if case.startswith("mixed_enum_na"):
        # enum splits whose left set is no prefix of the levels, and the
        # NA bucket on either side
        is_cat = np.asarray(kw0["is_cat"])
        nonprefix = 0
        for kw, _, tf in blocks:
            sc, bs = np.asarray(tf.split_col), np.asarray(tf.bitset)
            for col, left in zip(sc.ravel(), bs.reshape(-1, bs.shape[-1])):
                if col >= 0 and is_cat[col]:
                    lv = left[:-1]
                    nonprefix += bool(lv.any() and
                                      not lv[:int(lv.sum())].all())
        assert nonprefix >= 1
    if case == "mixed_enum_na":
        sides = {bool(b) for nab, sc in na_bits
                 for b in nab[sc >= 0].ravel()}
        assert sides == {True, False}
    if case == "monotone":
        assert kw0["use_mono"]
    if case == "multinomial":
        assert kw0["K"] == 3
    if case in ("drf_out_of_bag", "gbm_sample_rate"):
        assert kw0["sample_rate"] < 1.0
    assert model.output["ntrees_actual"] == 3
