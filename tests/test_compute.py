"""Compute-layer tests: quantiles and the histogram kernel.

Oracle strategy follows the reference's golden tests (SURVEY §4
testdir_golden): compare distributed results against numpy-computed truth.
"""

import numpy as np
import pytest


def test_quantile_matches_numpy(cl, rng):
    from h2o_tpu.core.frame import Vec
    from h2o_tpu.core.quantile import quantile_vec
    x = rng.normal(0, 10, size=20000).astype(np.float32)
    v = Vec(x)
    probs = [0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99]
    got = quantile_vec(v, probs)
    want = np.quantile(x, probs)
    span = x.max() - x.min()
    np.testing.assert_allclose(got, want, atol=span * 2e-3)


def test_quantile_with_nas_and_scalar(cl, rng):
    from h2o_tpu.core.frame import Vec
    from h2o_tpu.core.quantile import quantile_vec
    x = rng.uniform(-5, 5, size=5003).astype(np.float32)
    x[::7] = np.nan
    v = Vec(x)
    med = quantile_vec(v, 0.5)
    want = np.nanquantile(x, 0.5)
    assert abs(med - want) < 0.02
    assert np.isscalar(med) or med.ndim == 0


def test_quantile_frame_api(cl, rng):
    from h2o_tpu.core.frame import Frame
    from h2o_tpu.core.quantile import quantile
    fr = Frame.from_dict({"a": rng.normal(size=1000),
                          "b": rng.uniform(size=1000),
                          "c": np.array(["x", "y"] * 500)})
    q = quantile(fr, [0.5])
    assert set(q.keys()) == {"a", "b"}  # categorical excluded


def _np_hist(bins, leaf, stats, L, B):
    """numpy oracle for histogram_build."""
    out = np.zeros((L, bins.shape[1], B + 1, stats.shape[1]), np.float64)
    for r in range(bins.shape[0]):
        if leaf[r] < 0:
            continue
        for c in range(bins.shape[1]):
            out[leaf[r], c, bins[r, c]] += stats[r]
    return out


def test_histogram_build_matches_numpy(cl, rng):
    from h2o_tpu.ops.histogram import histogram_build
    from h2o_tpu.core.cloud import cloud
    R, C, L, B = 1000, 3, 4, 8
    bins_h = rng.integers(0, B + 1, size=(R, C)).astype(np.int32)
    leaf_h = rng.integers(-1, L, size=R).astype(np.int32)  # some inactive
    stats_h = rng.normal(size=(R, 4)).astype(np.float32)
    c = cloud()
    bins = c.device_put_rows(bins_h)
    leaf = c.device_put_rows(leaf_h)       # padding arrives as 0s...
    stats = c.device_put_rows(stats_h)
    # ...so force padded rows inactive via the real padded leaf array
    import jax.numpy as jnp
    pad = bins.shape[0] - R
    leaf_full = np.concatenate([leaf_h, np.full(pad, -1, np.int32)])
    leaf = c.device_put_rows(leaf_full)
    got = np.asarray(histogram_build(bins, leaf, stats, L, B,
                                     block_rows=128))
    want = _np_hist(bins_h, leaf_h, stats_h, L, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_histogram_build_remainder_block(cl, rng):
    """Shard size not divisible by block_rows exercises the remainder path."""
    from h2o_tpu.ops.histogram import histogram_build
    from h2o_tpu.core.cloud import cloud
    R, C, L, B = 333, 2, 2, 4
    bins_h = rng.integers(0, B + 1, size=(R, C)).astype(np.int32)
    leaf_h = rng.integers(0, L, size=R).astype(np.int32)
    stats_h = np.ones((R, 1), np.float32)
    c = cloud()
    pad_to = c.device_put_rows(bins_h).shape[0]
    leaf_full = np.concatenate([leaf_h, np.full(pad_to - R, -1, np.int32)])
    got = np.asarray(histogram_build(
        c.device_put_rows(bins_h), c.device_put_rows(leaf_full),
        c.device_put_rows(stats_h), L, B, block_rows=100))
    assert got[..., 0].sum() == pytest.approx(R * C)  # each col sums to R
    want = _np_hist(bins_h, leaf_h, stats_h, L, B)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _contraction_rows(jaxpr):
    """Rows contracted by every ``dot_general`` of a jaxpr, nested
    bodies (shard_map, scan) included."""
    rows = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            rows.append(eqn.invars[0].aval.shape[lhs_c[0]])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    rows += _contraction_rows(inner)
    return rows


@pytest.mark.parametrize("rows,block", [(1000, 96), (1000, 125), (640, 128)])
def test_histogram_cuts_every_block_at_one_shape(cl, rng, rows, block):
    """Whatever the rows leave over a block, every contraction of the
    program has the one shape (the rows left over are padded to a whole
    block of inactive rows), and every row is counted once: at the width
    at which the chip's compiler zeroed the table through a last block
    of its own, shorter shape (13 columns, 338 bins: PERF.md, PR 34; the
    parent contracts 32 and 3 rows beside 96 and 125 here)."""
    import jax
    from h2o_tpu.ops.histogram import histogram_build_traced
    from h2o_tpu.core.cloud import cloud
    C, L, B = 13, 2, 337
    bins_h = rng.integers(0, B + 1, size=(rows, C)).astype(np.int32)
    leaf_h = rng.integers(-1, L, size=rows).astype(np.int32)
    stats_h = rng.normal(size=(rows, 4)).astype(np.float32)
    stats_h[:, 0] = 1.0
    c = cloud()
    bins = c.device_put_rows(bins_h)
    pad = bins.shape[0] - rows
    leaf = c.device_put_rows(
        np.concatenate([leaf_h, np.full(pad, -1, np.int32)]))
    # padded rows carry NaN payloads, as a frame's do
    stats = c.device_put_rows(
        np.concatenate([stats_h, np.full((pad, 4), np.nan, np.float32)]))

    def build(b, l, s):
        return histogram_build_traced(b, l, s, L, B, block_rows=block)
    shard = bins.shape[0] // c.n_nodes
    contracted = _contraction_rows(
        jax.make_jaxpr(build)(bins, leaf, stats).jaxpr)
    assert contracted and set(contracted) == {min(block, shard)}
    got = np.asarray(jax.jit(build)(bins, leaf, stats))
    want = _np_hist(bins_h, leaf_h, stats_h, L, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert got[..., 0].sum() == C * int((leaf_h >= 0).sum())


def test_bin_features(cl):
    import jax.numpy as jnp
    from h2o_tpu.ops.histogram import bin_features
    m = jnp.array([[0.5, -1.0], [2.5, 0.0], [jnp.nan, 5.0]], jnp.float32)
    # col0 thresholds [1, 2]; col1 thresholds [0, nan-pad]
    sp = jnp.array([[1.0, 2.0], [0.0, jnp.nan]], jnp.float32)
    b = np.asarray(bin_features(m, sp))
    assert b.tolist() == [[0, 0], [2, 1], [3, 1]]  # NaN -> NA bucket (B=3)


@pytest.mark.parametrize("scored", [False, True])
def test_a_forest_grown_from_empty_tables_says_so(cl, rng, monkeypatch,
                                                  scored):
    """A histogram table that comes back all zero (what the chip's
    compiler once made of a 338-bin table: PERF.md, PR 34) gives trees of
    no split; the job carries a warning instead of handing the forest
    back in silence.  A sound job carries none."""
    import jax.numpy as jnp
    from h2o_tpu.core.frame import Frame
    from h2o_tpu.models.tree import jit_engine
    from h2o_tpu.models.tree.gbm import GBM
    X = rng.normal(size=(400, 3)).astype(np.float32)
    fr = Frame.from_numpy(np.column_stack([X, X[:, 0] + rng.normal(size=400)
                                           ]).astype(np.float32),
                          names=["a", "b", "c", "y"])
    kw = dict(ntrees=2, max_depth=2, min_rows=5, seed=1)
    if scored:
        kw["score_tree_interval"] = 1
    sound = GBM(**kw).train(y="y", training_frame=fr)
    assert not [w for w in sound.output.get("warnings", []) if "root" in w]
    real = jit_engine._shard_histogram
    monkeypatch.setattr(
        jit_engine, "_shard_histogram",
        lambda *a, **k: jnp.zeros_like(real(*a, **k)))
    # another depth: a program of its own, traced with the fault in
    empty = GBM(**dict(kw, max_depth=3)).train(y="y", training_frame=fr)
    assert int((np.asarray(empty.output["split_col"]) >= 0).sum()) == 0
    assert [w for w in empty.output["warnings"] if "root covers no row" in w]
