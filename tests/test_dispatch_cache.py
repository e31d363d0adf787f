"""Dispatch-overhaul regression tests.

The steady-state premise (SURVEY §3.3, one compiled program instead of an
MRTask fan-out) dies quietly if a hot path re-traces per call, so these
tests pin the dispatch layer's invariants:

- compile-count: N repeated ``map_reduce``/``map_frame``/rollup/quantile
  calls with identical shapes compile exactly once; a shape change
  compiles exactly once more (cache-miss count for the dispatch cache,
  backend-compile count via the jax monitoring listener for the
  module-level kernels).
- donation: trained-model outputs are bitwise-identical with
  H2O_TPU_DONATE=0/1 (on XLA:CPU donation is a no-op alias-wise, but it
  must select the donating executable without changing results).
- block loop: any partition of a forest into blocks (an early stop's
  discarded block included) builds the forest one block of ``ntrees``
  builds, bitwise, and the TimeLine event order proves block *t+1* is
  DISPATCHED before block *t* is materialized (the overlap).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from h2o_tpu.core.diag import DispatchStats, TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT


# module-level map fns: a per-test closure would (correctly) miss the
# cache on every call — the cache keys on function identity
def _colsum_masked(shard, mask_shard):
    return jnp.sum(jnp.where(mask_shard[:, None], shard, 0.0), axis=0)


def _double(m):
    return m * 2.0


def _negate(x):
    return -x


def _sharded_matrix(cl, rng, rows, cols):
    x = rng.normal(size=(rows, cols)).astype(np.float32)
    fr = Frame.from_numpy(x)
    mask = np.arange(fr.padded_rows) < fr.nrows
    from h2o_tpu.core.cloud import cloud
    return x, fr, cloud().device_put_rows(mask)


def _toy_binomial(rng, n=1200, c=4):
    X = rng.normal(size=(n, c)).astype(np.float32)
    logits = 2.0 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2]
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.int32)
    names = [f"x{j}" for j in range(c)] + ["y"]
    vecs = [Vec(X[:, j]) for j in range(c)] + \
        [Vec(y, T_CAT, domain=["no", "yes"])]
    return Frame(names, vecs)


def _gbm(rng, fr, **kw):
    from h2o_tpu.models.tree.gbm import GBM
    kw.setdefault("ntrees", 6)
    kw.setdefault("max_depth", 3)
    kw.setdefault("learn_rate", 0.3)
    kw.setdefault("seed", 7)
    return GBM(**kw).train(y="y", training_frame=fr)


def _forest_arrays(m):
    out = m.output
    return {k: np.asarray(out[k]) for k in
            ("split_col", "value", "varimp") if k in out}


# ---------------------------------------------------------------- cache


def test_map_reduce_compiles_once(cl, rng):
    from h2o_tpu.core.mrtask import dispatch_cache, map_reduce
    x, fr, msk = _sharded_matrix(cl, rng, 1000, 3)
    m = fr.as_matrix()
    DispatchStats.install_xla_listener()

    s0 = dispatch_cache().stats()
    out = map_reduce(_colsum_masked, m, msk)
    np.testing.assert_allclose(np.asarray(out), x.sum(axis=0), rtol=1e-4)
    c1 = DispatchStats.xla_compiles()
    for _ in range(4):                       # >= 5 calls total
        out = map_reduce(_colsum_masked, m, msk)
    s1 = dispatch_cache().stats()
    # exactly one compile across 5 identical-shape calls...
    assert s1["misses"] - s0["misses"] == 1
    assert s1["hits"] - s0["hits"] == 4
    # ...confirmed at the backend: the repeats built zero XLA programs
    assert DispatchStats.xla_compiles() == c1

    # a shape change is a different program: exactly one more compile
    x2, fr2, msk2 = _sharded_matrix(cl, rng, 1000, 5)
    out2 = map_reduce(_colsum_masked, fr2.as_matrix(), msk2)
    np.testing.assert_allclose(np.asarray(out2), x2.sum(axis=0), rtol=1e-4)
    s2 = dispatch_cache().stats()
    assert s2["misses"] - s1["misses"] == 1


def test_map_frame_compiles_once(cl, rng):
    from h2o_tpu.core.mrtask import dispatch_cache, map_frame
    x, fr, _ = _sharded_matrix(cl, rng, 800, 3)
    s0 = dispatch_cache().stats()
    for _ in range(5):
        out = map_frame(_double, fr)
    s1 = dispatch_cache().stats()
    assert s1["misses"] - s0["misses"] == 1
    assert s1["hits"] - s0["hits"] == 4
    np.testing.assert_allclose(np.asarray(out)[: fr.nrows], x * 2.0,
                               rtol=1e-5)


def test_rollups_steady_state_no_recompile(cl, rng):
    DispatchStats.install_xla_listener()
    n = 700
    Vec(rng.normal(size=n).astype(np.float32)).rollups      # warm shape
    c0 = DispatchStats.xla_compiles()
    for _ in range(5):
        v = Vec(rng.normal(size=n).astype(np.float32))
        r = v.rollups
        assert np.isfinite(r.mean)
    assert DispatchStats.xla_compiles() == c0               # zero new
    # a new shape compiles again (fresh program, counted)
    Vec(rng.normal(size=n + 64).astype(np.float32)).rollups
    assert DispatchStats.xla_compiles() > c0


def test_quantile_steady_state_no_recompile(cl, rng):
    from h2o_tpu.core.quantile import quantile_vec
    DispatchStats.install_xla_listener()
    v = Vec(rng.normal(size=900).astype(np.float32))
    probs = [0.25, 0.5, 0.75]
    q0 = quantile_vec(v, probs)                             # warm
    c0 = DispatchStats.xla_compiles()
    for _ in range(5):
        v2 = Vec(rng.normal(size=900).astype(np.float32))
        quantile_vec(v2, probs)
    assert DispatchStats.xla_compiles() == c0
    assert q0[0] <= q0[1] <= q0[2]


def test_mutate_array_cache_and_inplace(cl, rng):
    from h2o_tpu.core.mrtask import dispatch_cache
    x = rng.normal(size=600).astype(np.float32)
    v = Vec(x.copy())
    _ = v.rollups
    s0 = dispatch_cache().stats()
    v.map_inplace(_negate)
    np.testing.assert_array_equal(v.to_numpy(), -x)
    assert v._rollups is None                   # invalidated
    v2 = Vec(x.copy())
    v2.map_inplace(_negate)                     # same shape: cache hit
    s1 = dispatch_cache().stats()
    assert s1["misses"] - s0["misses"] == 1
    assert s1["hits"] - s0["hits"] == 1


def test_dispatch_rest_route(cl):
    from h2o_tpu.api.handlers import dispatch_route
    out = dispatch_route({})
    assert {"hits", "misses", "entries", "capacity"} <= set(out["cache"])
    assert "dispatches" in out["dispatch"]
    assert "xla_compiles" in out["dispatch"]


# -------------------------------------------------------------- donation


def test_donation_bitwise_identical(cl, rng, monkeypatch):
    fr = _toy_binomial(rng)
    monkeypatch.setenv("H2O_TPU_DONATE", "0")
    m_off = _gbm(rng, fr, score_tree_interval=2)
    monkeypatch.setenv("H2O_TPU_DONATE", "1")
    m_on = _gbm(rng, fr, score_tree_interval=2)
    a, b = _forest_arrays(m_off), _forest_arrays(m_on)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert m_off.output["training_metrics"]["logloss"] == \
        m_on.output["training_metrics"]["logloss"]


# ------------------------------------------------------------ block loop


def test_blocked_forest_bitwise_equals_one_block(cl, rng):
    # the driver's contract: any partition of the forest into blocks
    # reproduces the identical forest bitwise.  The reference is the
    # same forest trained in ONE block (block = ntrees).
    fr = _toy_binomial(rng)
    m_one = _gbm(rng, fr, score_tree_interval=6)
    m_blocked = _gbm(rng, fr, score_tree_interval=2)
    a, b = _forest_arrays(m_one), _forest_arrays(m_blocked)
    for k in ("split_col", "value"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # the blocks' importances are added on the host: another order
    np.testing.assert_allclose(a["varimp"], b["varimp"], rtol=1e-6)
    assert len(m_one.output["scoring_history"]) == 1
    assert len(m_blocked.output["scoring_history"]) == 3


def test_early_stop_keeps_prefix_of_one_block_forest(cl, rng):
    # the speculative-discard path: an early stop throws away the
    # already-launched block t+1 — the kept forest must be the prefix
    # of the same forest trained in one block with no stopping
    fr = _toy_binomial(rng, n=1500)
    m_stop = _gbm(rng, fr, ntrees=40, learn_rate=0.5,
                  stopping_rounds=2, stopping_tolerance=5e-2,
                  score_tree_interval=2)
    kept = int(m_stop.output["ntrees_actual"])
    assert 0 < kept < 40        # it did stop, and discarded a block
    m_one = _gbm(rng, fr, ntrees=40, learn_rate=0.5,
                 score_tree_interval=40)
    a, b = _forest_arrays(m_one), _forest_arrays(m_stop)
    for k in ("split_col", "value"):
        np.testing.assert_array_equal(a[k][:kept], b[k], err_msg=k)


def test_driver_overlaps_blocks(cl, rng):
    """The overlap proof: block t+1's device launch is recorded BEFORE
    block t's host materialization — host transfer of one block rides
    under the next block's compute."""
    fr = _toy_binomial(rng)
    TimeLine.clear()
    _gbm(rng, fr, ntrees=6, score_tree_interval=2)
    evs = [e for e in TimeLine.snapshot()
           if e["what"].startswith("tree_block_")]
    launches = {e["t0"]: i for i, e in enumerate(evs)
                if e["what"] == "tree_block_launch"}
    mats = {e["t0"]: i for i, e in enumerate(evs)
            if e["what"] == "tree_block_materialize"}
    assert set(launches) == {0, 2, 4} and set(mats) == {0, 2, 4}
    # block 2 launched before block 0 materialized, 4 before 2, ...
    for t0 in (0, 2):
        assert launches[t0 + 2] < mats[t0], (launches, mats)


def test_driver_overlap_under_slow_transfer(cl, rng):
    """Chaos slow-transfer widens the host window; the pipeline must
    still produce the bitwise-identical forest."""
    from h2o_tpu.core import chaos as chaos_mod
    fr = _toy_binomial(rng, n=800)
    m_ref = _gbm(rng, fr, score_tree_interval=2)
    chaos_mod.configure(transfer_slow_p=1.0, transfer_slow_ms=5, seed=0)
    try:
        m_slow = _gbm(rng, fr, score_tree_interval=2)
        assert chaos_mod.chaos().injected_slow_transfers >= 3
    finally:
        chaos_mod.configure()               # back to inert
    a, b = _forest_arrays(m_ref), _forest_arrays(m_slow)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
