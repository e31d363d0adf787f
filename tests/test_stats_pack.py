"""Quantized-gradient histograms (ops/statpack.py + tree.stats_dtype).

The contracts under test: (1) DECODE — per-slot scaling bounds every
dequantized stat by max|f|/qmax, and stochastic rounding is a pure
function of the per-tree fold_in key, so the same key reproduces the
same carrier bitwise.  (2) EXACTNESS — int32 tables built from the
carrier are exact integer sums, therefore invariant to block
partition, bitwise-equal under sibling subtraction vs the direct
build, and bitwise-identical across mesh shapes.  (3) REFERENCE —
with the lever unset on CPU the engine never draws quantization noise
and stays bitwise-identical to the forced-f32 forest, with zero
autotuner probes.  (4) TOLERANCE — the quantized forest's metrics sit
inside statpack.METRIC_TOL of f32, and the autotuner disqualifies a
candidate outside the lever's table tolerance band.
"""

import dataclasses

import numpy as np
import pytest

from h2o_tpu.core.frame import Frame, T_CAT, Vec

FOREST_KEYS = ("split_col", "value", "thr_bin", "bitset", "na_left",
               "child", "f0", "val_t")


@pytest.fixture(autouse=True)
def _stats_env(monkeypatch, cl):
    """Hermetic lever state; every test sets H2O_TPU_STATS_DTYPE
    itself (or deliberately leaves it unset)."""
    from h2o_tpu.core import autotune as at
    from h2o_tpu.ops import statpack as sp
    for v in ("H2O_TPU_STATS_DTYPE", "H2O_TPU_BINS_PACK",
              "H2O_TPU_AUTOTUNE", "H2O_TPU_EXEC_STORE_DIR"):
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setenv("H2O_TPU_AUTOTUNE_REPS", "1")
    at.reset()
    sp.reset_stats()
    yield
    at.reset()
    sp.reset_stats()


def _mixed_frame(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x1[::17] = np.nan
    cat = rng.integers(0, 5, n).astype(np.int32)
    cat[::13] = -1
    y = (np.nan_to_num(x1) + (cat == 2) > 0).astype(np.int32)
    return Frame(["x1", "x2", "y"],
                 [Vec(x1.astype(np.float32), ),
                  Vec(cat, T_CAT, domain=list("abcde")),
                  Vec(y, T_CAT, domain=["n", "p"])])


def _forest(model):
    return {k: np.asarray(model.output[k]) for k in FOREST_KEYS
            if model.output.get(k) is not None}


def _assert_bitwise(fa, fb):
    assert fa.keys() == fb.keys()
    for k in fa:
        assert fa[k].dtype == fb[k].dtype, k
        np.testing.assert_array_equal(fa[k], fb[k], err_msg=k)


def _train_gbm(monkeypatch, mode, fr, **kw):
    """mode: '1' force int16, '0' force f32, None leave unset (auto)."""
    from h2o_tpu.models.tree.gbm import GBM
    if mode is None:
        monkeypatch.delenv("H2O_TPU_STATS_DTYPE", raising=False)
    else:
        monkeypatch.setenv("H2O_TPU_STATS_DTYPE", mode)
    kw.setdefault("ntrees", 4)
    kw.setdefault("max_depth", 3)
    kw.setdefault("seed", 7)
    return GBM(**kw).train(y="y", training_frame=fr)


def _qstats(R=512, S=4, seed=3, dtype="int16"):
    import jax
    import jax.numpy as jnp
    from h2o_tpu.ops import statpack as sp
    rng = np.random.default_rng(seed)
    stats = jnp.asarray(rng.normal(size=(R, S)), jnp.float32)
    qmax = sp.stats_qmax(R, dtype)
    q, inv = sp.quantize_stats(stats, jax.random.PRNGKey(11), dtype,
                               qmax)
    return stats, q, inv, qmax


# ------------------------------------------------------ decode contract


def test_qmax_overflow_guard():
    """qmax is the carrier max tightened so int32 accumulation over
    every row can never overflow."""
    from h2o_tpu.ops import statpack as sp
    assert sp.stats_qmax(1024, "int16") == 32767
    assert sp.stats_qmax(1 << 20, "int16") == (2 ** 31 - 1) // (1 << 20)
    assert sp.stats_qmax(1 << 20, "int16") * (1 << 20) < 2 ** 31
    assert sp.stats_qmax(1024, "int8") == 127
    with pytest.raises(ValueError):
        sp.stats_qdtype("int64")


@pytest.mark.parametrize("dtype", ["int16", "int8"])
def test_decode_bound_and_key_determinism(dtype):
    """|dequant(q) - f| < max|f|/qmax per element, and the carrier is a
    pure function of the key: same key -> bitwise-same q, different
    key -> different stochastic rounding."""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.ops import statpack as sp
    stats, q, inv, qmax = _qstats(dtype=dtype)
    assert q.dtype == sp.stats_qdtype(dtype)
    deq = np.asarray(q.astype(jnp.float32) * inv[None, :])
    bound = np.max(np.abs(np.asarray(stats)), axis=0) / qmax
    err = np.abs(deq - np.asarray(stats))
    assert (err <= bound[None, :] + 1e-7).all(), err.max()
    q2, _ = sp.quantize_stats(stats, jax.random.PRNGKey(11), dtype,
                              qmax)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    q3, _ = sp.quantize_stats(stats, jax.random.PRNGKey(12), dtype,
                              qmax)
    assert not np.array_equal(np.asarray(q), np.asarray(q3))


# ----------------------------------------------- integer-exact tables


def test_quantized_table_block_partition_invariant():
    """The int32 table is an exact integer sum — identical under any
    scan block partition (the f32 build can only promise approximate
    equality under reordering)."""
    import jax.numpy as jnp
    from h2o_tpu.ops.histogram import histogram_build_traced
    R, C, B, L = 512, 3, 16, 8
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B + 1, (R, C)), jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, R), jnp.int32)
    _, q, _, _ = _qstats(R=R)
    t_small = histogram_build_traced(bins, leaf, q, L, B, block_rows=64)
    t_big = histogram_build_traced(bins, leaf, q, L, B,
                                   block_rows=8192)
    assert t_small.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(t_small),
                                  np.asarray(t_big))


def test_sibling_subtraction_bitwise_equal_direct_build():
    """Integer sibling subtraction (right = parent - left) is BITWISE
    equal to building every child histogram directly — the exactness
    claim the f32 path cannot make.  Includes an unsplit parent whose
    children must stay exactly zero."""
    import jax.numpy as jnp
    from h2o_tpu.models.tree.jit_engine import _hist_level_with_sibling
    from h2o_tpu.ops.histogram import histogram_build_traced
    R, C, B, L = 512, 3, 16, 8          # 4 parents -> 8 children
    P = L // 2
    rng = np.random.default_rng(1)
    bins = jnp.asarray(rng.integers(0, B + 1, (R, C)), jnp.int32)
    parent = rng.integers(0, P, R).astype(np.int32)
    went_right = rng.integers(0, 2, R).astype(np.int32)
    split = np.array([True, False, True, True])     # parent 1 unsplit
    slot = np.where(split[parent], 2 * parent + went_right, -1)
    _, q, _, _ = _qstats(R=R, seed=4)
    cfg = {"block_rows": 128, "bf16": False, "pallas": False}
    parent_hist = histogram_build_traced(
        bins, jnp.asarray(parent), q, P, B, block_rows=128)
    sib = _hist_level_with_sibling(
        bins, jnp.asarray(slot, jnp.int32), q, L, B, cfg,
        parent_hist, jnp.asarray(split))
    direct = histogram_build_traced(
        bins, jnp.asarray(slot, jnp.int32), q, L, B, block_rows=128)
    assert sib.dtype == jnp.int32 == direct.dtype
    np.testing.assert_array_equal(np.asarray(sib), np.asarray(direct))
    # the unsplit parent's children are exactly zero either way
    assert not np.asarray(direct)[2:4].any()


def test_find_splits_rejects_integer_table():
    """Split finding consumes the dequantized table only — handing it
    the raw int32 table is a contract violation caught at trace time
    (dequantize ONCE per level, never per row, never implicitly)."""
    import jax.numpy as jnp
    from h2o_tpu.models.tree.shared_tree import find_splits
    hist = jnp.zeros((4, 2, 17, 4), jnp.int32)
    is_cat = jnp.zeros((2,), bool)
    col_allowed = jnp.ones((4, 2), bool)
    with pytest.raises(TypeError, match="dequantize"):
        find_splits(hist, is_cat, col_allowed, min_rows=1.0)


# ------------------------------------------- forest-level guarantees


def test_quantized_forest_metrics_within_tolerance(monkeypatch):
    from h2o_tpu.ops import statpack as sp
    fr = _mixed_frame()
    mq = _train_gbm(monkeypatch, "1", fr)
    mf = _train_gbm(monkeypatch, "0", fr)
    assert mq.params.get("effective_stats_dtype") == "int16"
    assert mf.params.get("effective_stats_dtype") == "f32"
    lq = float(mq.output["training_metrics"]["logloss"])
    lf = float(mf.output["training_metrics"]["logloss"])
    assert abs(lq - lf) <= sp.METRIC_TOL, (lq, lf)
    c = sp.stats()
    assert c["quantized_trains"] >= 1 and c["f32_trains"] >= 1
    assert c["bytes_saved_est"] > 0


def test_cpu_unset_is_bitwise_f32_reference_zero_probes(monkeypatch):
    """H2O_TPU_STATS_DTYPE unset on CPU: auto resolves to the f32
    reference with ZERO probes, and the forest is bitwise-identical to
    the forced-f32 one — the quantizer draws no noise, folds no keys,
    perturbs nothing."""
    from h2o_tpu.core import autotune as at
    fr = _mixed_frame(seed=2)
    ma = _train_gbm(monkeypatch, None, fr)
    m0 = _train_gbm(monkeypatch, "0", fr)
    _assert_bitwise(_forest(ma), _forest(m0))
    assert ma.params.get("effective_stats_dtype") == "f32"
    assert at.stats()["probes"] == 0


def test_checkpoint_resume_across_stats_flip(monkeypatch):
    """A forest checkpointed under one stats carrier resumes VALIDLY
    under the other: checkpointed trees are preserved bitwise, the
    continued forest scores, and its metrics stay inside METRIC_TOL of
    the no-flip continuation."""
    from h2o_tpu.ops import statpack as sp
    fr = _mixed_frame(seed=6)
    m4 = _train_gbm(monkeypatch, "0", fr, ntrees=4)
    flip = _train_gbm(monkeypatch, "1", fr, ntrees=8, checkpoint=m4)
    stay = _train_gbm(monkeypatch, "0", fr, ntrees=8, checkpoint=m4)
    np.testing.assert_array_equal(
        np.asarray(flip.output["split_col"])[:4],
        np.asarray(m4.output["split_col"]))
    lq = float(flip.output["training_metrics"]["logloss"])
    lf = float(stay.output["training_metrics"]["logloss"])
    assert np.isfinite(lq) and abs(lq - lf) <= sp.METRIC_TOL
    p = flip.predict(fr)
    for n in p.names:
        assert np.isfinite(
            np.asarray(p.vec(n).to_numpy(), np.float64)).all()


@pytest.fixture()
def reboot():
    from h2o_tpu.core.cloud import Cloud
    saved = Cloud._instance
    yield lambda **f: Cloud.boot(**f)
    with Cloud._lock:
        Cloud._instance = saved


@pytest.mark.parametrize("mesh", [
    dict(nodes=1, model_axis=1),
    dict(nodes=2, model_axis=2),
    dict(slices=2, nodes=4, model_axis=2),
])
def test_quantized_build_parity_across_mesh_shapes(reboot, mesh):
    """The quantized histogram build is bitwise-identical on a 1x1, a
    2x2 and a two-slice (2,4,2) mesh: the stochastic-rounding draw
    depends only on (tree key, flat row index) and integer psum is
    associative, so no partition of the rows can perturb the int32
    table.  (The f32 build can make no such claim — its cross-shard
    float sums reorder.)"""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.core.cloud import Cloud
    from h2o_tpu.ops import statpack as sp
    from h2o_tpu.ops.histogram import histogram_build
    R, C, B, L = 512, 3, 16, 8
    rng = np.random.default_rng(5)
    bins_h = rng.integers(0, B + 1, (R, C)).astype(np.int32)
    leaf_h = rng.integers(0, L, R).astype(np.int32)
    stats_h = rng.normal(size=(R, 4)).astype(np.float32)
    qmax = sp.stats_qmax(R, "int16")

    def build():
        q, _ = sp.quantize_stats(jnp.asarray(stats_h),
                                 jax.random.PRNGKey(11), "int16", qmax)
        t = histogram_build(jnp.asarray(bins_h), jnp.asarray(leaf_h),
                            q, n_leaves=L, nbins=B, block_rows=64)
        assert t.dtype == jnp.int32
        return np.asarray(t)

    reboot(**mesh)
    got = build()
    with Cloud._lock:
        Cloud._instance = None
    reboot(nodes=1, model_axis=1)
    np.testing.assert_array_equal(got, build())


# -------------------------------------------------- autotuner gate


_SMALL_BUCKET = (1024, 4, 64)


def test_quantized_candidate_passes_tolerance_gate(monkeypatch):
    from h2o_tpu.core import autotune as at
    monkeypatch.setenv("H2O_TPU_AUTOTUNE", "force")
    rec = at.resolve("tree.stats_dtype", _SMALL_BUCKET)
    assert rec["candidates"]["int16"]["status"] == "ok"
    assert rec["winner"] in ("f32", "int16")


def test_corrupted_quantized_candidate_disqualified(monkeypatch):
    """A candidate whose dequantized table drifts outside the lever's
    tolerance band is disqualified — f32 ships, the train survives."""
    from h2o_tpu.core import autotune as at
    monkeypatch.setenv("H2O_TPU_AUTOTUNE", "force")
    real = at.lever("tree.stats_dtype")

    def corrupt(v, w):
        out = real.run_variant(v, w)
        return out + 10.0 if v == "int16" else out

    at.register_lever(dataclasses.replace(real, run_variant=corrupt))
    try:
        assert at.resolve_flag("tree.stats_dtype", _SMALL_BUCKET) \
            is False
        rec = at.resolve("tree.stats_dtype", _SMALL_BUCKET)
        assert rec["winner"] == "f32"
        assert rec["candidates"]["int16"]["status"] == "parity_fail"
        assert at.stats()["parity_disqualified"] >= 1
    finally:
        at.register_lever(real)


# ------------------------------------------------- byte accounting


def test_memory_stats_account_true_packed_stat_nbytes():
    """MemoryManager byte accounting is exact for a quantized stats
    holder: an int16 (R, S) carrier registers R*S*2 bytes — half of
    f32 — and the bytes model matches the real array."""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.core.memory import MemoryManager
    from h2o_tpu.ops import statpack as sp

    class Holder:
        pass

    R, S = 1024, 4
    stats = jnp.zeros((R, S), jnp.float32)
    q, _ = sp.quantize_stats(stats, jax.random.PRNGKey(0), "int16",
                             sp.stats_qmax(R, "int16"))
    assert q.nbytes == R * S * sp.stats_itemsize("int16") \
        == stats.nbytes // 2
    m = MemoryManager(0)
    h = Holder()
    m.register(h, q.nbytes)
    st = m.stats()
    assert st["resident_bytes"] == R * S * 2
    assert st["resident_vecs"] == 1
    assert st["largest_holders"] == [R * S * 2]
