"""Dtype-packed binned matrices (ops/binpack.py + tree.bins_dtype lever).

The decode contract under test: a packed matrix (uint8/int16 by fine
bin count) holds EXACTLY the same integers as the int32 reference — so
every consumer (histogram kernels, routers, scorers, MOJO export,
contributions) must produce BITWISE-identical results under either
carrier, on any mesh shape, across checkpoint-resume, and the
autotuner's parity gate must disqualify any packed kernel that breaks
that promise.  The no-HBM-copy half is checked structurally: the traced
histogram program may widen per-block (in-register), never the full
matrix.
"""

import dataclasses

import numpy as np
import pytest

from h2o_tpu.core.frame import Frame, T_CAT, Vec

FOREST_KEYS = ("split_col", "value", "thr_bin", "bitset", "na_left",
               "child", "f0", "val_t")


@pytest.fixture(autouse=True)
def _pack_env(monkeypatch, cl):
    """Hermetic lever state; every test sets H2O_TPU_BINS_PACK itself."""
    from h2o_tpu.core import autotune as at
    for v in ("H2O_TPU_BINS_PACK", "H2O_TPU_AUTOTUNE",
              "H2O_TPU_EXEC_STORE_DIR"):
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setenv("H2O_TPU_AUTOTUNE_REPS", "1")
    at.reset()
    yield
    at.reset()


def _mixed_frame(n=256, seed=0):
    """NaNs in a numeric column + a categorical with -1 missing codes —
    both halves of the sentinel remap the decode contract covers."""
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


def _train_gbm(monkeypatch, pack, fr, **kw):
    from h2o_tpu.models.tree.gbm import GBM
    monkeypatch.setenv("H2O_TPU_BINS_PACK", pack)
    kw.setdefault("ntrees", 4)
    kw.setdefault("max_depth", 3)
    kw.setdefault("seed", 7)
    return GBM(**kw).train(y="y", training_frame=fr)


# ------------------------------------------------------ decode contract


def test_dtype_selection_boundaries():
    import jax.numpy as jnp
    from h2o_tpu.ops import binpack as bp
    assert bp.bins_dtype_for(64) == jnp.uint8
    assert bp.bins_dtype_for(255) == jnp.uint8      # F==255 still fits
    assert bp.bins_dtype_for(256) == jnp.int16      # spills to int16
    assert bp.bins_dtype_for(32767) == jnp.int16
    assert bp.bins_dtype_for(32768) == jnp.int32
    assert bp.packed_dtype_name(64, True) == "uint8"
    assert bp.packed_dtype_name(64, False) == "int32"


@pytest.mark.parametrize("F", [64, 255, 256])
def test_na_and_cat_roundtrip_at_dtype_boundary(F):
    """NA sentinel (bin == F) and clipped categorical codes (incl. the
    -1 missing-level code) survive the narrow carrier value-for-value at
    the uint8 boundary and across the int16 spill."""
    import jax.numpy as jnp
    from h2o_tpu.models.tree import shared_tree as st
    from h2o_tpu.ops import binpack as bp
    rng = np.random.default_rng(F)
    R, C = 128, 2
    m = rng.normal(size=(R, C)).astype(np.float32)
    m[::7, 0] = np.nan                       # numeric NAs
    m[:, 1] = rng.integers(-1, 5, R)         # cat codes with -1 missing
    sp = np.sort(rng.normal(size=(C, F - 1)), axis=1).astype(np.float32)
    is_cat = np.array([False, True])
    ref = np.asarray(st._bin_all(jnp.asarray(m), jnp.asarray(sp),
                                 jnp.asarray(is_cat), F))
    packed = st._bin_all(jnp.asarray(m), jnp.asarray(sp),
                         jnp.asarray(is_cat), F,
                         out_dtype=bp.packed_dtype_name(F, True))
    assert packed.dtype == bp.bins_dtype_for(F)
    got = np.asarray(packed)
    np.testing.assert_array_equal(got.astype(np.int32), ref)
    assert (got[::7, 0] == F).all()          # NA sentinel round-trips
    assert got.max() <= F and got.astype(np.int64).min() >= 0
    # -1 cat codes clipped into [0, F-1], i.e. decodable unsigned
    assert (got[m[:, 1] == -1, 1] == 0).all()


# ---------------------------------------------- bitwise forest parity


def test_gbm_forest_parity_and_predict(monkeypatch):
    fr = _mixed_frame()
    m1 = _train_gbm(monkeypatch, "1", fr)
    m0 = _train_gbm(monkeypatch, "0", fr)
    _assert_bitwise(_forest(m1), _forest(m0))
    p1, p0 = m1.predict(fr), m0.predict(fr)
    for n in p1.names:
        np.testing.assert_array_equal(np.asarray(p1.vec(n).to_numpy()),
                                      np.asarray(p0.vec(n).to_numpy()))


def test_drf_forest_parity(monkeypatch):
    from h2o_tpu.models.tree.drf import DRF
    fr = _mixed_frame(seed=1)
    monkeypatch.setenv("H2O_TPU_BINS_PACK", "1")
    m1 = DRF(ntrees=4, max_depth=3, seed=3).train(y="y",
                                                  training_frame=fr)
    monkeypatch.setenv("H2O_TPU_BINS_PACK", "0")
    m0 = DRF(ntrees=4, max_depth=3, seed=3).train(y="y",
                                                  training_frame=fr)
    _assert_bitwise(_forest(m1), _forest(m0))


def test_uplift_forest_parity(monkeypatch):
    from h2o_tpu.models.tree.uplift import UpliftDRF
    rng = np.random.default_rng(2)
    n = 512
    X = rng.normal(size=(n, 2)).astype(np.float32)
    treat = rng.integers(0, 2, n).astype(np.int32)
    y = ((X[:, 0] > 0) & (treat == 1)).astype(np.int32)
    fr = Frame(["x0", "x1", "treatment", "y"],
               [Vec(X[:, 0]), Vec(X[:, 1]),
                Vec(treat, T_CAT, domain=["0", "1"]),
                Vec(y, T_CAT, domain=["0", "1"])])

    def train():
        return UpliftDRF(treatment_column="treatment", ntrees=3,
                         max_depth=3, seed=4).train(
            x=["x0", "x1"], y="y", training_frame=fr)

    monkeypatch.setenv("H2O_TPU_BINS_PACK", "1")
    m1 = train()
    monkeypatch.setenv("H2O_TPU_BINS_PACK", "0")
    m0 = train()
    _assert_bitwise(_forest(m1), _forest(m0))


@pytest.fixture()
def reboot():
    """Boot differently-shaped meshes, restoring the session Cloud
    instance at teardown (test_mesh_resize idiom)."""
    from h2o_tpu.core.cloud import Cloud
    saved = Cloud._instance
    yield lambda n, m: Cloud.boot(nodes=n, model_axis=m)
    with Cloud._lock:
        Cloud._instance = saved


@pytest.mark.parametrize("mesh", [(1, 1), (2, 2)])
def test_forest_parity_across_mesh_shapes(monkeypatch, reboot, mesh):
    """Packed == int32 bitwise on a 1x1 and a 2x2 nodes x model mesh —
    packing must not perturb sharded-collective numerics."""
    reboot(*mesh)
    fr = _mixed_frame(seed=5)
    m1 = _train_gbm(monkeypatch, "1", fr)
    m0 = _train_gbm(monkeypatch, "0", fr)
    _assert_bitwise(_forest(m1), _forest(m0))


# ---------------------------------------- resume / scoring-path parity


def test_checkpoint_resume_across_pack_flip(monkeypatch):
    """A forest checkpointed under one carrier resumes bitwise under
    the other: bin VALUES are identical, so the flip is invisible."""
    fr = _mixed_frame(seed=6)
    m4 = _train_gbm(monkeypatch, "1", fr, ntrees=4)
    flip = _train_gbm(monkeypatch, "0", fr, ntrees=8, checkpoint=m4)
    stay = _train_gbm(monkeypatch, "1", fr, ntrees=8, checkpoint=m4)
    _assert_bitwise(_forest(flip), _forest(stay))
    np.testing.assert_array_equal(
        np.asarray(flip.output["split_col"])[:4],
        np.asarray(m4.output["split_col"]))


def test_mojo_scoring_parity_on_packed_bins(monkeypatch, tmp_path):
    from h2o_tpu.mojo import export_mojo, load_mojo
    fr = _mixed_frame(seed=8)
    m1 = _train_gbm(monkeypatch, "1", fr)
    m0 = _train_gbm(monkeypatch, "0", fr)
    paths = []
    for tag, m in (("p", m1), ("r", m0)):
        path = str(tmp_path / f"gbm_{tag}.zip")
        export_mojo(m, path)
        paths.append(path)
    mp, mr = load_mojo(paths[0]), load_mojo(paths[1])
    Xs = np.stack([np.asarray(fr.vec(c).to_numpy(), np.float64)
                   for c in mp.columns], axis=1)
    np.testing.assert_array_equal(np.asarray(mp.score_matrix(Xs)),
                                  np.asarray(mr.score_matrix(Xs)))
    # standalone still matches the in-cluster packed model
    incluster = np.asarray(m1.predict_raw(fr))[: fr.nrows]
    np.testing.assert_allclose(np.asarray(mp.score_matrix(Xs)),
                               incluster, atol=1e-4, rtol=1e-4)


def test_contributions_parity_on_packed_bins(monkeypatch):
    fr = _mixed_frame(seed=9)
    m1 = _train_gbm(monkeypatch, "1", fr)
    m0 = _train_gbm(monkeypatch, "0", fr)
    c1 = m1.predict_contributions(fr)
    c0 = m0.predict_contributions(fr)
    assert c1.names == c0.names
    for n in c1.names:
        np.testing.assert_array_equal(np.asarray(c1.vec(n).to_numpy()),
                                      np.asarray(c0.vec(n).to_numpy()))


# -------------------------------------------------- autotuner gate


_SMALL_BUCKET = (1024, 4, 64)


def test_packed_candidate_passes_bitwise_parity_gate(monkeypatch):
    """The real lever, force-probed on a small bucket: the packed
    candidate must clear the (0.0, 0.0) parity gate — its histogram is
    bitwise-equal the int32 reference's."""
    from h2o_tpu.core import autotune as at
    monkeypatch.setenv("H2O_TPU_AUTOTUNE", "force")
    rec = at.resolve("tree.bins_dtype", _SMALL_BUCKET)
    assert rec["candidates"]["packed"]["status"] == "ok"
    assert rec["winner"] in ("int32", "packed")


def test_corrupted_packed_kernel_disqualified(monkeypatch):
    """Acceptance drill: a deliberately-corrupted packed kernel is
    parity-disqualified — the int32 reference ships, never the broken
    packed path, and the caller sees a clean decision."""
    from h2o_tpu.core import autotune as at
    monkeypatch.setenv("H2O_TPU_AUTOTUNE", "force")
    real = at.lever("tree.bins_dtype")

    def corrupt(v, w):
        out = real.run_variant(v, w)
        return out + 1.0 if v == "packed" else out

    at.register_lever(dataclasses.replace(real, run_variant=corrupt))
    try:
        assert at.resolve_flag("tree.bins_dtype", _SMALL_BUCKET) is False
        rec = at.resolve("tree.bins_dtype", _SMALL_BUCKET)
        assert rec["winner"] == "int32"
        assert rec["candidates"]["packed"]["status"] == "parity_fail"
        assert at.stats()["parity_disqualified"] >= 1
    finally:
        at.register_lever(real)       # restore the uncorrupted lever


def test_cpu_auto_stays_int32_reference():
    """Off-TPU, auto mode resolves to the int32 reference with zero
    probes — CPU tiers stay bitwise-identical to the pre-packing
    engine by default."""
    from h2o_tpu.core import autotune as at
    assert at.resolve_flag("tree.bins_dtype") is False
    assert at.stats()["probes"] == 0


def test_traced_matrix_resolves_no_lever(monkeypatch):
    """The serve engine compiles a model's whole predict, so bin_matrix
    sees a tracer there.  A probe cannot run mid-trace (on the chip,
    where probing is the default, it raised and was swallowed as a
    resolve error on every /score bucket): a traced matrix takes the
    int32 reference and asks the autotuner nothing."""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.core import autotune as at
    from h2o_tpu.models.tree import shared_tree as st
    monkeypatch.setenv("H2O_TPU_AUTOTUNE", "force")
    before = at.stats()
    sp = jnp.linspace(-1.0, 1.0, 63, dtype=jnp.float32)[None, :]
    x = jnp.zeros((8, 1), jnp.float32)
    b = jax.jit(lambda m: st.bin_matrix(m, sp, np.zeros(1, bool), 64))(x)
    assert b.dtype == jnp.int32
    after = at.stats()
    assert (after["probes"], after["resolve_errors"]) == \
        (before["probes"], before["resolve_errors"])


# ------------------------------------------- no-HBM-upcast structure


def test_no_full_matrix_int32_convert_in_traced_histogram():
    """Structural half of the no-HBM-copy criterion: the traced
    histogram program on packed bins contains NO convert_element_type
    to int32 at the FULL matrix shape — only per-block (in-register)
    widens inside the scan body."""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.ops.histogram import histogram_build_traced

    R, C, B, L = 16384, 4, 16, 8          # 2 scan blocks of 8192 rows
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B + 1, (R, C)), jnp.uint8)
    leaf = jnp.asarray(rng.integers(0, L, R), jnp.int32)
    stats = jnp.asarray(rng.random((R, 4)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda b, l, s: histogram_build_traced(b, l, s, L, B)
    )(bins, leaf, stats)

    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(jx):
        for eqn in jx.eqns:
            yield eqn
            for v in eqn.params.values():
                for s in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(s, ClosedJaxpr):
                        yield from walk(s.jaxpr)
                    elif isinstance(s, Jaxpr):
                        yield from walk(s)

    offenders = [
        e for e in walk(jaxpr.jaxpr)
        if e.primitive.name == "convert_element_type"
        and e.params.get("new_dtype") == jnp.int32
        and tuple(e.invars[0].aval.shape) == (R, C)]
    assert not offenders, offenders


def test_packed_train_adds_no_host_pulls(monkeypatch):
    """Runtime half: a packed train makes no MORE host pulls than the
    int32 reference train — packing never bounces the matrix through
    the host to widen it."""
    from h2o_tpu.core.diag import DispatchStats

    def pulls_during(pack, seed):
        before = sum(DispatchStats.snapshot()["host_pulls"].values())
        _train_gbm(monkeypatch, pack, _mixed_frame(seed=seed))
        return sum(DispatchStats.snapshot()["host_pulls"].values()) \
            - before

    base = pulls_during("0", 11)
    packed = pulls_during("1", 11)
    assert packed <= base, (packed, base)


def test_memory_stats_account_true_packed_nbytes():
    """MemoryManager byte accounting is exact for a packed holder: a
    uint8 (R, C) matrix registers R*C bytes — a quarter of int32."""
    import jax.numpy as jnp
    from h2o_tpu.core.memory import MemoryManager
    from h2o_tpu.ops import binpack as bp

    class Holder:
        pass

    R, C = 1024, 8
    bins32 = jnp.zeros((R, C), jnp.int32)
    packed = bp.cast_bins(bins32, bp.bins_dtype_for(64))
    assert packed.nbytes == R * C == bins32.nbytes // 4
    m = MemoryManager(0)
    h = Holder()
    m.register(h, packed.nbytes)
    st = m.stats()
    assert st["resident_bytes"] == R * C
    assert st["resident_vecs"] == 1
    assert st["largest_holders"] == [R * C]
