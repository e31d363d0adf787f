"""Tiered column store + shard-direct landing (core/landing.py,
core/memory.py tiers, mrtask.FrameBlockStreamer).

The acceptance drills for training on frames bigger than HBM:

- shard-direct landing: no single host->device transfer ever exceeds
  ONE shard (landing.stats() pull accounting);
- streamed prepare_bins is BITWISE equal to the full-matrix path, and
  a bounded-HBM GBM produces a forest BITWISE equal to the unbounded
  run with ZERO steady-state recompiles;
- rollups / histogram / matrix results survive spill -> persist ->
  reload round-trips unchanged;
- T_TIME/T_STR residues tier host <-> persist (never HBM) and chunked
  ingest matches whole-array ingest exactly;
- chaos composition: an injected OOM mid-stream shrinks the resident
  window (counted degradation at site ``tier.block``) and the job
  still completes bitwise; a slice loss DURING a tiered train reforms
  the mesh and resumes bitwise.
"""

import time

import numpy as np
import pytest

from h2o_tpu.core.frame import Frame, T_CAT, T_STR, T_TIME, Vec

FOREST_KEYS = ("split_col", "value", "thr_bin", "bitset", "na_left")


def _forest_arrays(model):
    return {k: np.asarray(model.output[k]) for k in FOREST_KEYS
            if model.output.get(k) is not None}


@pytest.fixture()
def stream_env(monkeypatch):
    """Force streaming with a small shard-aligned window so a few
    hundred rows exercise many blocks."""
    # 32 per-shard rows with row_align=8: two shrink rungs (32->16->8)
    # under the ladder, and a few hundred rows still span many windows
    monkeypatch.setenv("H2O_TPU_TIER_STREAM", "1")
    monkeypatch.setenv("H2O_TPU_TIER_BLOCK_ROWS", "32")
    from h2o_tpu.core import landing
    landing.reset_stats()
    yield
    monkeypatch.setenv("H2O_TPU_TIER_STREAM", "0")


@pytest.fixture()
def chaos_clean():
    from h2o_tpu.core import chaos, oom
    chaos.reset()
    oom.reset_stats()
    yield
    chaos.reset()
    oom.reset_stats()


def _mixed_frame(rng, n=700):
    """Floats with NaN holes + a categorical + binary response —
    the layouts the streamed window assembly must reproduce."""
    x0 = rng.normal(size=n).astype(np.float32)
    x1 = rng.normal(size=n).astype(np.float32)
    x1[rng.random(n) < 0.15] = np.nan
    codes = rng.integers(-1, 3, size=n).astype(np.int32)  # -1 == NA
    y = (np.nan_to_num(x1) + x0 > 0).astype(np.int32)
    return Frame(
        ["x0", "x1", "c", "y"],
        [Vec(x0), Vec(x1), Vec(codes, T_CAT, domain=["a", "b", "c"]),
         Vec(y, T_CAT, domain=["n", "p"])])


def _gbm(**kw):
    from h2o_tpu.models.tree.gbm import GBM
    kw.setdefault("ntrees", 4)
    kw.setdefault("max_depth", 3)
    kw.setdefault("seed", 7)
    kw.setdefault("nbins", 16)
    kw.setdefault("histogram_type", "UniformAdaptive")
    return GBM(**kw)


# ---------------------------------------------------------------------------
# shard-direct landing
# ---------------------------------------------------------------------------

def test_landing_shard_direct_pull_accounting(cl, rng):
    """device_put_rows routes through the landing layer: each shard's
    slice transfers individually — the largest single transfer is one
    shard, never the whole column — and the values round-trip exactly
    (NaN row padding)."""
    from h2o_tpu.core import landing
    landing.reset_stats()
    n = cl.row_multiple() * 5 + 3          # deliberately unaligned
    host = rng.normal(size=n).astype(np.float32)
    arr = cl.device_put_rows(host)
    padded = arr.shape[0]
    assert padded % cl.row_multiple() == 0
    st = landing.stats()
    assert st["chunks_landed"] >= 1
    assert st["shard_transfers"] >= cl.n_nodes
    shard_bytes = (padded // cl.n_nodes) * host.dtype.itemsize
    assert 0 < st["max_transfer_bytes"] <= shard_bytes
    back = np.asarray(arr)
    np.testing.assert_array_equal(back[:n], host)
    assert np.isnan(back[n:]).all()


def test_landing_rows_equal_host_padding_nan(cl, rng):
    """The shard path's own check on a matrix whose rows are already
    aligned and one that needs padding: the landed rows equal the host
    rows, the padding is NaN, one transfer a shard and nothing wider."""
    from h2o_tpu.core import landing
    for n in (cl.row_multiple() * 2, cl.row_multiple() * 2 + 5):
        landing.reset_stats()
        host = rng.normal(size=(n, 3)).astype(np.float32)
        arr = cl.device_put_rows(host)
        st = landing.stats()
        assert st["chunks_landed"] == 1
        assert st["shard_transfers"] == cl.n_nodes
        assert st["bytes_landed"] == arr.nbytes
        assert st["max_transfer_bytes"] == arr.nbytes // cl.n_nodes
        back = np.asarray(arr)
        np.testing.assert_array_equal(back[:n], host)
        assert back.shape[0] % cl.row_multiple() == 0
        assert np.isnan(back[n:]).all()


# ---------------------------------------------------------------------------
# streamed binning: bitwise parity, zero steady-state recompiles
# ---------------------------------------------------------------------------

def test_streamed_prepare_bins_bitwise(cl, rng, stream_env):
    """Pass-1 blocked min/max and pass-2 window scatter reproduce the
    full-matrix BinnedData bit-for-bit (split points AND bins)."""
    import os
    from h2o_tpu.models.model import DataInfo
    from h2o_tpu.models.tree import shared_tree as st

    fr_full = _mixed_frame(rng)
    # identical data in a second frame
    fr_stream = Frame(fr_full.names,
                      [Vec(np.asarray(v.to_numpy()).copy(), v.type,
                           domain=list(v.domain) if v.domain else None)
                       for v in fr_full.vecs])
    os.environ["H2O_TPU_TIER_STREAM"] = "0"
    di_full = DataInfo(fr_full, ["x0", "x1", "c"], "y", mode="tree")
    b_full = st.prepare_bins(di_full, 16, 32, "UniformAdaptive", 64)
    os.environ["H2O_TPU_TIER_STREAM"] = "1"
    di_stream = DataInfo(fr_stream, ["x0", "x1", "c"], "y", mode="tree")
    b_stream = st.prepare_bins(di_stream, 16, 32, "UniformAdaptive", 64)
    np.testing.assert_array_equal(np.asarray(b_full.split_points),
                                  np.asarray(b_stream.split_points))
    np.testing.assert_array_equal(np.asarray(b_full.bins),
                                  np.asarray(b_stream.bins))
    assert b_full.bins.dtype == b_stream.bins.dtype


def test_streamed_gbm_bitwise_prefetch_and_zero_recompiles(
        cl, rng, stream_env):
    """The whole drill: a streamed GBM forest is BITWISE the full-path
    forest; the prefetcher overlaps (hits recorded); no window ever
    transfers more than one shard; and a repeat streamed train compiles
    NOTHING new (one window shape -> zero steady-state recompiles)."""
    import os
    from h2o_tpu.core import landing
    from h2o_tpu.core.diag import DispatchStats
    from h2o_tpu.core.memory import manager

    data = _mixed_frame(rng)

    def mk():
        return Frame(data.names,
                     [Vec(np.asarray(v.to_numpy()).copy(), v.type,
                          domain=list(v.domain) if v.domain else None)
                      for v in data.vecs])

    os.environ["H2O_TPU_TIER_STREAM"] = "0"
    ref = _forest_arrays(_gbm().train(y="y", training_frame=mk()))

    os.environ["H2O_TPU_TIER_STREAM"] = "1"
    ms0 = manager().stats()
    landing.reset_stats()
    m1 = _gbm().train(y="y", training_frame=mk())
    got = _forest_arrays(m1)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)

    ms1 = manager().stats()
    # streaming ran: every window went through the prefetcher (hit or
    # demand-page miss — the split is timing-dependent on CPU)
    windows0 = ms0["prefetch_hits"] + ms0["prefetch_misses"]
    windows1 = ms1["prefetch_hits"] + ms1["prefetch_misses"]
    assert windows1 > windows0
    st = landing.stats()
    full_matrix_bytes = data.padded_rows * 3 * 4
    assert st["max_transfer_bytes"] < full_matrix_bytes

    DispatchStats.install_xla_listener()
    c0 = DispatchStats.xla_compiles()
    m2 = _gbm().train(y="y", training_frame=mk())
    assert DispatchStats.xla_compiles() == c0, \
        "steady-state streamed train recompiled"
    got2 = _forest_arrays(m2)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got2[k], err_msg=k)


def test_bounded_hbm_budget_auto_streams_bitwise(cl, rng, monkeypatch):
    """TIER_STREAM=auto + an HBM budget smaller than the matrix: the
    gate trips on its own, training completes under the budget with
    block paging, and the forest matches the unbounded run bitwise."""
    from h2o_tpu.core.memory import manager, set_budget
    monkeypatch.setenv("H2O_TPU_TIER_STREAM", "auto")
    monkeypatch.setenv("H2O_TPU_TIER_BLOCK_ROWS", "16")

    data = _mixed_frame(rng, n=900)

    def mk():
        return Frame(data.names,
                     [Vec(np.asarray(v.to_numpy()).copy(), v.type,
                          domain=list(v.domain) if v.domain else None)
                      for v in data.vecs])

    ref = _forest_arrays(_gbm().train(y="y", training_frame=mk()))
    prev = manager().budget
    # smaller than the 3-col f32 matrix -> the auto gate must stream
    m = set_budget(data.padded_rows * 3 * 4 // 2)
    try:
        s0 = m.stats()
        p0 = s0["prefetch_hits"] + s0["prefetch_misses"]
        got = _forest_arrays(_gbm().train(y="y", training_frame=mk()))
        s1 = m.stats()
        assert s1["prefetch_hits"] + s1["prefetch_misses"] > p0
        for k in ref:
            np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    finally:
        set_budget(prev)


# ---------------------------------------------------------------------------
# spill -> persist -> reload round-trips
# ---------------------------------------------------------------------------

def test_rollups_histogram_matrix_across_persist_reload(cl, rng):
    """Rollups, histograms and the expanded matrix computed BEFORE a
    spill -> persist round-trip match what a reload computes after —
    the host tier's block store rehydrates bit-for-bit."""
    from h2o_tpu.core.memory import manager, set_budget
    n, p = 6_000, 6
    X = rng.normal(size=(n, p)).astype(np.float32)
    fr = Frame([f"x{j}" for j in range(p)],
               [Vec(X[:, j]) for j in range(p)])
    names = list(fr.names)
    before = {
        "matrix": np.asarray(fr.as_matrix(names)).copy(),
        "mean": [fr.vec(c).rollups.mean for c in names],
        "sigma": [fr.vec(c).rollups.sigma for c in names],
        "hist": [np.asarray(fr.vec(c).histogram(16)).copy()
                 for c in names],
    }
    prev = manager().budget
    m = set_budget(40_000)                 # force every column out
    try:
        assert m.spill_count > 0
        persisted = m.persist_sweep()      # host tier -> disk
        assert persisted > 0
        st = m.stats()
        assert st["tiers"]["persist"] > 0
        for j, c in enumerate(names):
            v = fr.vec(c)
            np.testing.assert_array_equal(np.asarray(v.to_numpy()),
                                          X[:, j])
            assert v.rollups.mean == before["mean"][j]
            assert v.rollups.sigma == before["sigma"][j]
            np.testing.assert_array_equal(
                np.asarray(v.histogram(16)), before["hist"][j])
        assert m.stats()["persist_reloads"] > 0
    finally:
        set_budget(prev)
    np.testing.assert_array_equal(np.asarray(fr.as_matrix(names)),
                                  before["matrix"])


def test_time_str_residues_chunked_parity_and_persist(cl):
    """T_TIME keeps an exact f64 residue and T_STR a host list — both
    tier host <-> persist (NEVER HBM) and chunked appends reproduce
    whole-array ingest exactly, across a persist round-trip."""
    from h2o_tpu.core.memory import manager
    t = (1.6e12 + np.arange(1000, dtype=np.float64) * 3600e3 + 0.25)
    s = [f"row-{i}" for i in range(1000)]

    whole = Frame(["t", "s"], [Vec(t, T_TIME), Vec(s, T_STR)])
    vt = Vec(t[:300], T_TIME)
    vs = Vec(s[:300], T_STR)
    chunked = Frame(["t", "s"], [vt, vs])
    for lo, hi in ((300, 650), (650, 1000)):
        vt.append(t[lo:hi])
        vs.append(s[lo:hi])

    # the T_STR residue never claims HBM
    assert vs._data is None
    assert whole.vec("s")._data is None
    # exact f64, not the device f32 round-trip
    np.testing.assert_array_equal(np.asarray(vt.to_numpy()), t)
    np.testing.assert_array_equal(np.asarray(whole.vec("t").to_numpy()),
                                  t)
    assert list(vs.to_numpy()) == s

    m = manager()
    wrote = m.persist_sweep()              # push residues to disk
    assert wrote > 0
    assert m.stats()["tiers"]["persist"] > 0
    # transparent reload, still exact
    np.testing.assert_array_equal(np.asarray(vt.to_numpy()), t)
    assert list(vs.to_numpy()) == s
    assert list(chunked.vec("s").host_data) == \
        list(whole.vec("s").host_data)


# ---------------------------------------------------------------------------
# chaos composition
# ---------------------------------------------------------------------------

def test_oom_mid_stream_shrinks_window_and_completes_bitwise(
        cl, rng, stream_env, chaos_clean):
    """Injected device OOM at the tier.block site: the ladder sweeps,
    then HALVES the resident window (a counted degradation), and the
    streamed train still produces the bitwise forest."""
    from h2o_tpu.core import chaos, oom

    data = _mixed_frame(rng)

    def mk():
        return Frame(data.names,
                     [Vec(np.asarray(v.to_numpy()).copy(), v.type,
                          domain=list(v.domain) if v.domain else None)
                      for v in data.vecs])

    def train():
        # score_tree_interval engages the driver's BLOCKED tree loop —
        # its tree.block ladder has shrink rungs (4 -> 2 -> 1), enough
        # to absorb fail-first-4 alongside the streamer's window rungs
        return _gbm(ntrees=8, score_tree_interval=4).train(
            y="y", training_frame=mk())

    ref = _forest_arrays(train())

    chaos.configure(oom_transient=2, seed=0)
    got = _forest_arrays(train())
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    site = oom.stats()["sites"].get("tier.block", {})
    assert site.get("oom_events", 0) >= 1
    assert site.get("sweeps", 0) >= 1

    # deeper injection walks past the sweeps into the shrink rung:
    # the window halves mid-stream and the forest is STILL bitwise
    chaos.configure(oom_transient=4, seed=0)
    oom.reset_stats()
    got2 = _forest_arrays(train())
    for k in ref:
        np.testing.assert_array_equal(ref[k], got2[k], err_msg=k)
    site = oom.stats()["sites"].get("tier.block", {})
    assert site.get("shrinks", 0) >= 1


@pytest.fixture()
def reboot():
    from h2o_tpu.core.cloud import Cloud
    saved = Cloud._instance

    def boot(n, m):
        return Cloud.boot(nodes=n, model_axis=m)

    yield boot
    with Cloud._lock:
        Cloud._instance = saved


@pytest.fixture()
def membership_clean():
    from h2o_tpu.core import chaos, membership
    membership.reset()
    yield membership.monitor()
    chaos.reset()
    membership.reset()


def test_slice_loss_during_tiered_train_reforms_and_resumes_bitwise(
        cl, rng, stream_env, reboot, tmp_path, membership_clean):
    """Composition with PR 12 elastic membership: a slice dies while a
    TIERED (streamed-bins) train is in flight; the monitor reforms the
    mesh and the resumed forest is bitwise the uninterrupted streamed
    run on the surviving mesh."""
    from h2o_tpu.core import chaos
    from h2o_tpu.core.oom import is_device_loss

    n = 512
    prg = np.random.default_rng(5)
    x0 = prg.integers(0, 16, size=n).astype(np.float32)
    x1 = prg.integers(0, 8, size=n).astype(np.float32)
    x2 = prg.integers(0, 4, size=n).astype(np.float32)
    yy = ((x0 + 2 * x1 + x2) % 2).astype(np.float32)

    def mk():
        return Frame(["x0", "x1", "x2", "y"],
                     [Vec(x0), Vec(x1), Vec(x2), Vec(yy)])

    def gbm(**kw):
        from h2o_tpu.models.tree.gbm import GBM
        return GBM(ntrees=4, max_depth=3, seed=7, nbins=16,
                   learn_rate=0.5, distribution="gaussian",
                   histogram_type="UniformAdaptive", **kw)

    mon = membership_clean
    rec = str(tmp_path / "rec")

    reboot(2, 2)
    ref = _forest_arrays(gbm().train(y="y", training_frame=mk()))

    reboot(4, 2)
    mon.configure(recovery_dir=rec, auto=True,
                  survivor_policy=lambda on, om, a:
                  {"nodes": max(1, on >> a), "model_axis": om})
    chaos.configure(slice_loss_at_block=2, seed=3)
    with pytest.raises(BaseException) as ei:
        gbm(recovery_dir=rec, checkpoint_interval=1,
            model_id="tier_ms").train(y="y", training_frame=mk())
    assert is_device_loss(ei.value), ei.value

    deadline = time.time() + 180.0
    while mon.epoch < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert mon.epoch >= 1, mon.events()
    assert mon.wait_stable(60)
    ev = mon.events()[-1]
    assert ev["ok"], ev
    assert ev["new_mesh"] == {"nodes": 2, "model": 2, "slices": 1}
    assert ev["jobs_resumed"] == 1

    assert len(mon.last_results) == 1
    m2 = mon.last_results[0]
    assert m2.output["ntrees_actual"] == 4
    got = _forest_arrays(m2)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
