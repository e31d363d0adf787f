"""Two-level ``slices x nodes`` mesh: hierarchical collectives (ISSUE 18).

The tentpole contract for core/cloud.py's two-level topology and the
``hpsum``/``hall_gather``/``hall_to_all`` helper layer:

- ``H2O_TPU_SLICES=1`` (the default) is byte-identical to the flat
  mesh — same axis layout, same programs;
- on a two-level mesh every munge verb, fused Rapids region and GBM
  forest is BITWISE equal to the flat-mesh run on the same shard count
  (the helpers lower to product-axis collectives, which XLA reduces in
  the same order as the flat axis) and to the host oracles;
- the per-axis byte ledger (DispatchStats.note_collective) records DCN
  bytes only on two-level meshes, and only for the combine collectives
  — O(table) cross-slice traffic, never O(rows): the same bytes at two
  row counts;
- the membership survivor policy drops a whole SLICE per attempt on a
  two-level mesh (an ICI island is the DCN failure unit), and a slice
  loss mid-train reforms to the surviving slice and resumes bitwise;
- recovery snapshots stamp the slice dimension plus the data geometry
  (shard count, row quantum) and refuse resume only when the shard
  quanta actually differ;
- the whole drill also runs in a fresh 8-virtual-device subprocess so
  two-level coverage is tier-1, not a dryrun-only property.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from h2o_tpu.core.diag import DispatchStats

# (slices, nodes, model) triples that fit the 8 forced host devices;
# FLAT and TWO share the shard count (4), so outputs must be bitwise
FLAT = (1, 4, 2)
TWO = (2, 4, 2)


@pytest.fixture()
def reboot():
    """Boot arbitrary (slices, nodes, model) meshes inside a test;
    restore the ORIGINAL session Cloud instance afterwards (see
    test_shard_munge.reboot)."""
    from h2o_tpu.core.cloud import Cloud
    saved = Cloud._instance

    def boot(s, n, m):
        return Cloud.boot(slices=s, nodes=n, model_axis=m)

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


def _torture_arrays(n=203, seed=31):
    rng = np.random.default_rng(seed)
    k1 = rng.integers(0, 5, size=n).astype(np.float32)
    k1[rng.uniform(size=n) < 0.15] = np.nan
    k2 = rng.normal(size=n).astype(np.float32)
    cat = rng.integers(-1, 3, size=n).astype(np.int32)
    pay = np.arange(n, dtype=np.float32)
    return k1, k2, cat, pay


def _torture_frame(n=203, seed=31):
    """Built AFTER a boot — device placement happens at construction."""
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    k1, k2, cat, pay = _torture_arrays(n, seed)
    return Frame(["k1", "k2", "c", "pay"],
                 [Vec(k1), Vec(k2),
                  Vec(cat, T_CAT, domain=["a", "b", "c"]), Vec(pay)])


def _cols(fr):
    return {n: np.asarray(fr.vec(n).to_numpy(), np.float64).copy()
            for n in fr.names}


def _assert_cols_equal(a, b):
    assert set(a) == set(b)
    for n in a:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)


def _coll():
    """Cumulative per-tag (ici, dcn) byte totals across phases."""
    snap = DispatchStats.snapshot().get("collectives", {})
    out = {}
    for ph in snap.values():
        for tag, d in ph.items():
            c = out.setdefault(tag, [0, 0])
            c[0] += d["ici_bytes"]
            c[1] += d["dcn_bytes"]
    return out


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def test_two_level_mesh_shape_and_pspec(cl, reboot):
    from jax.sharding import PartitionSpec as P
    from h2o_tpu.core.cloud import DATA_AXIS, MODEL_AXIS, SLICE_AXIS
    c = reboot(*TWO)
    assert c.n_slices == 2 and c.n_nodes == 4
    assert c.mesh.axis_names == (SLICE_AXIS, DATA_AXIS, MODEL_AXIS)
    assert c.mesh.devices.shape == (2, 2, 2)
    assert c.data_pspec() == P((SLICE_AXIS, DATA_AXIS))
    assert c.data_pspec(None) == P((SLICE_AXIS, DATA_AXIS), None)
    # flat mesh keeps the exact historical 2-axis layout
    c1 = reboot(*FLAT)
    assert c1.n_slices == 1
    assert c1.mesh.axis_names == (DATA_AXIS, MODEL_AXIS)
    assert c1.data_pspec() == P(DATA_AXIS)


def test_slices_must_divide_nodes(cl, reboot):
    with pytest.raises(ValueError):
        reboot(3, 4, 2)


def test_slices_env_knob(cl, reboot, monkeypatch):
    from h2o_tpu.core.cloud import Cloud
    monkeypatch.setenv("H2O_TPU_SLICES", "2")
    c = Cloud.boot(nodes=4, model_axis=2)
    assert c.n_slices == 2


# ---------------------------------------------------------------------------
# bitwise parity: verbs, fused regions, GBM
# ---------------------------------------------------------------------------

def test_verb_parity_bitwise_flat_vs_two_level(cl, reboot):
    """All four munge verbs: the two-level outputs are bitwise equal to
    the flat-mesh outputs at the same shard count, AND to the host
    oracles — the duplicated keys straddle slices, so the group-by's
    upper-bound count path and the sort's cross-slice route are both
    exercised."""
    from h2o_tpu.core import munge
    from h2o_tpu.core.frame import Frame, Vec
    from h2o_tpu.rapids.interp import (_groupby_host, _merge_host,
                                       _row_select_host, _sort_host)
    aggs = [("mean", 3, "all"), ("sum", 3, "all"), ("nrow", 3, "all")]

    def run_all():
        fr = _torture_frame()
        rk = Frame(["k1", "y"],
                   [Vec(np.asarray([2., 3., np.nan, 0.], np.float32)),
                    Vec(np.asarray([9., 8., 7., 6.], np.float32))])
        srt = munge.sort_frame(fr, [0, 1], [True, False])
        k2 = np.asarray(fr.vec("k2").to_numpy())
        flt = munge.filter_rows(fr, fr.vec("k2").data > 0)
        gb = munge.groupby_frame(fr, [2, 0], aggs)
        mg = munge.merge_frames(fr, rk, True, False, [0], [0])
        host = {
            "sort": _cols(_sort_host(fr, [0, 1], [True, False])),
            "filter": _cols(_row_select_host(fr, np.flatnonzero(k2 > 0))),
            "groupby": _cols(_groupby_host(fr, [2, 0], aggs)),
            "merge": _cols(_merge_host(fr, rk, True, False, [0], [0]))}
        return ({"sort": _cols(srt), "filter": _cols(flt),
                 "groupby": _cols(gb), "merge": _cols(mg)}, host)

    reboot(*FLAT)
    flat, host_flat = run_all()
    for shape in (TWO, (2, 8, 1)):
        reboot(*shape)
        two, host_two = run_all()
        for verb in ("sort", "filter", "merge"):
            _assert_cols_equal(flat[verb], two[verb])
            _assert_cols_equal(two[verb], host_two[verb])
        # group-by aggregates: bitwise vs flat (same combine order),
        # float-tolerant vs the host oracle (different summation order)
        _assert_cols_equal(flat["groupby"], two["groupby"])
        for n in two["groupby"]:
            np.testing.assert_allclose(
                two["groupby"][n], host_two["groupby"][n],
                rtol=1e-4, atol=1e-5, equal_nan=True, err_msg=n)


@pytest.mark.shared_dkv
def test_fused_region_parity_flat_vs_two_level(cl, reboot, monkeypatch):
    """The lazy planner's fused programs inherit the hierarchy through
    the same helpers: fused sort and group-by regions are bitwise equal
    across flat and two-level meshes."""
    from h2o_tpu.core.cloud import cloud
    from h2o_tpu.rapids.interp import Session, rapids_exec
    monkeypatch.setenv("H2O_TPU_RAPIDS_FUSE", "1")
    rng = np.random.default_rng(17)
    n = 4096
    x = rng.normal(size=n).astype(np.float32)
    x[rng.random(n) < 0.1] = np.nan
    g = rng.integers(0, 8, n).astype(np.int32)

    def run():
        from h2o_tpu.core.frame import Frame, T_CAT, Vec
        fr = Frame(["x", "g"],
                   [Vec(x), Vec(g, T_CAT,
                                domain=[f"g{i}" for i in range(8)])])
        fr.key = "tlm_pipe"
        cloud().dkv.put("tlm_pipe", fr)
        sess = Session("tlm")
        inner = "(rows tlm_pipe (> (cols tlm_pipe [0]) -2))"
        try:
            srt = rapids_exec(f"(sort (na.omit {inner}) [1 0] [1 1])",
                              sess)
            gb = rapids_exec("(GB (rows tlm_pipe "
                             "(<= (cols tlm_pipe [0]) 1)) [1] "
                             "mean 0 'all' nrow 0 'all')", sess)
            return _cols(srt), _cols(gb)
        finally:
            cloud().dkv.remove("tlm_pipe")

    reboot(*FLAT)
    srt_flat, gb_flat = run()
    reboot(*TWO)
    srt_two, gb_two = run()
    _assert_cols_equal(srt_flat, srt_two)
    _assert_cols_equal(gb_flat, gb_two)


def test_gbm_forest_parity_flat_vs_two_level(cl, reboot):
    """A GBM forest (histogram hpsum + mrtask reducers + tree window
    scatter) trains bitwise-identically on flat and two-level meshes of
    the same shard count."""
    from h2o_tpu.models.tree.gbm import GBM
    rng = np.random.default_rng(5)
    n = 512
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)

    def run():
        from h2o_tpu.core.frame import Frame, Vec
        fr = Frame([f"x{j}" for j in range(4)] + ["y"],
                   [Vec(X[:, j]) for j in range(4)] + [Vec(y)])
        m = GBM(ntrees=3, max_depth=3, seed=5, nbins=32,
                distribution="gaussian",
                histogram_type="UniformAdaptive").train(
            y="y", training_frame=fr)
        return np.asarray(m.predict_raw(fr)).copy()

    reboot(*FLAT)
    p_flat = run()
    reboot(*TWO)
    p_two = run()
    np.testing.assert_array_equal(p_flat, p_two)


# ---------------------------------------------------------------------------
# the per-axis byte ledger
# ---------------------------------------------------------------------------

def test_collective_byte_ledger(cl, reboot):
    """Flat mesh: every collective is ICI, zero DCN.  Two-level mesh:
    the combine tags carry DCN bytes (one cross-slice combine per
    level) and the ledger surfaces at GET /3/Dispatch."""
    from h2o_tpu.core import munge
    aggs = [("sum", 3, "all"), ("nrow", 3, "all")]

    reboot(*FLAT)
    c0 = _coll()
    fr = _torture_frame(n=2000, seed=41)       # fresh bucket -> compiles
    munge.sort_frame(fr, [0], [True])
    munge.groupby_frame(fr, [2], aggs)
    c1 = _coll()
    flat_delta = {t: (v[0] - c0.get(t, [0, 0])[0],
                      v[1] - c0.get(t, [0, 0])[1])
                  for t, v in c1.items() if v != c0.get(t, [0, 0])}
    assert flat_delta, "flat verbs recorded no collectives"
    assert all(d[1] == 0 for d in flat_delta.values()), flat_delta
    assert any(d[0] > 0 for d in flat_delta.values())

    reboot(*TWO)
    c2 = _coll()
    fr = _torture_frame(n=1000, seed=43)
    munge.sort_frame(fr, [0], [True])
    munge.groupby_frame(fr, [2], aggs)
    c3 = _coll()
    two_delta = {t: (v[0] - c2.get(t, [0, 0])[0],
                     v[1] - c2.get(t, [0, 0])[1])
                 for t, v in c3.items() if v != c2.get(t, [0, 0])}
    for tag in ("all_gather:sort.splitters", "psum:groupby.count",
                "all_gather:groupby.partials"):
        assert two_delta.get(tag, (0, 0))[1] > 0, (tag, two_delta)
    # surfaced at GET /3/Dispatch
    from h2o_tpu.api.handlers import dispatch_route
    coll = dispatch_route({})["dispatch"]["collectives"]
    assert any("sort.splitters" in t for ph in coll.values()
               for t in ph), coll


# the combine collectives of each step: the tags whose DCN bytes must not
# grow with the rows.  The sort's route all_to_all (sort.route) moves
# O(rows) by design and is no combine.
_COMBINE_TAGS = {"sort": ("sort.splitters", "sort.counts"),
                 "groupby": ("groupby.count", "groupby.partials"),
                 "hist": ("hist.table",)}


@pytest.mark.parametrize("step", sorted(_COMBINE_TAGS))
def test_combine_dcn_bytes_do_not_grow_with_rows(cl, reboot, step):
    """Two-slice mesh, one step at two row counts (each a fresh bucket,
    so each compiles and writes the trace-time ledger): the cross-slice
    bytes of its combine collectives are positive and equal."""
    import jax.numpy as jnp
    from h2o_tpu.core import munge
    from h2o_tpu.ops.histogram import histogram_build
    reboot(*TWO)

    def run(n):
        fr = _torture_frame(n=n, seed=47)
        c0 = _coll()
        if step == "sort":
            munge.sort_frame(fr, [1], [True])
        elif step == "groupby":
            munge.groupby_frame(fr, [2], [("sum", 3, "all"),
                                          ("nrow", 3, "all")])
        else:
            rng = np.random.default_rng(9)
            histogram_build(
                jnp.asarray(rng.integers(0, 32, size=(n, 4)), jnp.int32),
                jnp.asarray(rng.integers(0, 8, size=(n,)), jnp.int32),
                jnp.asarray(rng.normal(size=(n, 4)), jnp.float32),
                n_leaves=8, nbins=32).block_until_ready()
        c1 = _coll()
        return {t: v[1] - c0.get(t, [0, 0])[1] for t, v in c1.items()
                if t.split(":", 1)[-1] in _COMBINE_TAGS[step]}

    small, large = run(3000), run(12000)
    assert any(v > 0 for v in small.values()), small
    assert small == large, (small, large)


# ---------------------------------------------------------------------------
# survivor policy + slice-loss drill
# ---------------------------------------------------------------------------

def test_target_shape_drops_whole_slice(cl, membership_clean):
    mon = membership_clean
    # two-level: one slice per attempt, q nodes each
    assert mon._target_shape(4, 2, 1, old_slices=2) == \
        {"nodes": 2, "slices": 1, "model_axis": 2}
    assert mon._target_shape(8, 1, 1, old_slices=4) == \
        {"nodes": 6, "slices": 3, "model_axis": 1}
    assert mon._target_shape(8, 1, 3, old_slices=4) == \
        {"nodes": 2, "slices": 1, "model_axis": 1}
    # attempts past the last slice: halve within it
    assert mon._target_shape(8, 1, 5, old_slices=4) == \
        {"nodes": 1, "slices": 1, "model_axis": 1}
    # flat mesh keeps the historical halving policy
    assert mon._target_shape(4, 2, 1) == {"nodes": 2, "model_axis": 2}


def test_slice_loss_mid_train_drops_slice_and_resumes_bitwise(
        cl, reboot, tmp_path, membership_clean):
    """GBM on the 2x2x2 two-level mesh dies on an injected slice loss
    mid-forest; the DEFAULT survivor policy drops the dead slice (not
    half the flat axis), reforms to the surviving 1x2x2, and the
    resumed forest is bitwise equal to an uninterrupted run there."""
    from h2o_tpu.core import chaos
    from h2o_tpu.core.frame import Frame, Vec
    from h2o_tpu.core.oom import is_device_loss
    from h2o_tpu.core.recovery import pending_recoveries
    from h2o_tpu.models.tree.gbm import GBM
    mon = membership_clean
    rec = str(tmp_path / "rec")
    rng = np.random.default_rng(5)
    n = 512
    x0 = rng.integers(0, 16, size=n).astype(np.float32)
    x1 = rng.integers(0, 8, size=n).astype(np.float32)
    y = ((x0 + 2 * x1) % 2).astype(np.float32)

    def frame():
        return Frame(["x0", "x1", "y"], [Vec(x0), Vec(x1), Vec(y)])

    def gbm(**kw):
        return GBM(ntrees=4, max_depth=3, seed=7, nbins=16,
                   learn_rate=0.5, distribution="gaussian",
                   histogram_type="UniformAdaptive", **kw)

    # uninterrupted reference on the TARGET (one surviving slice) mesh
    reboot(1, 2, 2)
    pred_ref = np.asarray(gbm().train(
        y="y", training_frame=frame()).predict_raw(frame())).copy()

    reboot(*TWO)
    mon.configure(recovery_dir=rec, auto=True)
    chaos.configure(slice_loss_at_block=2, seed=3)
    with pytest.raises(BaseException) as ei:
        gbm(recovery_dir=rec, checkpoint_interval=1,
            model_id="tlm_gbm").train(y="y", training_frame=frame())
    assert is_device_loss(ei.value), ei.value

    deadline = time.time() + 180
    while mon.epoch < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert mon.epoch >= 1, mon.events()
    assert mon.wait_stable(60)
    ev = mon.events()[-1]
    assert ev["ok"], ev
    assert ev["old_mesh"] == {"nodes": 4, "model": 2, "slices": 2}
    assert ev["new_mesh"] == {"nodes": 2, "model": 2, "slices": 1}
    assert len(mon.last_results) == 1
    m2 = mon.last_results[0]
    assert m2.output["ntrees_actual"] == 4
    np.testing.assert_array_equal(
        pred_ref, np.asarray(m2.predict_raw(frame())))
    assert pending_recoveries(rec) == []


# ---------------------------------------------------------------------------
# recovery stamp
# ---------------------------------------------------------------------------

def test_recovery_stamp_carries_slices_and_quantum(cl, reboot):
    from h2o_tpu.core.recovery import _mesh_info
    reboot(*TWO)
    info = _mesh_info()
    assert info["slices"] == 2
    assert info["data_shards"] == 4
    assert info["devices"] == 8
    assert info["row_quantum"] == 4 * 8        # nodes * row_align


def test_pending_recoveries_gates_on_data_geometry(cl, tmp_path):
    """A 2x2x2 stamp is resumable wherever its shard count fits (the
    axis SPLIT is not the refusal unit); refusal happens only when the
    shard quanta actually differ — data_shards beyond this process's
    devices, or a row quantum this mesh cannot re-pad."""
    from h2o_tpu.core.recovery import pending_recoveries
    rec = tmp_path / "rec"

    def snap(name, mesh):
        d = rec / name
        d.mkdir(parents=True)
        info = {"key": name, "algo": "gbm", "started": 1.0,
                "done": False}
        if mesh is not None:
            info["mesh"] = mesh
        (d / "info.json").write_text(json.dumps(info))

    # stamped by a 2x2x2 two-level mesh: 4 shards, quantum 32 — both
    # fit the 8-device flat session cloud, so it must be recoverable
    snap("two_level", {"nodes": 4, "model": 2, "slices": 2,
                       "data_shards": 4, "row_quantum": 32,
                       "devices": 8})
    snap("too_many_shards", {"nodes": 64, "model": 1, "slices": 8,
                             "data_shards": 64, "row_quantum": 512,
                             "devices": 64})
    snap("alien_quantum", {"nodes": 4, "model": 2, "slices": 2,
                           "data_shards": 4, "row_quantum": 12,
                           "devices": 8})
    pend = pending_recoveries(str(rec))
    assert sorted(p["key"] for p in pend) == ["two_level"], pend


# ---------------------------------------------------------------------------
# subprocess drill: 8 virtual devices, fresh interpreter
# ---------------------------------------------------------------------------

_DRILL_SRC = textwrap.dedent("""
    import json
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from h2o_tpu.core.cloud import Cloud
    from h2o_tpu.core import munge
    from h2o_tpu.core.frame import Frame, Vec
    assert len(jax.devices()) == 8, jax.devices()
    rng = np.random.default_rng(21)
    k = rng.integers(0, 5, size=240).astype(np.float32)
    k[rng.uniform(size=240) < 0.2] = np.nan
    pay = np.arange(240, dtype=np.float32)
    outs = {}
    for s, n, m in ((1, 4, 2), (2, 4, 2), (1, 8, 1), (2, 8, 1)):
        c = Cloud.boot(slices=s, nodes=n, model_axis=m)
        assert c.n_slices == s
        fr = Frame(["k", "pay"], [Vec(k), Vec(pay)])
        srt = munge.sort_frame(fr, [0], [True])
        gb = munge.groupby_frame(fr, [0], [("sum", 1, "all"),
                                           ("nrow", 1, "all")])
        outs[(s, n, m)] = (
            np.asarray(srt.vec("pay").to_numpy()).tobytes(),
            np.asarray(gb.vecs[1].to_numpy()).tobytes(),
            np.asarray(gb.vecs[2].to_numpy()).tobytes())
    assert outs[(1, 4, 2)] == outs[(2, 4, 2)], "2x2x2 != flat 4x2"
    assert outs[(1, 8, 1)] == outs[(2, 8, 1)], "2x4x1 != flat 8x1"
    print(json.dumps({"ok": True, "meshes": 4}))
""")


def test_two_level_subprocess_drill():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["H2O_TPU_ROW_ALIGN"] = "8"
    env.pop("H2O_TPU_SLICES", None)
    r = subprocess.run([sys.executable, "-c", _DRILL_SRC],
                       capture_output=True, env=env, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    out = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert out["ok"] and out["meshes"] == 4
