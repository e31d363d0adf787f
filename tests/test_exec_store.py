"""Unified executable store (core/exec_store.py) regression suite.

The three PR 2-4 caches (DispatchCache, the serve predict cache, the
munge cached_kernel buckets) now route through ONE store, so this suite
pins the store's own contract:

- hit/miss/eviction parity with the old caches (a memory miss is a
  compile, a memory hit is not, the LRU bound evicts oldest-first);
- donation: donating and non-donating variants are DISTINCT entries
  over the same build, bitwise-equal results;
- OOM-ladder integration: a store dispatch that hits a (chaos-injected)
  device OOM sweeps and retries instead of failing;
- the persistent AOT layer: executables serialize to
  H2O_TPU_EXEC_STORE_DIR and a fresh store (same process) or a fresh
  PROCESS (subprocess test) loads them as disk hits — strictly fewer
  backend compiles for the same GBM-train + serve-score workload;
  schema-versioned entries invalidate cleanly on header mismatch;
- the Mosaic/Pallas kernel-compile fallback rung (core/oom.py
  kernel_fallback) and the widened VMEM working-set gate
  (ops/hist_pallas.plan_tile_rows).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from h2o_tpu.core.exec_store import (ExecStore, bucket_pow2,
                                     stable_fn_name)


def _add(x, y):
    return x + y


def _scale(x):
    return x * 3.0


def _add_one(x):
    return x + 1.0


# ------------------------------------------------------------- LRU core


def test_bucket_pow2():
    assert [bucket_pow2(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 17)] == \
        [1, 1, 2, 4, 4, 8, 8, 16, 32]


def test_hit_miss_and_eviction():
    st = ExecStore(max_entries=2)
    a = jnp.ones((8,))
    fn = st.get_or_build("t", ("k1",), lambda: _scale)
    np.testing.assert_allclose(np.asarray(fn(a)), 3.0 * np.ones(8))
    assert (st.misses, st.hits) == (1, 0)
    st.get_or_build("t", ("k1",), lambda: _scale)
    assert (st.misses, st.hits) == (1, 1)
    st.get_or_build("t", ("k2",), lambda: _scale)
    st.get_or_build("t", ("k3",), lambda: _scale)     # evicts k1
    assert st.stats()["entries"] == 2
    assert st.evictions == 1
    st.get_or_build("t", ("k1",), lambda: _scale)     # miss again
    assert st.misses == 4


def test_donation_variants_are_distinct_and_bitwise_equal():
    st = ExecStore(max_entries=8)
    a = jnp.arange(16, dtype=jnp.float32)
    b = jnp.ones((16,), jnp.float32)
    plain = st.get_or_build("t", ("add",), lambda: _add,
                            donate_argnums=(0,), donate=False,
                            args=(a, b))
    out_plain = np.asarray(plain(a, b))
    donating = st.get_or_build("t", ("add",), lambda: _add,
                               donate_argnums=(0,), donate=True,
                               args=(jnp.array(a), b))
    out_don = np.asarray(donating(jnp.array(a), b))
    assert st.misses == 2                 # two entries over one build
    np.testing.assert_array_equal(out_plain, out_don)


def test_stable_fn_name_rejects_closures():
    assert stable_fn_name(_add) == f"{__name__}._add"

    def local(x):
        return x

    y = 2.0
    closure = (lambda x: x * y)
    assert stable_fn_name(local) is None          # <locals> qualname
    assert stable_fn_name(closure) is None


# ------------------------------------------------- OOM-ladder dispatch


def test_dispatch_walks_sweep_rung_on_injected_oom(cl):
    from h2o_tpu.core import chaos as chaos_mod
    from h2o_tpu.core import oom
    st = ExecStore(max_entries=8)
    a = jnp.arange(8, dtype=jnp.float32)
    site = "exec_store.test_sweep"
    chaos_mod.configure(oom_transient=1)
    try:
        before = oom.stats()["sites"].get(site, {}).get("sweeps", 0)
        out = st.dispatch("t", ("sweep",), lambda: _scale, (a,),
                          site=site)
        np.testing.assert_allclose(np.asarray(out), 3.0 * np.arange(8))
        after = oom.stats()["sites"][site]
        assert after["oom_events"] >= 1
        assert after["sweeps"] - before >= 1
    finally:
        chaos_mod.reset()


def test_dispatch_reroutes_nondonating_on_oom(cl, monkeypatch):
    """An OOM retry must not re-donate: the store fetches the
    non-donating twin for the retry (two entries materialize)."""
    from h2o_tpu.core import chaos as chaos_mod
    monkeypatch.setenv("H2O_TPU_DONATE", "1")
    st = ExecStore(max_entries=8)
    a = jnp.arange(8, dtype=jnp.float32)
    # fail the initial attempt AND the first sweep retry: the on_oom
    # hook fires (twice) and the retry runs the non-donating twin
    chaos_mod.configure(oom_transient=2)
    try:
        out = st.dispatch("t", ("redon",), lambda: _scale,
                          (a,), donate_argnums=(0,),
                          site="exec_store.test_redonate")
        np.testing.assert_allclose(np.asarray(out), 3.0 * np.arange(8))
        assert st.misses == 2              # donating + plain twin
    finally:
        chaos_mod.reset()


def test_dispatch_deleted_donated_input_is_terminal(cl, monkeypatch):
    """If the failed donating run already consumed a donated buffer,
    no retry can re-read it: the ladder must surface a clear OOMError
    naming the dead argument, not an unclassified 'Array has been
    deleted' RuntimeError."""
    from h2o_tpu.core import chaos as chaos_mod
    from h2o_tpu.core.oom import OOMError
    monkeypatch.setenv("H2O_TPU_DONATE", "1")
    st = ExecStore(max_entries=8)
    a = jnp.arange(8, dtype=jnp.float32)
    out = st.dispatch("t", ("dead",), lambda: _scale, (jnp.array(a),),
                      donate_argnums=(0,), site="exec_store.test_dead")
    np.testing.assert_allclose(np.asarray(out), 3.0 * np.arange(8))
    dead = jnp.array(a)
    dead.delete()
    chaos_mod.configure(oom_transient=1)
    try:
        with pytest.raises(OOMError, match="donated input buffer"):
            st.dispatch("t", ("dead",), lambda: _scale, (dead,),
                        donate_argnums=(0,),
                        site="exec_store.test_dead")
    finally:
        chaos_mod.reset()


def test_engine_bookkeeping_reconciles_with_store():
    """Serve bucket bookkeeping must track the SHARED store's LRU: an
    entry evicted by other phases' traffic (or never present) may not
    be reported as a warm bucket."""
    from h2o_tpu.serve.engine import ScoringEngine
    eng = ScoringEngine()
    with eng._lock:
        eng._keys.add(("ghost_model", 0, 8))
    assert eng.buckets_for("ghost_model", 0) == []
    assert ("ghost_model", 0, 8) not in eng._keys


# --------------------------------------------------- persistent layer


def _placed(kind, cl):
    """The three placements a stored executable can be bound to on the
    8-device mesh: the default device, another single device, and the
    whole mesh (row-sharded)."""
    import jax
    a = jnp.arange(64, dtype=jnp.float32)
    if kind == "default":
        return a
    if kind == "device3":
        return jax.device_put(a, jax.devices()[3])
    return jax.device_put(a, cl.row_sharding)


@pytest.mark.parametrize("kind", ["default", "device3", "row_sharded"])
def test_disk_roundtrip_and_fresh_store_loads(tmp_path, monkeypatch, cl,
                                              kind):
    """A disk entry must load onto the devices it was compiled for and
    RUN there — jax binds a deserialized executable to every local
    device unless told otherwise, which only ever worked on one."""
    monkeypatch.setenv("H2O_TPU_EXEC_STORE_DIR", str(tmp_path))
    a = _placed(kind, cl)
    st1 = ExecStore(max_entries=8)
    fn = st1.get_or_build("t", ("p1",), lambda: _scale,
                          persist="test:p1", args=(a,))
    ref = np.asarray(fn(a))
    s = st1.stats()
    assert s["disk_stores"] == 1 and s["serialized_bytes_written"] > 0
    # a FRESH store (the new-process analog) loads instead of building
    st2 = ExecStore(max_entries=8)
    fn2 = st2.get_or_build("t", ("p1",), lambda: _scale,
                           persist="test:p1", args=(a,))
    s2 = st2.stats()
    assert s2["disk_hits"] == 1 and s2["serialized_bytes_read"] > 0
    out = fn2(a)
    assert out.sharding.device_set == a.sharding.device_set
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_disk_key_mismatch_invalidates_cleanly(tmp_path, monkeypatch):
    """A schema/key mismatch discards the entry and rebuilds — never a
    half-load, never a wrong program."""
    monkeypatch.setenv("H2O_TPU_EXEC_STORE_DIR", str(tmp_path))
    a = jnp.arange(16, dtype=jnp.float32)
    st1 = ExecStore(max_entries=8)
    st1.get_or_build("t", ("p2",), lambda: _scale,
                     persist="test:p2", args=(a,))
    (path,) = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)]
    blob = open(path, "rb").read()
    # corrupt the header region: the loader must treat it as invalid
    open(path, "wb").write(blob[:12] + b"\xff" * 8 + blob[20:])
    st2 = ExecStore(max_entries=8)
    fn = st2.get_or_build("t", ("p2",), lambda: _scale,
                          persist="test:p2", args=(a,))
    assert st2.disk_invalid == 1 and st2.disk_hits == 0
    np.testing.assert_allclose(np.asarray(fn(a)), 3.0 * np.arange(16))
    assert st2.disk_stores == 1            # discarded, then re-stored
    # the re-stored entry is valid again: a third store disk-hits it
    st3 = ExecStore(max_entries=8)
    st3.get_or_build("t", ("p2",), lambda: _scale,
                     persist="test:p2", args=(a,))
    assert st3.disk_hits == 1 and st3.disk_invalid == 0


def test_code_fingerprint_tracks_body():
    from h2o_tpu.core.exec_store import code_fingerprint
    assert code_fingerprint(_scale) == code_fingerprint(_scale)
    assert code_fingerprint(_scale) != code_fingerprint(_add)

    def v1(x):
        return x * 2.0

    def v2(x):
        return x * 5.0

    # same arity/name-shape, different constant: distinct fingerprints
    assert code_fingerprint(v1) != code_fingerprint(v2)


def test_disk_key_content_fingerprint_invalidates(tmp_path, monkeypatch):
    """The stale-content hazard: a serialized executable bakes closure
    constants in, so the same persist name with DIFFERENT content (a
    retrained model under a reused model_id, an upgraded kernel body)
    must rebuild — never disk-load the old program."""
    monkeypatch.setenv("H2O_TPU_EXEC_STORE_DIR", str(tmp_path))
    a = jnp.arange(16, dtype=jnp.float32)
    st1 = ExecStore(max_entries=8)
    st1.get_or_build("t", ("c1",), lambda: _scale,
                     persist="test:content", content="modelA", args=(a,))
    assert st1.disk_stores == 1
    st2 = ExecStore(max_entries=8)
    fn = st2.get_or_build("t", ("c1",), lambda: _add_one,
                          persist="test:content", content="modelB",
                          args=(a,))
    assert st2.disk_hits == 0 and st2.disk_stores == 1
    np.testing.assert_allclose(np.asarray(fn(a)), np.arange(16) + 1.0)
    # matching content still warms from disk
    st3 = ExecStore(max_entries=8)
    st3.get_or_build("t", ("c1",), lambda: _scale,
                     persist="test:content", content="modelA", args=(a,))
    assert st3.disk_hits == 1


def test_store_files_are_private(tmp_path, monkeypatch):
    """Disk entries are unpickled on load (code execution), so the
    store writes 0o600 files in a 0o700 directory."""
    monkeypatch.setenv("H2O_TPU_EXEC_STORE_DIR", str(tmp_path / "s"))
    a = jnp.arange(8, dtype=jnp.float32)
    st = ExecStore(max_entries=8)
    st.get_or_build("t", ("perm",), lambda: _scale,
                    persist="test:perm", args=(a,))
    assert st.disk_stores == 1
    d = tmp_path / "s"
    assert (os.stat(d).st_mode & 0o777) == 0o700
    for f in os.listdir(d):
        assert (os.stat(d / f).st_mode & 0o777) == 0o600


def test_closure_entries_never_persist(tmp_path, monkeypatch):
    """mrtask routes persist names only for closure-free module-level
    map fns — a closure entry must stay memory-only (two closures with
    one qualname would collide on a disk key)."""
    monkeypatch.setenv("H2O_TPU_EXEC_STORE_DIR", str(tmp_path))
    from h2o_tpu.core.mrtask import mutate_array
    x = jnp.arange(8, dtype=jnp.float32)
    y = 2.0
    out = mutate_array(lambda v: v * y, x)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.arange(8))
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".exec")]


# ----------------------------------------------- migrated call sites


def test_serve_engine_routes_through_store(cl, rng):
    from h2o_tpu.core.exec_store import exec_store
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    from h2o_tpu.models.tree.gbm import GBM
    from h2o_tpu.serve.engine import ScoringEngine
    x = rng.normal(size=(300, 3)).astype(np.float32)
    yv = (x[:, 0] > 0).astype(np.int32)
    fr = Frame([f"x{j}" for j in range(3)] + ["y"],
               [Vec(x[:, j]) for j in range(3)] +
               [Vec(yv, T_CAT, domain=["a", "b"])])
    m = GBM(ntrees=2, max_depth=2, seed=3, nbins=16).train(
        y="y", training_frame=fr)
    eng = ScoringEngine()
    eng.predict(m, 1, x[:5].astype(np.float64))
    mid = str(m.key)
    in_store = [k for k in exec_store()._entries
                if k[:2] == ("serve", "predict") and k[2] == mid]
    assert in_store, "serve predict executable not in the unified store"
    assert eng.buckets_for(mid, 1) == [8]
    eng.evict(mid, 1)
    assert eng.buckets_for(mid, 1) == []
    assert not [k for k in exec_store()._entries
                if k[:2] == ("serve", "predict") and k[2] == mid]


def test_dispatch_route_reports_store(cl):
    from h2o_tpu.api.handlers import dispatch_route
    out = dispatch_route({})
    # legacy cache block keeps the PR 3 keys; store block adds the
    # persistent-AOT surface
    assert {"hits", "misses", "entries", "capacity"} <= set(out["cache"])
    assert {"disk_hits", "disk_stores", "serialized_bytes_written",
            "serialized_bytes_read", "aot_entries",
            "serialize_unsupported"} <= set(out["store"])
    assert "disk_hits" in out["dispatch"]


# ------------------------------------- Pallas fallback + VMEM gate


def test_kernel_fallback_degrades_to_xla_path():
    from h2o_tpu.core import oom
    calls = []

    def run(pallas):
        calls.append(pallas)
        if pallas:
            raise RuntimeError(
                "Mosaic lowering failed: unsupported memref layout")
        return "xla"

    before = oom.stats()["sites"].get("test.kernel", {}).get(
        "kernel_fallbacks", 0)
    assert oom.kernel_fallback("test.kernel", run, pallas=True) == "xla"
    assert calls == [True, False]
    site = oom.stats()["sites"]["test.kernel"]
    assert site["kernel_fallbacks"] - before == 1
    # non-kernel failures propagate untouched
    with pytest.raises(ValueError):
        oom.kernel_fallback(
            "test.kernel",
            lambda p: (_ for _ in ()).throw(ValueError("boom")),
            pallas=True)


def test_vmem_gate_bounds_a_matrix_temporary():
    """The first gate bounded the one-hot and the accumulator but not
    the (TR, L*S) A temporary, so narrow-feature / wide-frontier shapes
    passed and then blew VMEM.  The kernel now builds A one 128-lane
    slab at a time, so what a wide frontier costs is the resident
    (C*B1p, L*S) output window — and the plan must reject on it."""
    from h2o_tpu.ops.hist_pallas import min_tile_fits, plan_tile_rows
    # modest shape: fits, and fits at a useful tile height
    t = plan_tile_rows(28, 65, 32, 4, jnp.float32)
    assert t is not None and t >= 512 and t % 128 == 0
    # one column, huge frontier: the A slab is 128 rows whatever L is,
    # and the 72 x 16384 f32 window is 4.5 MiB — fits
    assert plan_tile_rows(1, 65, 4096, 4, jnp.float32) is not None
    # the bench columns at that frontier: a 2016 x 16384 f32 window is
    # 126 MiB of the chip's 128 — must be rejected
    assert plan_tile_rows(28, 65, 4096, 4, jnp.float32) is None
    assert not min_tile_fits(28, 65, 4096, 4)
    # very wide features: the one-hot alone overflows the minimum tile
    assert not min_tile_fits(4096, 65, 1, 4)
    # a narrow one-hot pads B+1 to 32 sublanes: more rows, fewer bytes
    assert plan_tile_rows(28, 65, 32, 4, jnp.int8, 1, 1) >= t


def test_pallas_flag_must_be_explicit_bool():
    from h2o_tpu.ops.histogram import _pallas_eligible
    with pytest.raises(TypeError):
        _pallas_eligible(8, 65, 32, 4, None, None)
    assert _pallas_eligible(8, 65, 32, 4, None, False) is False


# ------------------------------------------- subprocess warm start


_WARM_SRC = textwrap.dedent("""
    import json, os, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from h2o_tpu.core.diag import DispatchStats
    DispatchStats.install_xla_listener()
    from h2o_tpu.core.cloud import Cloud, cloud
    Cloud.boot()
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    rng = np.random.default_rng(0)
    x = rng.normal(size=(400, 3)).astype(np.float32)
    yv = (x[:, 0] > 0).astype(np.int32)
    fr = Frame([f"x{j}" for j in range(3)] + ["y"],
               [Vec(x[:, j]) for j in range(3)] +
               [Vec(yv, T_CAT, domain=["a", "b"])])
    from h2o_tpu.models.tree.gbm import GBM
    m = GBM(ntrees=2, max_depth=2, learn_rate=0.3, seed=1, nbins=16,
            model_id="warmstart_gbm").train(y="y", training_frame=fr)
    g = rng.integers(0, 4, size=256).astype(np.int32)
    f2 = Frame(["g", "x"],
               [Vec(g, T_CAT, domain=[f"g{i}" for i in range(4)]),
                Vec(x[:256, 0])])
    f2.key = "warm_gb"
    cloud().dkv.put("warm_gb", f2)
    from h2o_tpu.rapids.interp import Session, rapids_exec
    gb = rapids_exec("(GB warm_gb [0] mean 1 'all')", Session("w"))
    gb0 = float(np.asarray(gb.vecs[1].to_numpy()).ravel()[0])
    from h2o_tpu.serve.engine import ScoringEngine
    eng = ScoringEngine()
    p = eng.predict(m, 0, x[:5].astype(np.float64))
    from h2o_tpu.core.exec_store import exec_store
    s = exec_store().stats()
    print(json.dumps({
        "disk_hits": s["disk_hits"], "disk_stores": s["disk_stores"],
        "disk_invalid": s["disk_invalid"],
        "bytes_read": s["serialized_bytes_read"],
        "backend_compiles": DispatchStats.xla_compiles(),
        "pred0": float(np.asarray(p).ravel()[0]), "gb0": gb0}))
""")


def _run_warm_proc(store_dir, xla_dir):
    env = dict(os.environ)
    env["H2O_TPU_EXEC_STORE_DIR"] = str(store_dir)
    env["H2O_TPU_COMPILE_CACHE"] = "1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(xla_dir)
    env["H2O_TPU_ROW_ALIGN"] = "8"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", _WARM_SRC],
                       capture_output=True, env=env, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    return json.loads(r.stdout.decode().strip().splitlines()[-1])


def test_fresh_process_warm_start(tmp_path):
    """THE acceptance drill: the same GBM-train + groupby + serve-score
    workload in two fresh processes sharing one store directory.  The
    second process must report >= 1 disk hit and STRICTLY fewer backend
    compiles than the first — and identical numeric outputs."""
    cold = _run_warm_proc(tmp_path / "exec", tmp_path / "xla")
    warm = _run_warm_proc(tmp_path / "exec", tmp_path / "xla")
    assert cold["disk_hits"] == 0 and cold["disk_stores"] >= 1
    assert warm["disk_hits"] >= 1, warm
    assert warm["bytes_read"] > 0
    assert warm["disk_invalid"] == 0
    assert warm["backend_compiles"] < cold["backend_compiles"], \
        (cold, warm)
    assert warm["pred0"] == cold["pred0"]
    assert warm["gb0"] == cold["gb0"]
