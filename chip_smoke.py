#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no child that needs the chip.  It drives the main path once
through the entry points a user calls — ``Cloud.boot()`` over every
visible chip, the REST server, CSV ingest, ``POST /3/ModelBuilders/gbm``
twice, bulk ``/3/Predictions``, ``POST /3/Serving`` and ``/score`` — at
the full width of the one configuration with any chip history: GBM
binomial on the HIGGS shape (1,000,000 x 28 float32 + a binary response
from ``make_data(seed=0)`` below; 20 trees, depth 5, 64 bins,
QuantilesGlobal).  No ``H2O_TPU_*`` variable is set: the default
switches decide what runs, and the assertions at the end prove that no
fallback fired on the way.

It exits non-zero on the first phase that raises, and without a result
when JAX finds no TPU.  The last line of stdout is one JSON object with
the device as JAX reports it.  The wall times it prints are smoke
timings, not benchmark results.
"""

import importlib.metadata
import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

ROWS, COLS = 1_000_000, 28
CSV_ROWS = 100_000
GBM = dict(ntrees=20, max_depth=5, nbins=64, learn_rate=0.1, seed=1,
           histogram_type="QuantilesGlobal", score_tree_interval=5)
NBINS, LEAVES = 64, (1, 16, 32)

# Train AUC of GBM on the CPU float32 path (reference levers, one CPU
# device), same data and seed:
#   JAX_PLATFORMS=cpu python -c "import chip_smoke; chip_smoke.cpu_reference_auc()"
CPU_F32_TRAIN_AUC = 0.783302
AUC_BAND = 0.003


# ------------------------------------------------------------------- data


def make_data(rows, cols, seed=0):
    """The HIGGS-shaped rows every assertion here was read on (rows
    first, response drawn last; ``benchmark.data.higgs_like`` draws
    other rows)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    # HIGGS-like signal: nonlinear combination of a few features
    logits = (1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
              + 0.5 * np.sin(3 * X[:, 4]))
    y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-logits))).astype(np.int32)
    return X, y


def make_frame(X, y):
    from h2o_tpu.core.frame import Frame, Vec, T_CAT
    cols = X.shape[1]
    names = [f"x{j}" for j in range(cols)] + ["y"]
    vecs = [Vec(X[:, j]) for j in range(cols)] + \
        [Vec(y, T_CAT, domain=["b", "s"])]
    return Frame(names, vecs)


# ---------------------------------------------------------------- harness


def phase(name, fn, *args, **kwargs):
    """Run one phase; an exception ends the run (no later phase runs
    after an earlier one failed)."""
    t0 = time.time()
    out = fn(*args, **kwargs)
    print(f"PHASE {name:<12s} {time.time() - t0:8.2f}s", flush=True)
    return out


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


class Rest:
    """The few REST calls the smoke makes, against the live server."""

    def __init__(self, port):
        self.base = f"http://127.0.0.1:{port}"

    def call(self, method, path, body=None):
        req = urllib.request.Request(
            self.base + path, method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=1200) as r:
            return json.loads(r.read().decode())

    def train(self, model_id, frame_key):
        """POST /3/ModelBuilders/gbm, poll the job to DONE."""
        job = self.call("POST", "/3/ModelBuilders/gbm",
                        dict(GBM, training_frame=frame_key,
                             response_column="y",
                             model_id=model_id))["job"]
        jid = job["key"]["name"]
        while job["status"] in ("CREATED", "RUNNING"):
            time.sleep(0.25)
            job = self.call("GET", f"/3/Jobs/{jid}")["jobs"][0]
        if job["status"] != "DONE":
            raise RuntimeError(f"job {jid} ended {job['status']}: "
                               f"{job.get('exception')}")


class CacheCounters:
    """XLA backend compiles and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        self.hits = self.misses = 0
        import jax
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


def cache_entries():
    import jax
    d = jax.config.jax_compilation_cache_dir
    n = len([f for f in os.listdir(d) if f.endswith("-cache")]) \
        if d and os.path.isdir(d) else 0
    return d, n


# ----------------------------------------------------------------- phases


def devices_or_exit(require):
    """Versions and devices first; no TPU means no result."""
    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu "
          f"{importlib.metadata.version('libtpu')}  platform {d0.platform}  "
          f"device_kind {d0.device_kind}  count {len(devs)}", flush=True)
    if d0.platform != require:
        print(f"chip_smoke: platform is {d0.platform!r}, need {require!r}",
              file=sys.stderr)
        sys.exit(1)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def boot(device):
    """Cloud over every visible chip, then the REST server — what
    ``python -m h2o_tpu`` does."""
    from h2o_tpu.api.server import RestServer
    from h2o_tpu.core.cloud import Cloud
    cl = Cloud.boot()
    n = device["count"]
    check(cl.mesh.devices.shape == (n, 1) and cl.n_nodes == n,
          f"mesh is {n}x1 over all {n} device(s)")
    check(len({d.id for d in cl.mesh.devices.flat}) == n,
          "mesh devices are distinct")
    srv = RestServer(port=0).start()
    rest = Rest(srv.port)
    c = rest.call("GET", "/3/Cloud")
    check((c["cloud_size"], c["platform"], c["device_kind"],
           c["device_count"]) ==
          (n, device["platform"], device["kind"], n),
          f"GET /3/Cloud reports {c['platform']} {c['device_kind']} x{n}")
    about = {e["name"]: e["value"] for e in
             rest.call("GET", "/3/About")["entries"]}
    check(device["kind"] in about["Backend"],
          f"GET /3/About backend: {about['Backend']}")
    return cl, srv, rest


def assert_sharded(fr, n, platform, what):
    """Every column: n equal shards on n distinct devices of the
    platform (not n copies on the first)."""
    for name, v in zip(fr.names, fr.vecs):
        shards = v.data.addressable_shards
        devs = {s.device for s in shards}
        if not (len(shards) == n and len(devs) == n and
                {d.platform for d in devs} == {platform} and
                len({s.data.shape for s in shards}) == 1 and
                sum(s.data.shape[0] for s in shards) == v.data.shape[0]):
            raise AssertionError(f"{what}: column {name} is not "
                                 f"row-sharded over {n} {platform} devices")
    print(f"  ok: {what}: {len(fr.vecs)} columns, each {n} equal shard(s) "
          f"on {n} distinct {platform} device(s)", flush=True)


def ingest(cl, device, X, y, csv_rows):
    """A CSV of the same data through parse_file (native tokenizer ->
    landing), and the full frame through Frame/Vec."""
    from h2o_tpu import native, parse_file
    check(native.available(), "native CSV tokenizer built and loaded")
    n = device["count"]
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "higgs_head.csv")
        cols = [f"x{j}" for j in range(X.shape[1])] + ["y"]
        np.savetxt(path, np.column_stack([X[:csv_rows],
                                          y[:csv_rows].astype(np.float32)]),
                   delimiter=",", header=",".join(cols), comments="",
                   fmt="%.9g")
        pf = parse_file(path)
    check(pf.nrows == csv_rows and pf.names == cols,
          f"parse_file landed {csv_rows} x {len(cols)}")
    got = np.asarray(pf.vec("x0").to_numpy())[:csv_rows]
    check(np.array_equal(got.astype(np.float32), X[:csv_rows, 0]),
          "parsed column x0 equals the generated values")
    assert_sharded(pf, n, device["platform"], "parsed frame")
    fr = make_frame(X, y)
    fr.key = "higgs"
    cl.dkv.put(fr.key, fr)
    assert_sharded(fr, n, device["platform"], "training frame")
    return fr


def _oracle(bins, leaf, stats, L, B1):
    """NumPy float64 (C*B1, L*S) histogram."""
    R, C = bins.shape
    out = np.zeros((C, B1, L, stats.shape[1]), np.float64)
    s64 = stats.astype(np.float64)
    for c in range(C):
        idx = bins[:, c].astype(np.int64) * L + leaf
        for s in range(stats.shape[1]):
            out[c, :, :, s] = np.bincount(
                idx, weights=s64[:, s], minlength=B1 * L).reshape(B1, L)
    return out.reshape(C * B1, L * stats.shape[1])


def kernels(rows, n_devices, interpret=False):
    """Both Pallas kernels, called directly (not through kernel_fallback,
    not through the autotuner) at the smoke's per-shard shape, against
    the XLA path — a Mosaic refusal raises here and fails the smoke.
    Also one level's histogram_build against a float64 oracle."""
    import functools
    import jax
    import jax.numpy as jnp
    from h2o_tpu.ops import hist_pallas as hp
    from h2o_tpu.ops import statpack
    from h2o_tpu.ops.histogram import (histogram_build,
                                       histogram_build_traced)

    @functools.partial(jax.jit, static_argnames=("L", "bf16"))
    def xla_plain(bins, leaf, stats, L, bf16=False):
        return histogram_build_traced(bins, leaf, stats, L, NBINS,
                                      bf16=bf16, pallas=False)

    @functools.partial(jax.jit, static_argnames=("L", "F"))
    def xla_adaptive(bins, leaf, stats, lo, hi, off, is_cat, L, F):
        return histogram_build_traced(
            bins, leaf, stats, L, NBINS,
            fine_map=(lo, hi, off, is_cat, F), pallas=False)

    q = 8 * n_devices          # the XLA path's shard_map splits the rows
    R, C, B, S = -(-rows // n_devices // q) * q, COLS, NBINS, 4
    rng = np.random.default_rng(3)
    bins_np = rng.integers(0, B + 1, size=(R, C)).astype(np.int32)
    stats_np = rng.uniform(0.5, 1.5, size=(R, S)).astype(np.float32)
    q8_np = rng.integers(-127, 128, size=(R, S)).astype(np.int8)
    stats, q8 = jnp.asarray(stats_np), jnp.asarray(q8_np)

    for name in statpack.STATS_DTYPES[1:]:
        ok = hp.mosaic_supports(hp.matmul_dtype(jnp.dtype(name), False))
        print(f"  static rule: stats carrier {name} -> "
              f"{'Pallas' if ok else 'XLA path only (no Mosaic matmul)'}",
              flush=True)

    def table(h, L):                       # (L, C, B1, S) -> (C*B1, L*S)
        return np.asarray(h).transpose(1, 2, 0, 3).reshape(
            C * (B + 1), L * S)

    n = 0
    for L in LEAVES:
        leaf_np = rng.integers(-1, L, size=(R,)).astype(np.int32)
        leaf = jnp.asarray(leaf_np)
        b32 = jnp.asarray(bins_np)
        # the XLA path, once per stats flavour (bin VALUES are the same
        # under every bins dtype)
        ref = {"f32": table(xla_plain(b32, leaf, stats, L), L),
               "bf16": table(xla_plain(b32, leaf, stats, L, bf16=True), L),
               "int8": table(xla_plain(b32, leaf, q8, L), L)}
        bf = float(np.max(np.abs(ref["bf16"] - ref["f32"]) / ref["f32"]))
        for bd in (jnp.int32, jnp.uint8):
            b = jnp.asarray(bins_np.astype(bd))
            got = np.asarray(hp.hist_pallas(b, leaf, stats, L, B,
                                            interpret=interpret))
            np.testing.assert_allclose(got, ref["f32"], rtol=1e-5)
            got = np.asarray(hp.hist_pallas(b, leaf, stats, L, B,
                                            bf16=True, interpret=interpret))
            np.testing.assert_allclose(got, ref["bf16"], rtol=1e-5)
            got = np.asarray(hp.hist_pallas(b, leaf, q8, L, B,
                                            interpret=interpret))
            np.testing.assert_array_equal(got, ref["int8"])
            n += 3
        # adaptive: random per-leaf ranges on a 1024-bin fine grid
        F = 1024
        fine_np = rng.integers(0, F + 1, size=(R, C)).astype(np.int32)
        lo = rng.integers(0, 256, size=(L, C)).astype(np.int32)
        hi = lo + rng.integers(1, 700, size=(L, C)).astype(np.int32)
        off = rng.integers(0, 8, size=(L, C)).astype(np.int32)
        is_cat = np.zeros(C, bool)
        fm = tuple(jnp.asarray(a) for a in (lo, hi, off, is_cat))
        aref = table(xla_adaptive(jnp.asarray(fine_np), leaf, stats, *fm,
                                  L=L, F=F), L)
        for bd in (jnp.int32, jnp.int16):
            got = np.asarray(hp.hist_pallas_adaptive(
                jnp.asarray(fine_np.astype(bd)), leaf, stats, *fm, L, B, F,
                interpret=interpret))
            np.testing.assert_allclose(got, aref, rtol=1e-5)
            n += 1
        print(f"  ok: L={L}: hist_pallas (int32/uint8 bins x f32/bf16/int8)"
              f" and hist_pallas_adaptive (int32/int16 bins) match the XLA "
              f"path; bf16 differs from f32 by up to {bf:.1e}", flush=True)
    print(f"  ok: {n} Pallas variants compiled by Mosaic "
          f"(interpret={interpret})", flush=True)

    # one level's f32 histogram_build vs float64, through the public
    # entry (whichever kernel the hist.kernel lever picks here)
    L = LEAVES[-1]
    leaf_np = rng.integers(0, L, size=(R,)).astype(np.int32)
    h = histogram_build(jnp.asarray(bins_np), jnp.asarray(leaf_np), stats,
                        n_leaves=L, nbins=B)
    want = _oracle(bins_np, leaf_np, stats_np, L, B + 1)
    got = table(h, L)
    err = float(np.max(np.abs(got - want) / np.abs(want)))
    check(err < 1e-5, f"f32 histogram_build ({C} cols, {B} bins, {L} "
                      f"leaves, {R} rows) within 1e-5 of the float64 "
                      f"oracle (max rel err {err:.2e})")


def model_auc(rest, model_id):
    out = rest.call("GET", f"/3/Models/{model_id}")["models"][0]["output"]
    return float(out["training_metrics"]["AUC"])


def predict_and_score(cl, rest, X, model_id):
    """Bulk predict over the frame, deploy, then /score at three batch
    sizes; the served probabilities must equal the bulk rows."""
    from h2o_tpu.serve import registry
    dest = rest.call(
        "POST", f"/3/Predictions/models/{model_id}/frames/higgs"
    )["predictions_frame"]["name"]
    pf = cl.dkv.get(dest)
    check(pf.nrows == X.shape[0] and pf.names[0] == "predict",
          f"bulk predict: {pf.nrows} rows, columns {pf.names}")
    p1 = np.asarray(pf.vecs[2].to_numpy())[:X.shape[0]]
    check(bool(np.isfinite(p1).all() and p1.min() >= 0 and p1.max() <= 1),
          "bulk probabilities finite and in [0, 1]")

    dep = rest.call("POST", "/3/Serving",
                    {"model_id": model_id, "name": "higgs",
                     "max_batch": 256})["deployment"]
    print(f"  deployed: {dep['name']} v{dep['version']} device_predict="
          f"{dep['device_predict']} compiled_buckets="
          f"{dep['compiled_buckets']}", flush=True)
    cols = [f"x{j}" for j in range(X.shape[1])]
    dom = pf.names[1:]
    for n in (1, 7, 256):
        rows = [{c: float(v) for c, v in zip(cols, X[i])} for i in range(n)]
        preds = rest.call("POST", "/3/Serving/higgs/score",
                          {"rows": rows})["predictions"]
        got = np.array([p["probabilities"][dom[1]] for p in preds])
        err = float(np.max(np.abs(got - p1[:n])))
        check(len(preds) == n and err <= 1e-6,
              f"/score batch {n}: probabilities equal bulk predict "
              f"(max abs diff {err:.1e})")
    eng = registry().engine
    st = eng.stats()
    check(not eng._no_device and st["fallback_batches"] == 0 and
          st["device_batches"] >= 3,
          f"serve scored on the device ({st['device_batches']} device "
          f"batches, 0 host fallbacks, no model marked no-device)")


def no_fallback_fired(rest, device):
    """The production safety nets stay; prove none of them caught
    anything."""
    from h2o_tpu.core import autotune, oom
    o = oom.stats()
    print(f"  oom ladder: {json.dumps(o)}", flush=True)
    check(o["oom_events"] == 0 and o["degradations"] == 0 and
          o["terminal_failures"] == 0,
          "OOM ladder: 0 events, 0 degradations (no kernel, host or "
          "unfused fallback at any site)")
    a = autotune.stats()
    print(f"  autotune: {json.dumps(a)}", flush=True)
    check(a["probe_failures"] == 0 and a["parity_disqualified"] == 0 and
          a["resolve_errors"] == 0,
          "autotune: 0 probe failures, 0 parity disqualifications, "
          "0 resolve errors")
    for rec in autotune.autotune_payload()["decisions"]:
        cands = {k: (v.get("status"), v.get("median_ms"))
                 for k, v in rec["candidates"].items()}
        print(f"  lever {rec['site']} bucket {tuple(rec['bucket'])}: "
              f"winner {rec['winner']} ({rec['source']}) {cands}",
              flush=True)
    coll = rest.call("GET", "/3/Dispatch")["dispatch"]["collectives"]
    ici = sum(d["ici_bytes"] for kinds in coll.values()
              for tag, d in kinds.items() if tag.endswith("hist.table"))
    print(f"  hist.table ICI bytes (trace-time ledger): {ici}", flush=True)
    if device["count"] > 1:
        check(ici > 0, "GET /3/Dispatch shows non-zero ICI bytes under "
                       "hist.table")


def run(rows=ROWS, csv_rows=CSV_ROWS, require="tpu", interpret=False,
        want_auc=None):
    t_start = time.time()
    device = phase("devices", devices_or_exit, require)
    import jax
    from h2o_tpu.core.diag import DispatchStats
    DispatchStats.install_xla_listener()
    counters = CacheCounters()
    cl, srv, rest = phase("boot", boot, device)
    try:
        d, n0 = cache_entries()
        print(f"compile cache: jax_compilation_cache_dir={d} "
              f"entries_before={n0}", flush=True)
        phase("kernels", kernels, rows, device["count"], interpret)
        X, y = make_data(rows, COLS, seed=0)
        phase("ingest", ingest, cl, device, X, y, csv_rows)

        c0, h0 = DispatchStats.xla_compiles(), counters.hits
        t0 = time.time()
        phase("train_first", rest.train, "smoke_gbm_1", "higgs")
        first_wall = time.time() - t0
        c1, h1 = DispatchStats.xla_compiles(), counters.hits
        t0 = time.time()
        phase("train_steady", rest.train, "smoke_gbm_2", "higgs")
        steady_wall = time.time() - t0
        c2 = DispatchStats.xla_compiles()
        print(f"  first train: {first_wall:.2f}s wall, {c1 - c0} XLA "
              f"compile requests, {h1 - h0} of them persistent-cache hits; "
              f"second train: {steady_wall:.2f}s wall, {c2 - c1} XLA "
              f"compile requests", flush=True)
        check(c2 - c1 == 0, "second train compiled 0 XLA programs")
        auc1, auc2 = (model_auc(rest, m) for m in
                      ("smoke_gbm_1", "smoke_gbm_2"))
        check(auc1 == auc2, f"both trains reach the same AUC ({auc1:.6f})")
        if want_auc is not None:
            check(abs(auc1 - want_auc) <= AUC_BAND,
                  f"train AUC {auc1:.4f} within {AUC_BAND} of the CPU "
                  f"float32 value {want_auc:.4f}")

        phase("serve", predict_and_score, cl, rest, X, "smoke_gbm_2")
        phase("no_fallback", no_fallback_fired, rest, device)
        d, n1 = cache_entries()
        print(f"compile cache: jax_compilation_cache_dir={d} "
              f"entries_after={n1} (+{n1 - n0}) persistent-cache hits="
              f"{counters.hits} misses={counters.misses}", flush=True)
    finally:
        srv.stop()
    print(f"CHIP_SMOKE OK  total {time.time() - t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def cpu_reference_auc():
    """The number pinned in CPU_F32_TRAIN_AUC (run with JAX_PLATFORMS=cpu)."""
    from h2o_tpu.core.cloud import Cloud
    from h2o_tpu.models.tree.gbm import GBM as Builder
    Cloud.boot()
    X, y = make_data(ROWS, COLS, seed=0)
    m = Builder(**GBM).train(y="y", training_frame=make_frame(X, y))
    print(f"CPU_F32_TRAIN_AUC = {m.output['training_metrics']['AUC']:.6f}")


if __name__ == "__main__":
    run(want_auc=CPU_F32_TRAIN_AUC)
