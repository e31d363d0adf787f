#!/usr/bin/env python
"""Benchmark ladder.

Prints ONE JSON line: {"metric", "value", "unit", "device", "detail"}.
It measures the accelerator: with no chip it exits non-zero and prints
nothing (``BENCH_PLATFORM=cpu`` is the one explicit way to run it
off-chip, for debugging), and it exits non-zero when any rung raised.
Every rung's result carries the device it ran on.

The ladder follows BASELINE.md's config list:
  1. GBM binomial, HIGGS-shaped 1M x 28          (rows*trees/sec)
  2. DRF + GLM on the same 1M rows               (rows*trees/sec, rows/sec)
  3. DeepLearning MLP                            (samples/sec)
  4. histogram kernel MFU (the XGBoost gpu_hist -> TPU analog)

Methodology (single-decision-tree-benchmark.ipynb convention: time AFTER a
warm build): every timed number is STEADY-STATE — an identical untimed
warm-up run first pays XLA compilation, then the timed run re-uses the
compiled programs.  Wall-with-compile is reported alongside in detail.

One process holds the chip.  The rungs that start children either pin
them to a virtual CPU mesh (scaleout, multichip, elastic — their results
are counts and CPU timings, labelled so) or run before this process
touches JAX (coldstart, whose children need the chip themselves).
"""

import functools
import json
import os
import sys
import time
import traceback

import numpy as np

# v5 lite = v5e.  Dense bf16 peak per chip; override: BENCH_PEAK_TFLOPS.
_TPU_PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5e": 197.0,
    "TPU v5": 459.0, "TPU v5p": 459.0, "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


_data_cache = {}


def _make_data_cached(rows, cols, seed):
    """gbm10m and cpuref10m share the identical 10M-row dataset; the
    cache avoids synthesizing ~1.1 GB twice."""
    key = (rows, cols, seed)
    if key not in _data_cache:
        _data_cache.clear()             # hold at most one big dataset
        _data_cache[key] = _make_data(rows, cols, seed=seed)
    return _data_cache[key]


def _make_data(rows, cols, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    # HIGGS-like signal: nonlinear combination of a few features
    logits = (1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
              + 0.5 * np.sin(3 * X[:, 4]))
    y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-logits))).astype(np.int32)
    return X, y


def _frame(X, y):
    from h2o_tpu.core.frame import Frame, Vec, T_CAT
    cols = X.shape[1]
    names = [f"x{j}" for j in range(cols)] + ["y"]
    vecs = [Vec(X[:, j]) for j in range(cols)] + \
        [Vec(y, T_CAT, domain=["b", "s"])]
    return Frame(names, vecs)


def _xla_compiles():
    """Global backend-compile count (0 when diag is unavailable)."""
    try:
        from h2o_tpu.core.diag import DispatchStats
        DispatchStats.install_xla_listener()
        return DispatchStats.xla_compiles()
    except Exception:  # noqa: BLE001 — observability must never fail a run
        return 0


def _timed_train(make_builder, fr, warmup=True):
    """Train twice with identical shapes: run 1 compiles (untimed unless
    warmup=False), run 2 is steady-state.  Also reports how many XLA
    programs the steady-state run compiled — the dispatch-overhaul
    invariant is that this is ~0 (compiles-per-tree ≈ 0)."""
    wall_compile = None
    if warmup:
        t0 = time.time()
        make_builder().train(y="y", training_frame=fr)
        wall_compile = time.time() - t0
    c0 = _xla_compiles()
    t0 = time.time()
    model = make_builder().train(y="y", training_frame=fr)
    return model, time.time() - t0, wall_compile, _xla_compiles() - c0


def bench_gbm(fr, rows, trees, depth,
              histogram_type="QuantilesGlobal", bf16=False):
    """Headline config pins QuantilesGlobal (the only configuration
    with any chip history); gbm_ua / gbm_bf16 measure the
    UniformAdaptive default and the bf16-histogram mode."""
    from h2o_tpu.models.tree.gbm import GBM
    m, wall, wall_c, sc = _timed_train(
        lambda: GBM(ntrees=trees, max_depth=depth, learn_rate=0.1, seed=1,
                    nbins=64, histogram_type=histogram_type,
                    bf16_histograms=bf16), fr)
    return {"value": round(rows * trees / wall, 1),
            "unit": "rows*trees/sec", "wall_s": round(wall, 2),
            "wall_with_compile_s": round(wall_c, 2),
            "steady_compiles": sc,
            "compiles_per_tree": round(sc / trees, 3),
            "ntrees": trees, "max_depth": depth,
            "histogram_type": histogram_type, "bf16": bf16,
            "train_auc": round(float(m.output["training_metrics"]["AUC"]),
                               4)}


def bench_drf(fr, rows, trees, depth):
    from h2o_tpu.models.tree.drf import DRF
    m, wall, wall_c, sc = _timed_train(
        lambda: DRF(ntrees=trees, max_depth=depth, seed=1, nbins=64,
                    histogram_type="QuantilesGlobal"), fr)
    return {"value": round(rows * trees / wall, 1),
            "unit": "rows*trees/sec", "wall_s": round(wall, 2),
            "wall_with_compile_s": round(wall_c, 2),
            "steady_compiles": sc,
            "ntrees": trees, "max_depth": depth,
            "train_auc": round(float(m.output["training_metrics"]["AUC"]),
                               4)}


def bench_glm(fr, rows):
    from h2o_tpu.models.glm import GLM
    m, wall, wall_c, sc = _timed_train(
        lambda: GLM(family="binomial", lambda_=0.0, seed=1), fr)
    iters = int(m.output.get("iterations", 1) or 1)
    return {"value": round(rows / wall, 1), "unit": "rows/sec",
            "wall_s": round(wall, 2),
            "wall_with_compile_s": round(wall_c, 2),
            "steady_compiles": sc,
            "iterations": iters,
            "train_auc": round(float(m.output["training_metrics"]["AUC"]),
                               4)}


def bench_dl(fr, rows, epochs=1.0):
    from h2o_tpu.models.deeplearning import DeepLearning
    m, wall, wall_c, sc = _timed_train(
        lambda: DeepLearning(hidden=[200, 200], epochs=epochs, seed=1), fr)
    samples = rows * epochs
    return {"value": round(samples / wall, 1), "unit": "samples/sec",
            "wall_s": round(wall, 2),
            "wall_with_compile_s": round(wall_c, 2),
            "steady_compiles": sc,
            "hidden": [200, 200], "epochs": epochs}


def bench_hist_mfu(rows, cols, nbins=64, leaves=32, reps=10):
    """Steady-state MFU of the histogram one-hot matmul (ops/histogram.py)
    in bf16 — the hot kernel of the XGBoost gpu_hist -> TPU path.

    FLOPs counted for the MXU matmul only: (C*(B+1), R) @ (R, L*S)
    = 2 * R * C*(B+1) * L*S per call (one-hot construction is VPU/bandwidth
    work, excluded by standard MFU convention)."""
    import jax
    import jax.numpy as jnp
    from h2o_tpu.ops.histogram import histogram_build

    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, nbins, size=(rows, cols)),
                       jnp.int32)
    leaf = jnp.asarray(rng.integers(0, leaves, size=(rows,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(rows, 4)), jnp.float32)

    def run():
        return histogram_build(bins, leaf, stats, n_leaves=leaves,
                               nbins=nbins, bf16=True)
    # host-fetch barrier: a device->host scalar fetch cannot complete
    # until the whole dependency chain has executed
    float(run().sum())                             # compile + complete
    t0 = time.time()
    for _ in range(reps):
        out = run()
    float(out.sum())
    wall = (time.time() - t0) / reps
    flops = 2.0 * rows * (cols * (nbins + 1)) * (leaves * 4)
    achieved_tflops = flops / wall / 1e12
    import jax as _j
    kind = _j.devices()[0].device_kind
    peak = float(os.environ.get(
        "BENCH_PEAK_TFLOPS",
        _TPU_PEAK_BF16_TFLOPS.get(kind, 0) or 0))
    return {"value": round(achieved_tflops, 2), "unit": "TFLOP/s (bf16)",
            "mfu": round(achieved_tflops / peak, 4) if peak else None,
            "peak_tflops": peak or None,
            "rows": rows, "cols": cols, "nbins": nbins, "leaves": leaves,
            "kernel_ms": round(wall * 1e3, 3)}


def bench_deep(fr, rows):
    """Sparse-frontier engine at stock DRF depth (VERDICT r3 item 2's
    "deep config"): max_depth=20 with a bounded live frontier — the
    regime the dense heap could not reach."""
    from h2o_tpu.models.tree.drf import DRF
    trees = int(os.environ.get("BENCH_DEEP_TREES", 3))
    cap = os.environ.get("BENCH_DEEP_LEAVES", "1024")
    prev = os.environ.get("H2O_TPU_MAX_LIVE_LEAVES")
    os.environ["H2O_TPU_MAX_LIVE_LEAVES"] = cap
    try:
        m, wall, wall_c, sc = _timed_train(
            lambda: DRF(ntrees=trees, max_depth=20, seed=1, nbins=64,
                        min_rows=1.0), fr)
    finally:
        if prev is None:
            os.environ.pop("H2O_TPU_MAX_LIVE_LEAVES", None)
        else:
            os.environ["H2O_TPU_MAX_LIVE_LEAVES"] = prev
    return {"value": round(rows * trees / wall, 1),
            "unit": "rows*trees/sec", "wall_s": round(wall, 2),
            "wall_with_compile_s": round(wall_c, 2),
            "steady_compiles": sc,
            "ntrees": trees, "max_depth": 20,
            "max_live_leaves": int(cap),
            "effective_max_depth": int(m.output["effective_max_depth"]),
            "train_auc": round(float(m.output["training_metrics"]["AUC"]),
                               4)}


def bench_rapids_groupby(rows, groups=1024, reps=5):
    """Rapids data-munging throughput: one group-by bundle
    (mean+sum+max) over a categorical key, steady-state after a warm
    call pays the munge-kernel compiles (H2O's AstGroup workload on the
    device-resident path, core/munge.py).  Unit is rows*groups/sec —
    work scales with both the scan and the segment width."""
    from h2o_tpu.core.cloud import cloud
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    from h2o_tpu.rapids.interp import Session, rapids_exec
    rng = np.random.default_rng(3)
    g = rng.integers(0, groups, size=rows).astype(np.int32)
    x = rng.normal(size=rows).astype(np.float32)
    fr = Frame(["g", "x"],
               [Vec(g, T_CAT, domain=[f"g{i}" for i in range(groups)]),
                Vec(x)])
    fr.key = "bench_rapids_gb"
    cloud().dkv.put("bench_rapids_gb", fr)
    sess = Session("bench")
    expr = ("(GB bench_rapids_gb [0] mean 1 'all' sum 1 'all' "
            "max 1 'all')")
    try:
        rapids_exec(expr, sess)                      # warm (compiles)
        c0 = _xla_compiles()
        t0 = time.time()
        for _ in range(reps):
            out = rapids_exec(expr, sess)
        wall = (time.time() - t0) / reps
        sc = _xla_compiles() - c0
        from h2o_tpu.core.munge import device_munge_enabled
        return {"value": round(rows * groups / wall, 1),
                "unit": "rows*groups/sec", "wall_s": round(wall, 4),
                "rows": rows, "groups": int(out.nrows),
                "steady_compiles": sc, "reps": reps,
                "device_munge": bool(device_munge_enabled())}
    finally:
        cloud().dkv.remove("bench_rapids_gb")


def bench_rapids_pipeline(rows, reps=5):
    """Fused vs per-verb Rapids pipeline: the lazy planner
    (rapids/plan.py) compiles the filter -> na.omit -> sort chain and
    the filter -> group-by chain each into ONE exec-store-cached
    shard_map program (H2O_TPU_RAPIDS_FUSE=1); the eager oracle
    (=0) runs the same verbs one dispatch at a time.  The headline is
    fused pipeline rows/sec; detail carries the unfused number, the
    speedup, the repack/host-sync elisions from the planner stats
    (strictly positive = the fused path did strictly less boundary
    work), and the steady-state compile count (must be 0 — the region
    program is exec-store cached per chain fingerprint x row bucket)."""
    from h2o_tpu.core.cloud import cloud
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    from h2o_tpu.rapids.interp import Session, rapids_exec
    from h2o_tpu.rapids.plan import PlanStats
    rng = np.random.default_rng(5)
    x = rng.normal(size=rows).astype(np.float32)
    x[rng.random(rows) < 0.05] = np.nan
    v = rng.normal(size=rows).astype(np.float32)
    g = rng.integers(0, 64, size=rows).astype(np.int32)
    fr = Frame(["x", "v", "g"],
               [Vec(x), Vec(v),
                Vec(g, T_CAT, domain=[f"g{i}" for i in range(64)])])
    fr.key = "bench_rapids_pipe"
    cloud().dkv.put("bench_rapids_pipe", fr)
    inner = "(rows bench_rapids_pipe (> (cols bench_rapids_pipe [0]) -2))"
    sort_expr = f"(sort (na.omit {inner}) [2 1] [1 1])"
    gb_expr = ("(GB (rows bench_rapids_pipe "
               "(> (cols bench_rapids_pipe [1]) 0)) [2] "
               "mean 0 'all' sum 1 'all' nrow 0 'all')")
    prev_env = os.environ.get("H2O_TPU_RAPIDS_FUSE")

    def run_mode(fuse):
        os.environ["H2O_TPU_RAPIDS_FUSE"] = "1" if fuse else "0"
        sess = Session("bench_pipe")
        rapids_exec(sort_expr, sess)             # warm (compiles)
        rapids_exec(gb_expr, sess)
        before = PlanStats.snapshot()
        c0 = _xla_compiles()
        t0 = time.time()
        for _ in range(reps):
            rapids_exec(sort_expr, sess)
            rapids_exec(gb_expr, sess)
        wall = (time.time() - t0) / reps
        after = PlanStats.snapshot()

        def d(k):
            return (after[k] - before[k]) // reps
        return {"wall_s": round(wall, 4),
                "rows_per_s": round(rows * 5 / wall, 1),
                "steady_compiles": _xla_compiles() - c0,
                "regions_fused": d("regions_fused"),
                "repacks_elided": d("repacks_elided"),
                "syncs_elided": d("host_syncs_elided"),
                "unfused_fallbacks": d("fallbacks_unfused")}

    try:
        fused = run_mode(True)
        unfused = run_mode(False)
        return {"value": fused["rows_per_s"],
                "unit": "pipeline verb rows/sec (fused)", "rows": rows,
                "reps": reps, "fused": fused, "unfused": unfused,
                "speedup_fused": round(
                    fused["rows_per_s"] / unfused["rows_per_s"], 3)
                if unfused["rows_per_s"] else None}
    finally:
        cloud().dkv.remove("bench_rapids_pipe")
        if prev_env is None:
            os.environ.pop("H2O_TPU_RAPIDS_FUSE", None)
        else:
            os.environ["H2O_TPU_RAPIDS_FUSE"] = prev_env


_SCALEOUT_SRC = r"""
import json, os, sys, time
import numpy as np
import jax
nodes = int(os.environ['SCALEOUT_NODES'])
rows = int(os.environ['SCALEOUT_ROWS'])
groups = int(os.environ.get('SCALEOUT_GROUPS', 512))
reps = int(os.environ.get('SCALEOUT_REPS', 3))
from h2o_tpu.core.cloud import Cloud
Cloud.boot(nodes=nodes, model_axis=1)
from h2o_tpu.core.frame import Frame, T_CAT, Vec
from h2o_tpu.core import munge
from h2o_tpu.core.diag import DispatchStats
rng = np.random.default_rng(3)
g = rng.integers(0, groups, size=rows).astype(np.int32)
x = rng.normal(size=rows).astype(np.float32)
fr = Frame(['g', 'x'],
           [Vec(g, T_CAT, domain=[f'g{i}' for i in range(groups)]),
            Vec(x)])
aggs = [('mean', 1, 'all'), ('sum', 1, 'all'), ('max', 1, 'all')]

def pipeline():
    s = munge.sort_frame(fr, [1], [True])
    gb = munge.groupby_frame(fr, [0], aggs)
    fl = munge.filter_rows(fr, fr.vec('x').data > 0)
    # host-fetch barrier: a scalar from each result pins completion
    return (float(s.vecs[1].data[0]) + float(gb.vecs[1].data[0]) +
            float(fl.vecs[1].data[0] if fl.nrows else 0.0))

p0 = DispatchStats.host_pulls('munge')
pipeline()                                   # warm (compiles)
t0 = time.time()
for _ in range(reps):
    pipeline()
wall = (time.time() - t0) / reps
print(json.dumps({
    'nodes': nodes, 'rows': rows, 'wall_s': wall,
    'verb_rows_per_s': rows * 3 / wall,
    'munge_host_pulls': DispatchStats.host_pulls('munge') - p0,
    'shard_munge': munge.shard_munge_enabled()}))
"""


_CPU_MESH = {"platform": "cpu", "kind": "virtual host devices", "count": 8}


def _cpu_mesh_child(src, **extra_env):
    """Run ``src`` in a child pinned to an 8-virtual-device CPU mesh —
    the chip belongs to THIS process, and what these children report
    (collective byte counts, bitwise hashes, CPU wall times) needs no
    chip.  Returns the child's last stdout line as JSON."""
    import subprocess
    env = dict(os.environ)
    env.update(extra_env)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    env.setdefault("H2O_TPU_ROW_ALIGN", "128")
    r = subprocess.run([sys.executable, "-c", src],
                       capture_output=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr.decode()[-400:])
    return json.loads(r.stdout.decode().strip().splitlines()[-1])


def bench_rapids_scaleout():
    """Scale-out data plane: the sort+group-by+filter pipeline as
    shard_map collectives at nodes=1 vs nodes=4, each in a fresh
    child on the virtual CPU mesh (the mesh shape is fixed at boot) —
    the SAME collectives CI runs.  A CPU timing, labelled so: verb-rows/s
    at 4 nodes, with the 1-node number and the speedup in detail."""
    rows = int(os.environ.get("BENCH_SCALEOUT_ROWS", 200_000))
    out = {"rows": rows, "unit": "verb rows/sec @4 virtual CPU nodes",
           "device": _CPU_MESH}
    per = {}
    for nodes in (1, 4):
        per[f"nodes_{nodes}"] = _cpu_mesh_child(
            _SCALEOUT_SRC, SCALEOUT_NODES=str(nodes),
            SCALEOUT_ROWS=str(rows))
    out.update(per)
    n4 = per.get("nodes_4", {})
    n1 = per.get("nodes_1", {})
    out["value"] = round(n4.get("verb_rows_per_s", 0.0), 1)
    if n1.get("verb_rows_per_s") and n4.get("verb_rows_per_s"):
        out["speedup_4x_vs_1x"] = round(
            n4["verb_rows_per_s"] / n1["verb_rows_per_s"], 3)
    return out


_MULTICHIP_SRC = r"""
import hashlib, json, os, sys
import numpy as np
import jax
import jax.numpy as jnp
slices = int(os.environ['MC_SLICES'])
rows_list = [int(r) for r in os.environ['MC_ROWS'].split(',')]
from h2o_tpu.core.cloud import Cloud
Cloud.boot(nodes=8, model_axis=1, slices=slices)
from h2o_tpu.core.frame import Frame, T_CAT, Vec
from h2o_tpu.core import munge
from h2o_tpu.core.diag import DispatchStats
from h2o_tpu.ops.histogram import histogram_build

def coll():
    snap = DispatchStats.snapshot().get('collectives', {})
    out = {}
    for ph in snap.values():
        for tag, d in ph.items():
            c = out.setdefault(tag, [0, 0])
            c[0] += d['ici_bytes']
            c[1] += d['dcn_bytes']
    return out

def diff(a, b):
    return {t: {'ici_bytes': b[t][0] - a.get(t, [0, 0])[0],
                'dcn_bytes': b[t][1] - a.get(t, [0, 0])[1]}
            for t in b if b[t] != a.get(t, [0, 0])}

def hx(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]

res = {}
for R in rows_list:
    rng = np.random.default_rng(9)
    x = rng.normal(size=R).astype(np.float32)
    g = rng.integers(0, 64, R).astype(np.int32)
    fr = Frame(['x', 'g'],
               [Vec(x), Vec(g, T_CAT,
                            domain=[f'g{i}' for i in range(64)])])
    c0 = coll()
    s = munge.sort_frame(fr, [0], [True])
    c1 = coll()
    gb = munge.groupby_frame(fr, [1], [('mean', 0, 'all'),
                                       ('sum', 0, 'all'),
                                       ('nrow', 0, 'all')])
    c2 = coll()
    bins = jnp.asarray(rng.integers(0, 32, size=(R, 4)), jnp.int32)
    leaf = jnp.asarray(rng.integers(0, 8, size=(R,)), jnp.int32)
    st = jnp.asarray(rng.normal(size=(R, 4)), jnp.float32)
    h = histogram_build(bins, leaf, st, n_leaves=8, nbins=32)
    c3 = coll()
    res[str(R)] = {
        'sort': diff(c0, c1), 'groupby': diff(c1, c2),
        'hist': diff(c2, c3),
        'hash': {'sort': hx(*[v.data[:s.nrows] for v in s.vecs]),
                 'groupby': hx(*[v.data[:gb.nrows] for v in gb.vecs]),
                 'hist': hx(h)}}
print(json.dumps({'slices': slices, 'per_rows': res}))
"""

# the combine collectives of each step — the tags whose DCN bytes must
# be row-count independent on a two-level mesh.  The route all_to_all
# (sort.route) legitimately moves O(rows) and is reported separately.
_MC_COMBINE_TAGS = {"sort": ("sort.splitters", "sort.counts"),
                    "groupby": ("groupby.count", "groupby.partials"),
                    "hist": ("hist.table",)}


def bench_dryrun_multichip():
    """Two-level-mesh dry run (core/cloud.py hierarchical collectives):
    sort + group-by + histogram on a simulated 2x4 two-slice mesh
    (slices=2, 8 data shards) at TWO row counts, plus a flat 1x8 leg,
    each in a fresh child on the virtual CPU mesh.  Proves the traffic
    claim (a count, not a device measurement) — the
    cross-slice (DCN) bytes of every combine collective are O(table),
    independent of row count — and the bitwise claim: flat-mesh and
    two-slice outputs hash identically per step.  The per-axis byte
    ledger (DispatchStats.note_collective, recorded at trace time)
    is the measurement; the route all_to_all's O(rows) exchange is
    reported separately, never counted as combine traffic."""
    rows = os.environ.get("BENCH_MULTICHIP_ROWS", "48000,192000")
    out = {"rows": rows,
           "unit": "DCN combine bytes/step (2-slice virtual CPU mesh)",
           "device": _CPU_MESH}
    per = {}
    for slices in (1, 2):
        per[f"slices_{slices}"] = _cpu_mesh_child(
            _MULTICHIP_SRC, MC_SLICES=str(slices), MC_ROWS=rows)
    out.update(per)
    two = per.get("slices_2", {}).get("per_rows", {})
    flat = per.get("slices_1", {}).get("per_rows", {})
    # ledger tags are "<kind>:<step tag>" (e.g. "all_gather:
    # sort.splitters") — match on the suffix so a lowering change of
    # kind does not silently drop a tag from the claim
    def _step_dcn(d, step, tags):
        return sum(v.get("dcn_bytes", 0)
                   for t, v in d.get(step, {}).items()
                   if t.split(":", 1)[-1] in tags)

    dcn_per_step = {}
    for R, d in two.items():
        dcn_per_step[R] = {
            step: _step_dcn(d, step, tags)
            for step, tags in _MC_COMBINE_TAGS.items()}
    out["dcn_combine_bytes"] = dcn_per_step
    out["dcn_route_bytes"] = {
        R: _step_dcn(d, "sort", ("sort.route",))
        for R, d in two.items()}
    vals = list(dcn_per_step.values())
    out["dcn_row_independent"] = bool(
        len(vals) == 2 and vals[0] == vals[1] and
        any(v > 0 for v in vals[0].values()))
    out["bitwise_match_flat"] = bool(
        two and flat and all(
            two[R]["hash"] == flat[R]["hash"] for R in two if R in flat))
    # headline: total combine DCN per step-suite at the larger row count
    out["value"] = float(sum(vals[-1].values())) if vals else 0.0
    return out


_COLD_START_SRC = r"""
import json, os, sys, time
import numpy as np
import jax
rows, cols, trees, depth = (int(os.environ[k]) for k in
                            ('CS_ROWS', 'CS_COLS', 'CS_TREES', 'CS_DEPTH'))
rng = np.random.default_rng(7)
X = rng.normal(size=(rows, cols)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int32)
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.core.diag import DispatchStats
DispatchStats.install_xla_listener()
fr = Frame([f'x{j}' for j in range(cols)] + ['y'],
           [Vec(X[:, j]) for j in range(cols)] +
           [Vec(y, T_CAT, domain=['b', 's'])])
from h2o_tpu.models.tree.gbm import GBM
t0 = time.time()
m = GBM(ntrees=trees, max_depth=depth, learn_rate=0.1, seed=1,
        nbins=32, model_id='coldstart_gbm').train(y='y', training_frame=fr)
train_s = time.time() - t0
from h2o_tpu.serve.engine import ScoringEngine
eng = ScoringEngine()
t0 = time.time()
out = eng.predict(m, 0, X[:16].astype(np.float64))
score_s = time.time() - t0
from h2o_tpu.core.exec_store import exec_store
s = exec_store().stats()
d = jax.devices()
print(json.dumps({'train_s': train_s, 'score_s': score_s,
                  'device': {'platform': d[0].platform,
                             'kind': d[0].device_kind, 'count': len(d)},
                  'disk_hits': s['disk_hits'],
                  'disk_stores': s['disk_stores'],
                  'serialized_bytes': s['serialized_bytes_written'],
                  'backend_compiles': DispatchStats.xla_compiles(),
                  'pred0': float(np.asarray(out).ravel()[0])}))
"""


_ELASTIC_SRC = r"""
import json, os, sys, time
import numpy as np
import jax
ndev = len(jax.devices())
if ndev < 2:
    print(json.dumps({"skipped": f"{ndev} device(s) - reform needs >= 2"}))
    sys.exit(0)
from h2o_tpu.core.cloud import Cloud
from h2o_tpu.core import chaos as chaos_mod
from h2o_tpu.core import membership
from h2o_tpu.core.oom import is_device_loss
from h2o_tpu.models.tree.gbm import GBM
model_axis = 2 if ndev >= 8 else 1
nodes = (ndev // model_axis) & ~1 or 1
cl = Cloud.boot(nodes=nodes, model_axis=model_axis)
rows = int(os.environ.get("ER_ROWS", 4096))
trees = int(os.environ.get("ER_TREES", 6))
rng = np.random.default_rng(11)
X = rng.normal(size=(rows, 6)).astype(np.float32)
y = (X @ rng.normal(size=6).astype(np.float32)).astype(np.float32)
from h2o_tpu.core.frame import Frame, Vec
def frame():
    return Frame([f"x{i}" for i in range(6)] + ["y"],
                 [Vec(X[:, i]) for i in range(6)] + [Vec(y)])
rec = os.environ["ER_REC_DIR"]
mon = membership.monitor().configure(recovery_dir=rec, auto=True)
chaos_mod.configure(slice_loss_at_block=2, seed=1)
params = dict(ntrees=trees, max_depth=3, seed=7, nbins=16,
              distribution="gaussian", score_tree_interval=2,
              checkpoint_interval=2)
t0 = time.monotonic()
err = None
try:
    GBM(recovery_dir=rec, model_id="er_gbm", **params).train(
        y="y", training_frame=frame())
except Exception as e:
    err = e
if err is None or not is_device_loss(err):
    print(json.dumps({"error": f"expected an injected slice loss, "
                               f"got {err!r}"}))
    sys.exit(0)
t_loss = time.monotonic()
if not mon.wait_stable(600):
    print(json.dumps({"error": "recovery did not reach stable"}))
    sys.exit(0)
t_rec = time.monotonic() - t_loss
ev = mon.events()[-1]
m = mon.last_results[0] if mon.last_results else None
chaos_mod.reset()
t1 = time.monotonic()
GBM(model_id="er_post", **params).train(y="y", training_frame=frame())
post_s = time.monotonic() - t1
print(json.dumps({
    "time_to_recover_s": round(t_rec, 3),
    "post_reform_throughput": round(rows * trees / post_s, 1),
    "post_train_s": round(post_s, 3),
    "old_mesh": ev.get("old_mesh"), "new_mesh": ev.get("new_mesh"),
    "reform_ok": bool(ev.get("ok")), "attempts": ev.get("attempts"),
    "resumed": m is not None,
    "jobs_interrupted": len(ev.get("jobs_interrupted") or ())}))
"""


def bench_elastic_resume():
    """Elastic-membership drill (core/membership.py): a GBM training
    under per-block checkpoints is hit by an injected slice loss
    mid-forest; the membership layer quiesces, reforms the mesh onto
    the surviving half and resumes the build from its last block
    checkpoint.  Headline value is time-to-recover (loss surfacing ->
    mesh stable with the job resumed); post-reform training throughput
    on the shrunken mesh rides in detail.  Runs in a fresh child on the
    virtual CPU mesh (a CPU timing, labelled so), so the mesh resize
    cannot disturb the rest of the ladder."""
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="h2o_elastic_")
    try:
        out = _cpu_mesh_child(_ELASTIC_SRC,
                              ER_REC_DIR=os.path.join(tmp, "rec"))
        if "error" in out:
            raise RuntimeError(out["error"])
        if "time_to_recover_s" in out:
            out = {"value": out.pop("time_to_recover_s"),
                   "unit": "s loss->recovered (virtual CPU mesh)", **out}
        out["device"] = _CPU_MESH
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_audit_overhead():
    """graftaudit zero-overhead contract (core/lockwitness.py): the
    runtime lock witness must be free when ``H2O_TPU_LOCK_WITNESS`` is
    off and within noise when on — the factory returns plain threading
    primitives at creation when disabled, and the steady-state witness
    path is one tls lookup + an existing-edge counter bump.  Two
    ExecStores built with the flag off/on dispatch the same cached
    kernel; headline is the median per-dispatch delta, gated < 2%.
    The kernel is munge-sized (256k rows, a few ops): the witness cost
    is a ~µs-scale constant per dispatch, so the gate is meaningful
    against a representative dispatch, not a no-op microbenchmark."""
    import statistics

    import jax.numpy as jnp

    from h2o_tpu.core.exec_store import ExecStore

    x = jnp.arange(262144.0)
    reps, iters = 7, 40

    def measure(flag):
        prev = os.environ.get("H2O_TPU_LOCK_WITNESS")
        os.environ["H2O_TPU_LOCK_WITNESS"] = flag
        try:
            st = ExecStore()  # lock flavor is decided at creation
            run = lambda: st.dispatch(  # noqa: E731
                "munge", ("audit_ovh", 262144),
                lambda: (lambda a: jnp.cumsum(a * 2.0) + 1.0), (x,),
                site="munge:audit_ovh")
            run()  # compile once; the loop times the cached path
            samples = []
            for _ in range(reps):
                t0 = time.time()
                for _ in range(iters):
                    run()
                samples.append((time.time() - t0) / iters)
            return statistics.median(samples)
        finally:
            if prev is None:
                os.environ.pop("H2O_TPU_LOCK_WITNESS", None)
            else:
                os.environ["H2O_TPU_LOCK_WITNESS"] = prev

    off_s = measure("0")
    on_s = measure("1")
    delta_pct = (on_s - off_s) / off_s * 100.0
    return {"value": round(delta_pct, 3),
            "unit": "% dispatch delta, witness on vs off",
            "ok": bool(delta_pct < 2.0),
            "dispatch_off_us": round(off_s * 1e6, 2),
            "dispatch_on_us": round(on_s * 1e6, 2)}


def bench_cold_start():
    """Cold-vs-warm process start (the exec-store AOT + XLA persistent
    cache unlock): the SAME tiny GBM-train + first-serve-score workload
    runs in two fresh children sharing one store/cache directory, handed
    to them through H2O_TPU_EXEC_STORE_DIR and JAX_COMPILATION_CACHE_DIR.
    Run 1 is fully cold (pays every XLA compile and writes the store);
    run 2 is a warm restart — it loads serialized executables from disk
    and hits the persistent compile cache.  The headline value is the
    cold/warm wall ratio for first-train; first-score and backend
    compile counts ride in detail.

    The children need the chip, so the ladder runs this rung BEFORE the
    parent touches JAX; the device in the result is what the children
    report."""
    import shutil
    import subprocess
    import tempfile
    tmp = tempfile.mkdtemp(prefix="h2o_cold_")
    try:
        env = dict(os.environ)
        env["H2O_TPU_EXEC_STORE_DIR"] = os.path.join(tmp, "exec")
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "xla")
        if os.environ.get("BENCH_PLATFORM"):
            env["JAX_PLATFORMS"] = os.environ["BENCH_PLATFORM"]
            env["H2O_TPU_COMPILE_CACHE"] = "1"
        rows = int(os.environ.get("BENCH_COLD_ROWS", 50_000))
        env.update({"CS_ROWS": str(rows), "CS_COLS": "8",
                    "CS_TREES": "3", "CS_DEPTH": "4"})

        def run():
            r = subprocess.run([sys.executable, "-c", _COLD_START_SRC],
                               capture_output=True, env=env, timeout=900)
            if r.returncode != 0:
                raise RuntimeError(r.stderr.decode()[-400:])
            out = json.loads(r.stdout.decode().strip().splitlines()[-1])
            _require_accelerator(out["device"]["platform"])
            return out

        cold = run()
        warm = run()
        return {"value": round(cold["train_s"] /
                               max(warm["train_s"], 1e-9), 3),
                "unit": "cold/warm first-train wall ratio",
                "device": warm["device"],
                "cold_train_s": round(cold["train_s"], 2),
                "warm_train_s": round(warm["train_s"], 2),
                "cold_score_s": round(cold["score_s"], 3),
                "warm_score_s": round(warm["score_s"], 3),
                "cold_backend_compiles": cold["backend_compiles"],
                "warm_backend_compiles": warm["backend_compiles"],
                "warm_disk_hits": warm["disk_hits"],
                "cold_disk_stores": cold["disk_stores"],
                "serialized_bytes": cold["serialized_bytes"],
                "rows": rows,
                "pred_match": cold["pred0"] == warm["pred0"]}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_streaming_refresh(rows=None, chunk_rows=None):
    """Streaming ingest + online refresh (h2o_tpu/stream): one pipeline
    ingests a CSV in chunks, GBM checkpoint-refreshes every 5 chunks and
    hot-swaps a serve alias, while a hammer thread scores the alias
    continuously.  Reports sustained ingest rows/s (headline), mean
    refresh-to-hot-swap latency, and /score p99 DURING refreshes — the
    no-downtime number the live alias contract promises."""
    import tempfile
    import threading
    from h2o_tpu.core.cloud import cloud
    from h2o_tpu.serve.registry import registry
    from h2o_tpu.stream import ChunkReader, start_pipeline, stop_pipeline

    rows = int(rows or os.environ.get("BENCH_STREAM_ROWS", 100_000))
    chunk_rows = int(chunk_rows or
                     os.environ.get("BENCH_STREAM_CHUNK_ROWS",
                                    max(rows // 25, 1)))
    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, 6)).astype(np.float32)
    y = np.where(X[:, 0] + 0.5 * X[:, 1] > 0, "s", "b")
    fd, path = tempfile.mkstemp(suffix=".csv")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(",".join(f"x{j}" for j in range(6)) + ",y\n")
            for i in range(rows):
                f.write(",".join(f"{v:.5f}" for v in X[i]) +
                        f",{y[i]}\n")
        alias = "bench_stream_live"
        lat, codes = [], []
        stop = threading.Event()
        probe = {f"x{j}": 0.1 for j in range(6)}

        def hammer():
            while not stop.is_set():
                t0 = time.time()
                try:
                    registry().score_rows(alias, [probe])
                    codes.append(200)
                except KeyError:
                    codes.append(404)      # before the first deploy
                except Exception:  # noqa: BLE001 — shed/deadline
                    codes.append(503)
                lat.append((time.time() - t0) * 1000.0)
                time.sleep(0.002)

        t = threading.Thread(target=hammer, daemon=True)
        t0 = time.time()
        pipe = start_pipeline(
            "bench_stream", ChunkReader(path, chunk_rows=chunk_rows),
            "y", algo="gbm",
            model_params=dict(max_depth=4, seed=1, nbins=16, ntrees=0),
            refresh_chunks=5, trees_per_refresh=5, alias=alias)
        t.start()
        pipe.job.join(timeout=1800)
        wall = time.time() - t0
        stop.set()
        t.join(timeout=5)
        st = pipe.status()
        ok_lat = [l for l, c in zip(lat, codes) if c == 200]
        p99 = float(np.percentile(ok_lat, 99)) if ok_lat else 0.0
        out = {"value": round(rows / wall, 1), "unit": "ingest rows/sec",
               "wall_s": round(wall, 2), "rows": rows,
               "chunks": st["chunks_landed"],
               "refreshes": st["refreshes"],
               "failed_refreshes": st["failed_refreshes"],
               "final_lag": st["lag"],
               "swap_ms_mean": round(float(np.mean(st["swap_ms"])), 2)
               if st["swap_ms"] else 0.0,
               "score_p99_ms_during_refresh": round(p99, 2),
               "score_requests": len(codes),
               "score_5xx": sum(1 for c in codes if c >= 500)}
        try:
            registry().undeploy(alias, drain_secs=2.0)
        except KeyError:
            pass
        stop_pipeline("bench_stream", remove=True)
        return out
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


def bench_serving_sustained():
    """Sustained serving under fixed offered load across a replica
    fleet (the VERDICT #5 BigScore analog, serving edition): N client
    threads drive a fixed request rate at a deployed alias routed
    through the fleet for a fixed window.  Reports achieved scored
    rows/s (headline), p50/p95/p99 latency of successful requests, the
    reject rate (429/503 sheds — deliberate degradation, not failures),
    and the adaptive/breaker state after the run.  Every non-contract
    status counts as an error."""
    import threading
    from h2o_tpu.models.tree.gbm import GBM
    from h2o_tpu.serve import ServingConfig
    from h2o_tpu.serve.replica import fleet, reset_fleet

    secs = float(os.environ.get("BENCH_SERVE_SECS", 15.0))
    offered = float(os.environ.get("BENCH_SERVE_RPS", 300.0))
    n_rep = int(os.environ.get("BENCH_SERVE_REPLICAS", 3))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    Xt, yt = _make_data(4096, 6, seed=13)
    fr = _frame(Xt, yt)
    m = GBM(ntrees=5, max_depth=4, seed=13, nbins=16).train(
        y="y", training_frame=fr)
    fl = fleet(n_rep)
    alias = "bench_serve_sustained"
    fl.deploy(alias, m, ServingConfig(max_batch=32, max_delay_ms=1.0,
                                      queue_cap=256, adaptive=True))
    lat, oks, rejects, errors = [], [0], [0], [0]
    lock = threading.Lock()
    stop = threading.Event()
    interval = clients / max(offered, 1.0)
    probe = [{f"x{j}": 0.1 for j in range(6)}]

    def client():
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                fl.score_rows(alias, probe, deadline_ms=2000)
                with lock:
                    oks[0] += 1
                    lat.append((time.monotonic() - t0) * 1000.0)
            except Exception as e:  # noqa: BLE001 — classify by contract
                kind = type(e).__name__
                with lock:
                    if kind in ("QueueFull", "ShedLoad", "BreakerOpen",
                                "TimeoutError", "MeshReforming",
                                "NoHealthyReplica"):
                        rejects[0] += 1
                    else:
                        errors[0] += 1
            # fixed offered load: sleep off the remainder of the slot
            left = interval - (time.monotonic() - t0)
            if left > 0:
                time.sleep(left)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(secs)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    wall = time.monotonic() - t0
    info = fl.describe(alias)
    total = oks[0] + rejects[0] + errors[0]
    p50, p95, p99 = (np.percentile(lat, [50, 95, 99])
                     if lat else (0.0, 0.0, 0.0))
    out = {"value": round(oks[0] / wall, 1), "unit": "scored req/sec",
           "wall_s": round(wall, 2), "replicas": n_rep,
           "clients": clients, "offered_rps": offered,
           "requests": total, "ok": oks[0],
           "rejected": rejects[0], "errors": errors[0],
           "reject_rate": round(rejects[0] / total, 4) if total else 0.0,
           "p50_ms": round(float(p50), 2),
           "p95_ms": round(float(p95), 2),
           "p99_ms": round(float(p99), 2),
           "max_batch_final": info["config"]["max_batch"]
           if not info["adaptive"].get("enabled") else
           info["adaptive"]["max_batch"],
           "retunes": info["adaptive"].get("retunes", 0),
           "breaker_trips": (info["breaker"] or {}).get("trips", 0),
           "fleet_retries": fl.stats()["retries"]}
    try:
        fl.undeploy(alias, drain_secs=2.0)
    except KeyError:
        pass
    reset_fleet()
    return out


def bench_automl_e2e():
    """End-to-end AutoML wall clock: one budgeted AutoML build (the
    grid + ensemble pipeline a tenant actually submits) on a HIGGS-like
    frame.  Reports models/min (headline), leaderboard depth, the
    leader's sort metric and total wall — the number that moves when
    admission, the job pool, or the builder hot path regress."""
    from h2o_tpu.automl.automl import AutoML

    rows = int(os.environ.get("BENCH_AUTOML_ROWS", 20_000))
    max_models = int(os.environ.get("BENCH_AUTOML_MODELS", 4))
    nfolds = int(os.environ.get("BENCH_AUTOML_NFOLDS", 2))
    X, y = _make_data(rows, 8, seed=29)
    fr = _frame(X, y)
    t0 = time.monotonic()
    aml = AutoML(max_models=max_models, seed=29, nfolds=nfolds,
                 include_algos=["GBM", "GLM", "DRF"],
                 project_name="bench_automl_e2e")
    aml.train(y="y", training_frame=fr)
    wall = time.monotonic() - t0
    n_models = len(aml.leaderboard.models)
    return {"value": round(n_models / wall * 60.0, 2),
            "unit": "models/min",
            "wall_s": round(wall, 2), "rows": rows,
            "models": n_models, "nfolds": nfolds,
            "leader": str(getattr(aml.leader, "key", aml.leader))
            if aml.leader is not None else None}


def bench_multitenant_soak():
    """Shortened in-process multi-tenant isolation rung (the full leg
    lives in tools/soak.py --multitenant): three weighted tenants each
    push a burst of small GBM jobs through fair-share admission while a
    serve hammer scores a shared alias per tenant.  Reports admitted
    jobs/sec (headline), the fairness spread (served/weight ratio
    max/min over tenants — 1.0 is perfect), classified-refusal counts,
    per-tenant serve p99, and the isolation invariant
    ``cross_tenant_evictions`` below the high-water mark (must be 0)."""
    import threading
    from h2o_tpu.core.cloud import cloud
    from h2o_tpu.core.memory import manager
    from h2o_tpu.core.tenant import (create_tenant, delete_tenant,
                                     tenant_context)
    from h2o_tpu.models.tree.gbm import GBM
    from h2o_tpu.serve import ServingConfig
    from h2o_tpu.serve.registry import registry

    jobs_per = int(os.environ.get("BENCH_MT_JOBS", 4))
    weights = {"mt_a": 3.0, "mt_b": 2.0, "mt_c": 1.0}
    for name, w in weights.items():
        create_tenant(name, weight=w, hbm_share=0.3)
    Xt, yt = _make_data(4096, 6, seed=31)
    fr = _frame(Xt, yt)
    m = GBM(ntrees=3, max_depth=3, seed=31, nbins=16).train(
        y="y", training_frame=fr)
    alias = "bench_mt_soak"
    registry().deploy(alias, m, ServingConfig(max_batch=32,
                                              max_delay_ms=1.0,
                                              queue_cap=128))
    lat = {t: [] for t in weights}
    lock = threading.Lock()
    stop = threading.Event()
    probe = [{f"x{j}": 0.1 for j in range(6)}]

    def hammer(tname):
        while not stop.is_set():
            h0 = time.monotonic()
            try:
                registry().score_rows(alias, probe, tenant=tname)
                with lock:
                    lat[tname].append((time.monotonic() - h0) * 1000.0)
            except Exception:  # noqa: BLE001 — sheds are the protocol
                pass
            time.sleep(0.005)

    hammers = [threading.Thread(target=hammer, args=(t,), daemon=True)
               for t in weights]
    for h in hammers:
        h.start()
    t0 = time.monotonic()
    jobs = []
    for name in weights:
        with tenant_context(name):
            for i in range(jobs_per):
                jobs.append(GBM(ntrees=2, max_depth=3, seed=31 + i,
                                nbins=16).train_async(
                    y="y", training_frame=fr))
    for j in jobs:
        j.join(timeout=600)
    wall = time.monotonic() - t0
    stop.set()
    for h in hammers:
        h.join(timeout=5)
    adm = cloud().jobs.admission.stats()
    mem = manager().stats()
    served = {t: adm["tenants"].get(t, {}).get("served", 0.0)
              for t in weights}
    ratios = [served[t] / weights[t] for t in weights if served[t]]
    fairness = (round(max(ratios) / min(ratios), 3)
                if len(ratios) == len(weights) else 0.0)
    done = sum(1 for j in jobs if j.status == "DONE")
    out = {"value": round(done / wall, 2), "unit": "tenant jobs/sec",
           "wall_s": round(wall, 2), "tenants": len(weights),
           "jobs": len(jobs), "done": done,
           "admitted": adm["admitted"], "rejected": adm["rejected"],
           "rejects_by_reason": adm["rejects_by_reason"],
           "fairness_spread": fairness,
           "cross_tenant_evictions": mem["cross_tenant_evictions"],
           "cross_tenant_below_highwater":
               mem["cross_tenant_below_highwater"],
           "serve_p99_ms": {t: round(float(np.percentile(v, 99)), 2)
                            for t, v in lat.items() if v}}
    try:
        registry().undeploy(alias, drain_secs=2.0)
    except KeyError:
        pass
    for name in weights:
        delete_tenant(name)
    return out


def bench_lever_ab():
    """Per-lever A/B deltas (core/autotune.py): force-probe every
    registered lever's candidates on the live backend — parity gate +
    median-of-k timing, decisions persisted when a store dir is set —
    and record per-lever winner, probe timings, and delta vs the
    reference variant.  This is the block that turns BENCH_*.json into
    the flag-flip evidence the speed-race item needs; on CPU tiers the
    reference variants win (Pallas candidates report ineligible)."""
    from h2o_tpu.core import autotune

    levers = {}
    best = 1.0
    for site in autotune.sites():
        try:
            d = autotune.resolve(site)
        except Exception as e:  # noqa: BLE001 — one broken lever must
            levers[site] = {"error": repr(e)}  # not lose the others
            continue
        win = d["winner"]
        cand = d["candidates"]
        delta = cand.get(win, {}).get("vs_ref", 1.0) \
            if win != d["reference"] else 1.0
        best = max(best, delta)
        levers[site] = {
            "winner": win, "reference": d["reference"],
            "flag": d["flag"], "source": d["source"],
            "bucket": d["bucket"], "backend": d["backend"],
            "delta_vs_reference": round(float(delta), 4),
            "timings_ms": {
                n: round(c["median_ms"], 4)
                for n, c in cand.items() if c.get("median_ms")},
            "disqualified": {
                n: c["status"] for n, c in cand.items()
                if c.get("status") not in (None, "ok")}}
    return {"value": round(best, 4),
            "unit": "best lever speedup (ref/winner)",
            "levers": levers, "stats": autotune.stats()}


def bench_bins_pack(fr, rows, depth):
    """Packed vs int32 binned-matrix A/B (ops/binpack.py, the
    ``tree.bins_dtype`` lever): the binned matrix's HBM footprint under
    each carrier, and the steady-state train-throughput delta with the
    lever forced each way.  The acceptance bar is >= 2x byte reduction
    at B <= 64 — the uint8 carrier gives 4x by construction; the
    throughput ratio is the measured half the autotuner's margin gate
    consumes on real silicon."""
    import jax.numpy as jnp
    from h2o_tpu.models.tree.gbm import GBM
    from h2o_tpu.ops import binpack

    trees = int(os.environ.get("BENCH_PACK_TREES", 5))
    prev = os.environ.get("H2O_TPU_BINS_PACK")
    walls, out = {}, {}
    try:
        for mode, flag in (("packed", "1"), ("int32", "0")):
            os.environ["H2O_TPU_BINS_PACK"] = flag
            m, wall, wall_c, sc = _timed_train(
                lambda: GBM(ntrees=trees, max_depth=depth,
                            learn_rate=0.1, seed=1, nbins=64,
                            histogram_type="QuantilesGlobal"), fr)
            walls[mode] = wall
            out[mode] = {"rows_trees_per_s": round(rows * trees / wall,
                                                   1),
                         "wall_s": round(wall, 2),
                         "steady_compiles": sc}
        from h2o_tpu.models.tree import shared_tree as st
        fine = st.model_fine_na(m.output)
        C = len(m.output["x"])
        itemsize = jnp.dtype(binpack.bins_dtype_for(fine)).itemsize
        bytes_i32 = rows * C * 4
        bytes_packed = rows * C * itemsize
        out.update({
            "packed_dtype": binpack.packed_dtype_name(fine, True),
            "fine_nbins": fine,
            "bins_bytes_int32": bytes_i32,
            "bins_bytes_packed": bytes_packed,
            "bytes_reduction": round(bytes_i32 / bytes_packed, 2)})
    finally:
        if prev is None:
            os.environ.pop("H2O_TPU_BINS_PACK", None)
        else:
            os.environ["H2O_TPU_BINS_PACK"] = prev
    out["value"] = round(walls["int32"] / walls["packed"], 4)
    out["unit"] = "packed/int32 speedup (train steady-state)"
    return out


def bench_stats_pack(fr, rows, depth):
    """Quantized vs f32 gradient-stat A/B (ops/statpack.py, the
    ``tree.stats_dtype`` lever): the histogram hot path's HBM bytes
    under each carrier (stats operand + one-hot matmul operands + the
    accumulated table), the per-level ``hist.table`` collective bytes
    from the PR 18 two-level ledger (int32 tables cross the wire when
    quantized), the steady-state train-throughput delta with the lever
    forced each way, and the forest-metric deviation the tolerance
    gate consumes.  The acceptance bar is >= 2x table+stats byte
    reduction at carrier itemsize <= 2 — int16 gives it by
    construction (every operand narrows 4 -> 2 bytes; the int32
    accumulator stays 4, but is O(table), not O(rows))."""
    import jax.numpy as jnp
    from h2o_tpu.core.diag import DispatchStats
    from h2o_tpu.models.tree.gbm import GBM
    from h2o_tpu.ops import statpack
    from h2o_tpu.ops.histogram import N_STATS

    trees = int(os.environ.get("BENCH_PACK_TREES", 5))
    prev = os.environ.get("H2O_TPU_STATS_DTYPE")
    walls, out, metrics, coll = {}, {}, {}, {}

    def _hist_table_bytes():
        snap = DispatchStats.snapshot().get("collectives", {})
        tot = {"n": 0, "ici_bytes": 0, "dcn_bytes": 0}
        for ph in snap.values():
            for tag, d in ph.items():
                if "hist.table" in tag:
                    for k in tot:
                        tot[k] += d[k]
        return tot

    try:
        for mode, flag in (("quantized", "1"), ("f32", "0")):
            os.environ["H2O_TPU_STATS_DTYPE"] = flag
            c0 = _hist_table_bytes()
            m, wall, wall_c, sc = _timed_train(
                lambda: GBM(ntrees=trees, max_depth=depth,
                            learn_rate=0.1, seed=1, nbins=64,
                            histogram_type="QuantilesGlobal"), fr)
            c1 = _hist_table_bytes()
            walls[mode] = wall
            tm = m.output.get("training_metrics") or {}
            metrics[mode] = {k: float(tm[k]) for k in
                             ("logloss", "auc", "mean_residual_deviance")
                             if tm.get(k) is not None}
            coll[mode] = {k: c1[k] - c0[k] for k in c1}
            out[mode] = {"rows_trees_per_s": round(rows * trees / wall,
                                                   1),
                         "wall_s": round(wall, 2),
                         "steady_compiles": sc,
                         "hist_table_collective": coll[mode]}
        C = len(m.output["x"])
        B1, S, L = 64 + 1, N_STATS, 1 << depth
        itemsize = statpack.stats_itemsize("int16")
        # per-level hot-path bytes: the stats operand, both matmul
        # operands (binhot and leafhot (x) stats — each at the stats
        # carrier dtype in the integer dot), plus the accumulated
        # table (int32 quantized, f32 reference: 4 bytes either way)
        table = L * C * B1 * S * 4
        ops_f32 = rows * (S + C * B1 + L * S) * 4
        ops_q = rows * (S + C * B1 + L * S) * itemsize
        out.update({
            "stats_dtype": "int16",
            "stats_bytes_f32": rows * S * 4,
            "stats_bytes_packed": rows * S * itemsize,
            "hot_path_bytes_f32": ops_f32 + table,
            "hot_path_bytes_packed": ops_q + table,
            # headline: the O(rows) traffic — stats + matmul operands,
            # every term narrowed 4 -> itemsize bytes.  The int32
            # accumulator table is row-count independent and 4 bytes
            # under BOTH carriers; the _with_table figure includes it
            "bytes_reduction": round(ops_f32 / ops_q, 2),
            "bytes_reduction_with_table": round((ops_f32 + table)
                                                / (ops_q + table), 2),
            "metrics": metrics,
            "metric_delta": {
                k: round(abs(metrics["quantized"][k]
                             - metrics["f32"][k]), 6)
                for k in metrics.get("f32", {})
                if k in metrics.get("quantized", {})},
            "metric_tol": statpack.METRIC_TOL})
    finally:
        if prev is None:
            os.environ.pop("H2O_TPU_STATS_DTYPE", None)
        else:
            os.environ["H2O_TPU_STATS_DTYPE"] = prev
    out["value"] = round(walls["f32"] / walls["quantized"], 4)
    out["unit"] = "quantized/f32 speedup (train steady-state)"
    return out


def bench_ingest_bigger_than_hbm(rows, cols, depth):
    """Train on a frame BIGGER than the configured HBM budget — the
    tiered-column-store rung (core/landing.py + core/memory.py):
    shard-direct ingest (no whole-frame single-host transfer), then a
    streamed-bins GBM whose windows page through HBM <-> host under
    ``H2O_TPU_MEM_BUDGET``.  Reports ingest rows/s (headline), the
    steady-state train throughput, peak HBM bytes vs the budget, the
    prefetcher's hit rate / demand-page stalls and the landing layer's
    pull accounting (largest single host->device transfer).  Rows
    arrive pre-capped by the CPU-fallback ladder."""
    from h2o_tpu.core import landing
    from h2o_tpu.core.memory import manager, set_budget
    from h2o_tpu.models.tree.gbm import GBM

    trees = int(os.environ.get("BENCH_TIER_TREES", 5))
    frame_bytes = rows * (cols + 1) * 4
    # bounded budget: a third of the frame, unless the operator pinned
    # one — either way the auto stream gate must trip
    budget = int(os.environ.get("H2O_TPU_MEM_BUDGET", 0) or
                 frame_bytes // 3)
    prev_budget = manager().budget
    prev_stream = os.environ.get("H2O_TPU_TIER_STREAM")
    os.environ["H2O_TPU_TIER_STREAM"] = "auto"
    X, y = _make_data(rows, cols, seed=3)
    m = set_budget(budget)
    out = {"budget_bytes": budget, "frame_bytes": frame_bytes,
           "rows": rows}
    try:
        s0 = m.stats()
        landing.reset_stats()
        t0 = time.time()
        fr = _frame(X, y)
        ingest_wall = time.time() - t0
        model, wall, _wc, sc = _timed_train(
            lambda: GBM(ntrees=trees, max_depth=depth, learn_rate=0.1,
                        seed=1, nbins=32,
                        histogram_type="UniformAdaptive"), fr)
        s1 = m.stats()
        land = landing.stats()
        hits = s1["prefetch_hits"] - s0["prefetch_hits"]
        misses = s1["prefetch_misses"] - s0["prefetch_misses"]
        out.update({
            "ingest_rows_per_s": round(rows / max(ingest_wall, 1e-9), 1),
            "train_rows_trees_per_s": round(rows * trees / wall, 1),
            "train_wall_s": round(wall, 2),
            "steady_compiles": sc,
            "peak_hbm_bytes": s1["peak_hbm_bytes"],
            "pages_in": s1["pages_in"] - s0["pages_in"],
            "pages_out": s1["pages_out"] - s0["pages_out"],
            "prefetch_hits": hits, "prefetch_misses": misses,
            "prefetch_hit_rate": round(hits / (hits + misses), 3)
            if (hits + misses) else None,
            "demand_page_stalls": s1["demand_page_stalls"]
            - s0["demand_page_stalls"],
            "landed_chunks": land["chunks_landed"],
            "whole_puts": land["whole_puts"],
            "max_single_transfer_bytes": land["max_transfer_bytes"]})
    finally:
        set_budget(prev_budget)
        if prev_stream is None:
            os.environ.pop("H2O_TPU_TIER_STREAM", None)
        else:
            os.environ["H2O_TPU_TIER_STREAM"] = prev_stream
    out["value"] = out["ingest_rows_per_s"]
    out["unit"] = "rows/sec ingest (HBM-bounded, shard-direct)"
    return out


def bench_cpu_reference(X, y, rows, trees, depth):
    """External CPU baseline for the north-star ratio (VERDICT r3 item 3):
    the same GBM workload through a widely-accepted CPU hist
    implementation — xgboost `hist` when importable, else sklearn
    HistGradientBoosting — timed the same steady-state way (fit is
    single-shot; sklearn/xgboost pay no JIT, so one timed fit IS
    steady-state).  Not an H2O cluster, but it turns "vs my own last
    round" into a defensible external ratio."""
    t_load = time.time()
    try:
        import xgboost as xgb  # noqa: F401
        impl = f"xgboost-{xgb.__version__} tree_method=hist"

        def fit():
            clf = xgb.XGBClassifier(
                n_estimators=trees, max_depth=depth, learning_rate=0.1,
                tree_method="hist", max_bin=64, n_jobs=-1,
                eval_metric="logloss")
            clf.fit(X, y)
    except ImportError:
        from sklearn.ensemble import HistGradientBoostingClassifier
        import sklearn
        impl = (f"sklearn-{sklearn.__version__} "
                "HistGradientBoostingClassifier")

        def fit():
            clf = HistGradientBoostingClassifier(
                max_iter=trees, max_depth=depth, learning_rate=0.1,
                max_bins=63, early_stopping=False)
            clf.fit(X, y)
    t0 = time.time()
    fit()
    wall = time.time() - t0
    import os as _os
    return {"value": round(rows * trees / wall, 1),
            "unit": "rows*trees/sec", "wall_s": round(wall, 2),
            "impl": impl, "ntrees": trees, "max_depth": depth,
            "nthreads": _os.cpu_count(),
            "import_s": round(t0 - t_load, 2)}


def bench_cpu_reference_10m(cols, depth):
    """External CPU baseline at the north-star row count (BASELINE.md
    names 10M rows): same data/ntrees/depth as bench_gbm10m, so
    vs_cpu_reference_10m is apples-to-apples where the chip is actually
    saturated."""
    rows = int(os.environ.get("BENCH_ROWS_10M", 10_000_000))
    X, y = _make_data_cached(rows, cols, seed=1)
    return bench_cpu_reference(X, y, rows, trees=5, depth=depth)


def bench_gbm10m(cols, depth):
    """BASELINE.md config 4: the XGBoost gpu_hist -> TPU path at 10M rows
    (the row count the north-star names).  Fewer trees keep the driver's
    wall clock bounded; throughput is steady-state rows*trees/sec."""
    rows = int(os.environ.get("BENCH_ROWS_10M", 10_000_000))
    trees = 5
    X, y = _make_data_cached(rows, cols, seed=1)
    fr = _frame(X, y)
    out = bench_gbm(fr, rows, trees, depth)
    out["rows"] = rows
    return out


def _require_accelerator(platform):
    """This ladder measures the accelerator.  A CPU number under a
    device metric's name is worse than no number, so no chip is an
    error — unless the caller asked for the CPU by name."""
    if platform == "cpu" and os.environ.get("BENCH_PLATFORM") != "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform=cpu); "
            "BENCH_PLATFORM=cpu runs the ladder off-chip for debugging")


def _device():
    """The device every in-process rung runs on, as JAX reports it
    (initialises the backend: call after the coldstart rung)."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    d = jax.devices()
    _require_accelerator(d[0].platform)
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def main():
    detail = {}
    failed = _main_ladder(detail)
    print(json.dumps(headline_payload(detail)), flush=True)
    if failed:
        print(f"bench.py: rungs failed: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


def _measured(v):
    return isinstance(v, dict) and "value" in v


def _pick_headline(detail):
    """Headline preference: gbm, else gbm_10m, else any other TPU-engine
    config that measured.  The CPU reference is a comparison point, NEVER
    the headline — an all-TPU-failed run must read as 0, not as the CPU
    throughput."""
    return next((detail[k] for k in ("gbm", "gbm_10m")
                 if _measured(detail.get(k))),
                next((v for k, v in detail.items()
                      if not k.startswith("cpu_reference")
                      and _measured(v)), {}))


def _ratio(detail, num, den, key):
    if _measured(detail.get(num)) and _measured(detail.get(den)) and \
            detail[den]["value"]:
        detail[key] = round(detail[num]["value"] / detail[den]["value"], 3)


def headline_payload(detail):
    """The headline pick plus the external CPU-reference ratios."""
    _ratio(detail, "gbm", "cpu_reference", "vs_cpu_reference")
    _ratio(detail, "gbm_10m", "cpu_reference_10m", "vs_cpu_reference_10m")
    head = _pick_headline(detail)
    return {
        "metric": "gbm_higgs_like_train_throughput_steady",
        "value": head.get("value", 0.0),
        "unit": head.get("unit", "rows*trees/sec"),
        "device": head.get("device"),
        "detail": detail,
    }


def _main_ladder(detail):
    """Run the configured rungs into ``detail``; returns the names of
    the rungs that raised (later rungs still run)."""
    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    cols = int(os.environ.get("BENCH_COLS", 28))
    trees = int(os.environ.get("BENCH_TREES", 20))
    depth = int(os.environ.get("BENCH_DEPTH", 5))
    # coldstart is not in the default list: its children need the chip,
    # so it can only run while this process has not touched JAX — ask
    # for it by name and it runs first
    configs = os.environ.get(
        "BENCH_CONFIG",
        "gbm,gbm_ua,gbm_bf16,drf,glm,dl,hist,rapidsgb,rapidspipe,"
        "scaleout,multichip,gbm10m,"
        "cpuref,cpuref10m,deep,streamref,leverab,elastic,"
        "auditovh,binspack,statspack,tierhbm,servesus,automl,mtsoak"
    ).split(",")

    detail.update({"rows": rows, "cols": cols})
    failed = []

    def rung(name, fn, device=None):
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — one failed rung must not
            # lose the others' measurements; main() exits non-zero
            traceback.print_exc()
            out = {"error": repr(e)}
            failed.append(name)
        if device is not None:
            out.setdefault("device", device)
        detail[name] = out

    if "coldstart" in configs:
        rung("cold_start", bench_cold_start)
    device = _device()
    detail["device"] = device

    # built on first use: a ladder of child-only rungs never lands a
    # frame on the chip
    @functools.cache
    def data():
        return _make_data(rows, cols)

    @functools.cache
    def frame():
        return _frame(*data())

    runs = [("gbm", lambda: bench_gbm(frame(), rows, trees, depth)),
            ("cpuref", lambda: bench_cpu_reference(*data(), rows, trees,
                                                   depth)),
            ("gbm_ua", lambda: bench_gbm(
                frame(), rows, trees, depth,
                histogram_type="UniformAdaptive")),
            ("gbm_bf16", lambda: bench_gbm(frame(), rows, trees, depth,
                                           bf16=True)),
            ("drf", lambda: bench_drf(frame(), rows, trees, depth)),
            ("glm", lambda: bench_glm(frame(), rows)),
            ("dl", lambda: bench_dl(frame(), rows)),
            ("hist", lambda: bench_hist_mfu(rows, cols)),
            ("rapidsgb", lambda: bench_rapids_groupby(
                min(rows, int(os.environ.get("BENCH_RAPIDS_GB_ROWS",
                                             1_000_000))))),
            ("rapidspipe", lambda: bench_rapids_pipeline(
                min(rows, int(os.environ.get("BENCH_RAPIDS_PIPE_ROWS",
                                             500_000))))),
            ("scaleout", bench_rapids_scaleout),
            ("multichip", bench_dryrun_multichip),
            ("gbm10m", lambda: bench_gbm10m(cols, depth)),
            ("cpuref10m", lambda: bench_cpu_reference_10m(cols, depth)),
            ("deep", lambda: bench_deep(frame(), rows)),
            ("streamref", bench_streaming_refresh),
            ("leverab", bench_lever_ab),
            ("elastic", bench_elastic_resume),
            ("auditovh", bench_audit_overhead),
            ("binspack", lambda: bench_bins_pack(frame(), rows, depth)),
            ("statspack", lambda: bench_stats_pack(frame(), rows, depth)),
            ("tierhbm", lambda: bench_ingest_bigger_than_hbm(
                min(rows, int(os.environ.get("BENCH_TIER_ROWS",
                                             rows))), cols, depth)),
            ("servesus", bench_serving_sustained),
            ("automl", bench_automl_e2e),
            ("mtsoak", bench_multitenant_soak)]
    names = {"hist": "hist_kernel", "gbm10m": "gbm_10m",
             "cpuref": "cpu_reference", "deep": "drf_deep20",
             "gbm_ua": "gbm_uniform_adaptive", "gbm_bf16": "gbm_bf16",
             "cpuref10m": "cpu_reference_10m",
             "rapidsgb": "rapids_groupby_throughput",
             "rapidspipe": "rapids_pipeline",
             "scaleout": "rapids_scaleout",
             "multichip": "dryrun_multichip",
             "streamref": "streaming_refresh",
             "leverab": "lever_ab",
             "elastic": "elastic_resume",
             "auditovh": "audit_overhead",
             "binspack": "bins_pack",
             "statspack": "stats_pack",
             "tierhbm": "ingest_bigger_than_hbm",
             "servesus": "serving_sustained",
             "automl": "automl_e2e",
             "mtsoak": "multitenant_soak"}
    # the host-only reference rungs run on this machine's CPU cores
    host = {"platform": "cpu", "kind": "host (sklearn reference)",
            "count": os.cpu_count()}
    for cfg, fn in runs:
        if cfg in configs:
            rung(names.get(cfg, cfg), fn,
                 host if cfg.startswith("cpuref") else device)
    return failed


if __name__ == "__main__":
    sys.exit(main())
