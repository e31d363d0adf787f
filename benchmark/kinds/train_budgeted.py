"""Traffic kind ``train_budgeted``: one boosted-forest training job on a
landed frame, sized to last about ``--seconds``.

Set-up makes the configuration's data from ``--seed``, lands it as a
``Frame``, and runs one short warm-up ``train()`` of the cell's exact
shapes and block size on the window's own path, then scores once at the
window's tree count, so that every program the window needs is compiled
or loaded and nothing else is.  The window is ONE call of the entry that
users drive, ``<builder>(**params).train(y=..., training_frame=...)``
(what ``POST /3/ModelBuilders/<algo>`` invokes), on the host clock; it
ends with the model's arrays on the host.

The job's size is fixed work, not a clock: ``ntrees = block * round(
seconds * trees_per_second / block)``, with ``trees_per_second`` found
once on the chip and written in the traffic file, as a serving cell's
offered rate is.  ``max_runtime_secs`` is set as well (at
``runtime_guard`` times ``--seconds``), because it is a real parameter
that AutoML sets on every model and it changes the program's path:
speculative block launches do not donate their carry, and the
incremental scorer is on.  See PERF.md for why the clock does not end
the job.

Traffic file parameters:
  score_tree_interval  trees per block (the program scores per block)
  trees_per_second     the fixed offered size, trees per second of window
  warm_blocks          blocks the warm-up train builds
  runtime_guard        max_runtime_secs = runtime_guard * --seconds
  check_trees          first trees that the reference follows
  search_trees         of those, trees whose every split is searched
  trace_start_s, trace_seconds   the slice of the window that is traced
  limits               name -> limit of each number compared
"""

from __future__ import annotations

import contextlib
import copy
import hashlib
import importlib
import shutil
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, trace as trace_mod
from benchmark.data import GENERATORS
from benchmark.reference.gbm import GbmReference, Spec, Tree


def planned_trees(traffic: Dict[str, Any], seconds: float) -> int:
    block = int(traffic["score_tree_interval"])
    want = seconds * float(traffic["trees_per_second"])
    least = max(int(traffic["check_trees"]), block)
    n = block * max(1, round(want / block))
    while n < least:
        n += block
    return n


def builder_class(config: Dict[str, Any]):
    mod, _, cls = config["builder"].partition(":")
    return getattr(importlib.import_module(mod), cls)


def land(config, X, y):
    """Host columns -> a Frame on the device, ending in
    ``block_until_ready``."""
    import jax
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    names = [f"x{j}" for j in range(X.shape[0])] + ["y"]
    vecs = [Vec(X[j]) for j in range(X.shape[0])]
    vecs.append(Vec(y, T_CAT, domain=list(config["response_domain"])))
    fr = Frame(names, vecs)
    jax.block_until_ready([v.data for v in fr.vecs])
    return fr


def warm_final_scoring(model, frame, ntrees: int) -> None:
    """``train()`` ends by scoring the whole forest on the training
    frame, a program whose shape holds the tree count.  Warm it at the
    window's count with the warm-up model's own trees repeated: every
    array of the model's output whose first axis counts its trees is
    tiled to ``ntrees``."""
    have = int(model.output["ntrees_actual"])
    wide = copy.copy(model)
    out = dict(model.output)
    reps = -(-ntrees // have)
    for k, v in model.output.items():
        if isinstance(v, np.ndarray) and v.ndim >= 1 and v.shape[0] == have:
            out[k] = np.concatenate([v] * reps)[:ntrees]
    out["ntrees_actual"] = ntrees
    wide.output = out
    wide.model_metrics(frame)


def program_trees(out: Dict[str, Any], k: int) -> List[Tree]:
    """The model artifact's first ``k`` trees in the reference's terms:
    a raw threshold per split node (a row goes left iff x < thr)."""
    sp = np.asarray(out["split_points"])
    B = int(out["nbins"])
    trees = []
    for t in range(k):
        col = np.asarray(out["split_col"][t, 0]).astype(np.int64)
        val = np.asarray(out["value"][t, 0]).astype(np.float64)
        thr_bin = np.asarray(out["thr_bin"][t, 0]).astype(np.int64)
        # numeric splits are prefix bitsets over the bins: the last bin
        # that goes left is the threshold's index
        last_left = np.asarray(out["bitset"][t, 0])[:, :B].sum(axis=1) - 1
        idx = np.where(thr_bin >= 0, thr_bin - 1, last_left)
        idx = np.clip(idx, 0, sp.shape[1] - 1)
        thr = np.where(col >= 0, sp[np.maximum(col, 0), idx], np.nan)
        trees.append(Tree(col, thr.astype(np.float32), val))
    return trees


def spec_of(config: Dict[str, Any]) -> Spec:
    p = config["params"]
    ht = p.get("histogram_type", "AUTO")
    return Spec(max_depth=int(p["max_depth"]), nbins=int(p["nbins"]),
                learn_rate=float(p["learn_rate"]),
                min_rows=float(p["min_rows"]),
                min_split_improvement=float(
                    p.get("min_split_improvement", 1e-5)),
                histogram_type="UniformAdaptive" if ht == "AUTO" else ht,
                nbins_top_level=int(p.get("nbins_top_level", 1024)))


def compare(config, traffic, X, y, model_out, ntrees_planned: int,
            threads: int = 4) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every number compared,
    beside its limit."""
    limits = traffic["limits"]
    k = int(traffic["check_trees"])
    built = int(model_out["ntrees_actual"])
    compared = {"trees_missing": (ntrees_planned - built,
                                  limits["trees_missing"])}
    ref = GbmReference(X, y, spec_of(config), threads=threads)
    compared_gap = ref.prepare(np.asarray(model_out["split_points"]))
    history = {int(r["number_of_trees"]): float(r["training_logloss"])
               for r in model_out["scoring_history"]
               if int(r["number_of_trees"]) <= k}
    nums = ref.check_forest(program_trees(model_out, min(k, built)),
                            float(np.asarray(model_out["f0"])[0]),
                            history, int(traffic["search_trees"]))
    nums.update(compared_gap)
    points = nums.pop("logloss_points")
    want_points = len([n for n in range(1, k + 1)
                       if n % int(traffic["score_tree_interval"]) == 0])
    compared["logloss_points_missing"] = (want_points - points, 0)
    # a number with no limit in the traffic file is read, not compared
    # (PERF.md says why it separates nothing)
    read_only = {}
    for name, value in nums.items():
        if name in limits:
            compared[name] = (value, limits[name])
        else:
            read_only[name] = value
    ok = all(np.isfinite(v) and v <= lim for v, lim in compared.values())
    return {"compared": compared, "correct": bool(ok),
            "read_only": read_only}


class _TraceSlice:
    """Start the profiler ``start_s`` into the window and stop it
    ``seconds`` later, from a thread of its own: the window is one
    blocking call."""

    def __init__(self, logdir, start_s: float, seconds: float):
        self.logdir, self.start_s, self.seconds = logdir, start_s, seconds
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.error = None

    def _run(self):
        import jax
        if self._stop.wait(self.start_s):
            return
        try:
            jax.profiler.start_trace(str(self.logdir))
            self._stop.wait(self.seconds)
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported in the notes
            self.error = repr(e)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=120)


def run(job: harness.Job) -> Dict[str, Any]:
    config, traffic = job.config, job.traffic
    clocks: Dict[str, float] = {}
    t = time.monotonic()
    rows, cols = int(config["rows"]), int(config["cols"])
    X, y = GENERATORS[config["data"]](rows, cols, job.seed)
    clocks["data_s"] = time.monotonic() - t

    t = time.monotonic()
    import h2o_tpu
    from h2o_tpu.core.diag import DispatchStats
    h2o_tpu.Cloud.boot(nodes=int(job.cell["chips"]))
    DispatchStats.install_xla_listener()
    clocks["boot_s"] = time.monotonic() - t

    t = time.monotonic()
    frame = land(config, X, y)
    clocks["landing_s"] = time.monotonic() - t

    Builder = builder_class(config)
    block = int(traffic["score_tree_interval"])
    ntrees = planned_trees(traffic, job.seconds)
    guard = float(traffic["runtime_guard"]) * job.seconds
    params = dict(config["params"])
    params.update(score_tree_interval=block, max_runtime_secs=guard,
                  seed=job.seed)

    t = time.monotonic()
    warm = Builder(**dict(params, ntrees=block * int(traffic["warm_blocks"])
                          )).train(y="y", training_frame=frame)
    clocks["first_train_s"] = time.monotonic() - t
    t = time.monotonic()
    warm_final_scoring(warm, frame, ntrees)
    clocks["warm_score_s"] = time.monotonic() - t
    del warm
    clocks["setup_s"] = time.monotonic() - job.t_start

    # ---- the window ----
    compiles0 = DispatchStats.xla_compiles()
    disp0 = sum(DispatchStats.snapshot()["dispatches"].values())
    # one trace kept per cell: the newest
    logdir = job.out_dir / f"trace-{job.cell['name']}"
    slicer = contextlib.nullcontext()
    if job.trace:
        shutil.rmtree(logdir, ignore_errors=True)
        logdir.mkdir(parents=True, exist_ok=True)
        slicer = _TraceSlice(logdir, float(traffic["trace_start_s"]),
                             float(traffic["trace_seconds"]))
    builder = Builder(**dict(params, ntrees=ntrees))
    t0 = time.monotonic()
    with slicer:
        model = builder.train(y="y", training_frame=frame)
    out = model.output
    built = int(np.asarray(out["split_col"]).shape[0])
    clocks["window_s"] = time.monotonic() - t0
    # ---- closed ----
    counters = {
        "window_compiles": DispatchStats.xla_compiles() - compiles0,
        "dispatches": sum(DispatchStats.snapshot()["dispatches"].values())
        - disp0,
        "trees": built, "rows": rows}
    peak = harness.memory_peak_bytes()
    final_ll = float(out["training_metrics"].get("logloss"))
    model_out = {k: out[k] for k in (
        "split_points", "nbins", "split_col", "value", "thr_bin", "bitset",
        "f0", "scoring_history", "ntrees_actual")}
    # free the program's state before the reference runs
    del model, builder, out, frame

    tr = None
    digest = hashlib.sha1()
    for k in ("split_col", "thr_bin", "value"):
        digest.update(np.ascontiguousarray(model_out[k]).tobytes())
    notes: Dict[str, Any] = {"clocks": clocks, "trees_planned": ntrees,
                             "trees_built": built,
                             "final_training_logloss": final_ll,
                             "forest_sha1": digest.hexdigest()}
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            # [kind, self seconds, events, distinct ops] of the slice
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, X, y, model_out, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    spec = spec_of(config)
    adaptive = spec.histogram_type != "QuantilesGlobal"
    shapes = {"rows": rows, "cols": cols, "nbins": spec.nbins,
              "max_depth": spec.max_depth,
              "fine_nbins": max(spec.nbins_top_level, spec.nbins)
              if adaptive else 0,
              "chips": int(job.cell["chips"])}
    return {
        "end_to_end": {"setup_s": clocks["setup_s"],
                       "train_rate": rows * built / clocks["window_s"]},
        "clocks": clocks, "counters": counters, "shapes": shapes,
        "device_kind": job.device.get("kind"), "trace": tr,
        "memory_peak_bytes": peak, "notes": notes,
        "attempted": ntrees, "failed": ntrees - built,
        "compared": verdict["compared"], "correct": verdict["correct"]}
