"""Traffic kind ``train_cv``: ONE cross-validated training job on a landed
frame: ``<builder>(nfolds=K, ...).train(y=..., training_frame=...)``,
the entry the REST handler and AutoML call.  The job is K fold models,
each trained with its fold at weight 0 and scored on it, their holdout
predictions combined into one frame and scored once
(``cross_validation_metrics``), the per-fold summary, then the main
model on all rows.

Sized, guarded and traced as ``train_budgeted`` sets out (its helpers are
imported, nothing of them is edited); every model of the job builds
``planned_trees`` trees in blocks of ``score_tree_interval``.  What
differs:

* set-up's warm-up is one whole cross-validated ``train()`` (``warm_blocks``
  blocks a model), so that every program of the window is loaded: the
  fold-weight build, the metric kernels under weights, the select of the
  combined predictions;
* the window is the second ``train()`` whole, from the call to the model
  with its cross-validation outputs in hand;
* ``train_rate`` counts, over the K + 1 models, the rows with non-zero
  weight x trees built, over the window's seconds;
* ``correct`` is decided by ``benchmark/reference/gbm_cv.py``: the fold
  ids, every model's root cover, one fold model (fold ``seed % K``)
  followed as ``reference/gbm.py`` follows a model, every holdout
  prediction against rows the reference routes itself, the holdout
  log-loss of every scoring point, ``cross_validation_metrics``, the
  summary, and the main model's training log-loss;
* a program with no ``ModelBuilder._cv_shared`` bins the frame eleven
  times for this job and cannot end it inside a run: it is refused at
  once, before any data is made.

Traffic file parameters: as ``train_budgeted``'s.
"""

from __future__ import annotations

import contextlib
import hashlib
import shutil
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, spans, trace as trace_mod
from benchmark.data import GENERATORS
from benchmark.kinds.train_budgeted import (_TraceSlice, builder_class,
                                            land, planned_trees,
                                            program_trees, spec_of)
from benchmark.reference.gbm_cv import FoldAnswers, GbmCvReference

# the numbers that are counts: compared exactly
_EXACT = ("fold_gap", "root_cover_gap", "trees_missing",
          "holdout_points_missing", "holdout_rows_missing")
_ARTIFACT = ("split_points", "nbins", "split_col", "value", "thr_bin",
             "bitset", "f0", "scoring_history", "ntrees_actual", "node_w")


def answers_of(out: Dict[str, Any], planned: int) -> FoldAnswers:
    """One model's artifact in the reference's terms."""
    built = int(out["ntrees_actual"])

    def history(key):
        return {int(r["number_of_trees"]): float(r[key])
                for r in out["scoring_history"] if key in r}

    return FoldAnswers(
        trees=program_trees(out, built),
        f0=float(np.asarray(out["f0"])[0]),
        root_cover=[float(c) for c in np.asarray(out["node_w"])[:, 0, 0]],
        train_history=history("training_logloss"),
        holdout_history=history("validation_logloss"), planned=planned)


def compare(config, traffic, X, y, job_out: Dict[str, Any], seed: int,
            ntrees_planned: int, threads: int = 4) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every number compared,
    beside its limit.  ``job_out``: ``fold_models`` and ``main`` (model
    outputs), ``fold_assignment``, ``holdout_p1``, ``cv_logloss``,
    ``cv_auc``, ``fold_loglosses``."""
    limits = traffic["limits"]
    nfolds = int(config["params"]["nfolds"])
    ref = GbmCvReference(X, y, spec_of(config), nfolds, threads=threads)
    main = answers_of(job_out["main"], ntrees_planned)
    nums = ref.prepare(np.asarray(job_out["main"]["split_points"]))
    nums.update(ref.check_job(
        [answers_of(o, ntrees_planned) for o in job_out["fold_models"]],
        main, job_out["fold_assignment"], job_out["holdout_p1"],
        job_out["cv_logloss"], job_out["cv_auc"],
        job_out["fold_loglosses"], followed=seed % nfolds,
        search_trees=int(traffic["search_trees"])))
    compared, read_only = {}, {}
    for name, value in nums.items():
        if name in _EXACT:
            compared[name] = (value, 0)
        elif name in limits:
            compared[name] = (value, limits[name])
        else:
            # read, not compared (PERF.md says why it separates nothing)
            read_only[name] = value
    ok = all(np.isfinite(v) and v <= lim for v, lim in compared.values())
    return {"compared": compared, "correct": bool(ok),
            "read_only": read_only}


def job_outputs(model, rows: int) -> Dict[str, Any]:
    """What the comparison reads of a finished job, on the host."""
    from h2o_tpu.core.cloud import cloud
    out, dkv = model.output, cloud().dkv

    def artifact(o):
        return {k: o[k] for k in _ARTIFACT}

    pf = dkv.get(out["cross_validation_holdout_predictions_frame_id"])
    ff = dkv.get(out["cross_validation_fold_assignment_frame_id"])
    return {
        "main": artifact(out),
        "fold_models": [artifact(dkv.get(k).output)
                        for k in out["cross_validation_models"]],
        "fold_assignment": ff.vecs[0].to_numpy()[:rows].astype(np.int64),
        # the last column: P(the response's second level)
        "holdout_p1": np.asarray(pf.vecs[-1].to_numpy()[:rows], np.float64),
        "cv_logloss": float(out["cross_validation_metrics"]["logloss"]),
        "cv_auc": float(out["cross_validation_metrics"]["AUC"]),
        "fold_loglosses": list(out["cross_validation_metrics_summary"]
                               ["logloss"]["values"])}


def drop_job_keys(model) -> None:
    """Take a finished job's frames and fold models out of the store, so
    that the next job finds the device as a first one would."""
    from h2o_tpu.core.cloud import cloud
    out, dkv = model.output, cloud().dkv
    for k in [out.get("cross_validation_holdout_predictions_frame_id"),
              out.get("cross_validation_fold_assignment_frame_id"),
              *out.get("cross_validation_models", ()), str(model.key)]:
        if k:
            dkv.remove(k)


def row_trees(job_out: Dict[str, Any], rows: int) -> int:
    """Rows with non-zero weight x trees built, over every model."""
    fold = job_out["fold_assignment"]
    total = rows * int(job_out["main"]["ntrees_actual"])
    for i, o in enumerate(job_out["fold_models"]):
        total += (rows - int(np.sum(fold == i))) * int(o["ntrees_actual"])
    return total


def forest_sha1(models: List[Dict[str, Any]]) -> str:
    digest = hashlib.sha1()
    for o in models:
        for k in ("split_col", "thr_bin", "value"):
            digest.update(np.ascontiguousarray(o[k]).tobytes())
    return digest.hexdigest()


def run(job: harness.Job) -> Dict[str, Any]:
    config, traffic = job.config, job.traffic
    import h2o_tpu
    from h2o_tpu.models.model import ModelBuilder
    if not hasattr(ModelBuilder, "_cv_shared"):
        raise harness.Refused(
            "this program trains a cross-validated job as K + 1 jobs, each "
            "binning a copy of the frame and scoring it whole again: it "
            "cannot end the configuration's job inside a run")
    clocks: Dict[str, float] = {}
    t = time.monotonic()
    rows, cols = int(config["rows"]), int(config["cols"])
    X, y = GENERATORS[config["data"]](rows, cols, job.seed)
    clocks["data_s"] = time.monotonic() - t

    t = time.monotonic()
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
    nmodels = int(params["nfolds"]) + 1

    t = time.monotonic()
    warm = Builder(**dict(params, ntrees=block * int(traffic["warm_blocks"])
                          )).train(y="y", training_frame=frame)
    clocks["first_train_s"] = time.monotonic() - t
    drop_job_keys(warm)
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
    clocks["window_s"] = time.monotonic() - t0
    # ---- closed ----
    dispatches = sum(DispatchStats.snapshot()["dispatches"].values()) - disp0
    compiles = DispatchStats.xla_compiles() - compiles0
    peak = harness.memory_peak_bytes()
    window = spans.window_spans()
    t = time.monotonic()
    job_out = job_outputs(model, rows)
    clocks["fetch_s"] = time.monotonic() - t
    final_ll = float(model.output["training_metrics"].get("logloss"))
    # free the program's state before the reference runs
    drop_job_keys(model)
    del model, builder, frame

    models: List[Dict[str, Any]] = job_out["fold_models"] + [job_out["main"]]
    built = sum(int(o["ntrees_actual"]) for o in models)
    work = row_trees(job_out, rows)
    counters = {"window_compiles": compiles, "dispatches": dispatches,
                "trees": built, "rows": rows, "row_trees": work,
                "models": len(models)}
    tr = None
    notes: Dict[str, Any] = {
        "clocks": clocks, "trees_planned": ntrees * nmodels,
        "trees_built": built, "fold_models": len(job_out["fold_models"]),
        "row_trees": work, "final_training_logloss": final_ll,
        "cv_logloss": job_out["cv_logloss"], "cv_auc": job_out["cv_auc"],
        "forest_sha1": forest_sha1(models),
        # the main model alone: the one-model cell's forest on this seed
        "main_forest_sha1": forest_sha1([job_out["main"]]),
        # [span, start ms, host ms] of the window's job, the spans of
        # the orchestration (a plain run has no trace to say)
        "window_spans_ms": [
            [e["what"], round((e["ns"] - window[0]["ns"]) / 1e6, 1),
             round(e["dur_ns"] / 1e6, 1)]
            for e in sorted(window, key=lambda e: e["ns"])
            if e["kind"] == "train" and (
                e["what"].startswith("cv.") or e["what"] in (
                    "bin", "final_metrics"))] if window else []}
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            # [kind, self seconds, events, distinct ops] of the slice
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, X, y, job_out, job.seed, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    spec = spec_of(config)
    # rows: the mean a tree of the job trains on, so that the work of
    # ``trees`` trees of ``rows`` rows is the job's (benchmark/work.py is
    # linear in the rows); frame_rows: the frame's
    shapes = {"rows": work / built if built else rows, "frame_rows": rows,
              "cols": cols, "nbins": spec.nbins,
              "max_depth": spec.max_depth, "fine_nbins": 0,
              "chips": int(job.cell["chips"])}
    attempted = ntrees * nmodels
    return {
        "end_to_end": {"setup_s": clocks["setup_s"],
                       "train_rate": work / clocks["window_s"]},
        "clocks": clocks, "counters": counters, "shapes": shapes,
        "device_kind": job.device.get("kind"), "trace": tr,
        "memory_peak_bytes": peak, "notes": notes,
        "attempted": attempted, "failed": attempted - built,
        "compared": verdict["compared"], "correct": verdict["correct"]}
