"""Traffic kind ``train_mixed``: ``train_budgeted``'s window on a frame of
mixed column types: numeric columns (some with missing values) beside
enum columns that carry a host domain.

One ``<builder>(**params).train(y=..., training_frame=...)`` of fixed
work on the host clock, sized, guarded and traced as ``train_budgeted``
sets out (its helpers are imported, nothing of it is edited).  What
differs:

* the data comes from ``benchmark/data_airline.py`` and lands as ``T_NUM``
  and ``T_CAT`` ``Vec``s under the source's column names, the enum
  response included;
* ``correct`` is decided by ``benchmark/reference/gbm_mixed.py``: enum
  nodes searched over the ordered prefixes of their levels, missing
  values on the side the node says, the program's own routing held
  through the per-tree log-loss;
* set-up warms no whole-forest scoring program: since PR 27 ``train()``
  runs none;
* a program that would bin numeric columns on the widest enum's grid
  (it states no ``col_nbins``) cannot run the configuration as written
  and is refused at once, before any data is made.

Traffic file parameters: as ``train_budgeted``'s.
"""

from __future__ import annotations

import contextlib
import hashlib
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchmark import harness, trace as trace_mod
from benchmark.data_airline import GENERATORS, RESPONSE, RESPONSE_DOMAIN
from benchmark.kinds.train_budgeted import (_TraceSlice, builder_class,
                                            planned_trees)
from benchmark.reference.gbm_mixed import (GbmMixedReference, Spec,
                                           trees_from_artifact)


def land(data):
    """Host columns -> a Frame on the device, ending in
    ``block_until_ready``: float columns as numeric ``Vec``s, code
    columns as enum ``Vec``s with their domains."""
    import jax
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    vecs = [Vec(c, T_CAT, domain=list(data.domains[n]))
            if n in data.domains else Vec(c)
            for n, c in zip(data.names, data.cols)]
    vecs.append(Vec(data.y, T_CAT, domain=list(RESPONSE_DOMAIN)))
    fr = Frame(list(data.names) + [RESPONSE], vecs)
    jax.block_until_ready([v.data for v in fr.vecs])
    return fr


def spec_of(config: Dict[str, Any]) -> Spec:
    p = config["params"]
    return Spec(max_depth=int(p["max_depth"]), nbins=int(p["nbins"]),
                nbins_cats=int(p["nbins_cats"]),
                learn_rate=float(p["learn_rate"]),
                min_rows=float(p["min_rows"]),
                min_split_improvement=float(p["min_split_improvement"]))


def program_trees(out: Dict[str, Any], k: int):
    """The model artifact's first ``k`` trees in the reference's terms."""
    return trees_from_artifact(
        np.asarray(out["split_col"])[:k, 0], np.asarray(out["bitset"])[:k, 0],
        np.asarray(out["value"])[:k, 0], out["split_points"], out["is_cat"],
        out["col_nbins"])


def compare(config, traffic, data, model_out, ntrees_planned: int,
            threads: int = 4) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every number compared,
    beside its limit."""
    limits = traffic["limits"]
    k = int(traffic["check_trees"])
    built = int(model_out["ntrees_actual"])
    compared = {"trees_missing": (ntrees_planned - built,
                                  limits["trees_missing"])}
    ref = GbmMixedReference(data.cols, data.card, data.y, spec_of(config),
                            threads=threads)
    nums = ref.prepare(np.asarray(model_out["split_points"]))
    history = {int(r["number_of_trees"]): float(r["training_logloss"])
               for r in model_out["scoring_history"]
               if int(r["number_of_trees"]) <= k}
    nums.update(ref.check_forest(
        program_trees(model_out, min(k, built)),
        float(np.asarray(model_out["f0"])[0]), history,
        int(traffic["search_trees"])))
    want_points = len([n for n in range(1, k + 1)
                       if n % int(traffic["score_tree_interval"]) == 0])
    compared["logloss_points_missing"] = (
        want_points - nums.pop("logloss_points"), 0)
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


def run(job: harness.Job) -> Dict[str, Any]:
    config, traffic = job.config, job.traffic
    import h2o_tpu
    from h2o_tpu.models.tree.shared_tree import BinnedData
    if "col_nbins" not in BinnedData._fields:
        raise harness.Refused(
            "this program bins numeric columns on the widest enum "
            "column's grid, not on the nbins the configuration states")
    clocks: Dict[str, float] = {}
    t = time.monotonic()
    rows = int(config["rows"])
    data = GENERATORS[config["data"]](rows, job.seed)
    clocks["data_s"] = time.monotonic() - t

    t = time.monotonic()
    from h2o_tpu.core.diag import DispatchStats
    h2o_tpu.Cloud.boot(nodes=int(job.cell["chips"]))
    DispatchStats.install_xla_listener()
    clocks["boot_s"] = time.monotonic() - t

    t = time.monotonic()
    frame = land(data)
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
                          )).train(y=RESPONSE, training_frame=frame)
    clocks["first_train_s"] = time.monotonic() - t
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
        model = builder.train(y=RESPONSE, training_frame=frame)
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
        "split_points", "nbins", "col_nbins", "is_cat", "split_col",
        "value", "bitset", "f0", "scoring_history", "ntrees_actual")}
    # free the program's state before the reference runs
    del model, builder, out, frame

    tr = None
    digest = hashlib.sha1()
    for k in ("split_col", "bitset", "value"):
        digest.update(np.ascontiguousarray(model_out[k]).tobytes())
    sc = np.asarray(model_out["split_col"])
    notes: Dict[str, Any] = {
        "clocks": clocks, "trees_planned": ntrees, "trees_built": built,
        "final_training_logloss": final_ll,
        "forest_sha1": digest.hexdigest(),
        "split_nodes": int((sc >= 0).sum()),
        "enum_split_nodes": int(np.asarray(model_out["is_cat"])[
            sc[sc >= 0]].sum()),
        "col_nbins": [int(b) for b in model_out["col_nbins"]]}
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            # [kind, self seconds, events, distinct ops] of the slice
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, data, model_out, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    spec = spec_of(config)
    # nbins: the one table's width, which sizes the narrowest bin index
    shapes = {"rows": rows, "cols": len(data.names),
              "nbins": int(model_out["nbins"]), "max_depth": spec.max_depth,
              "fine_nbins": 0, "chips": int(job.cell["chips"])}
    return {
        "end_to_end": {"setup_s": clocks["setup_s"],
                       "train_rate": rows * built / clocks["window_s"]},
        "clocks": clocks, "counters": counters, "shapes": shapes,
        "device_kind": job.device.get("kind"), "trace": tr,
        "memory_peak_bytes": peak, "notes": notes,
        "attempted": ntrees, "failed": ntrees - built,
        "compared": verdict["compared"], "correct": verdict["correct"]}
