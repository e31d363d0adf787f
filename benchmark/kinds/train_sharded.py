"""Traffic kind ``train_sharded``: ``train_mixed``'s window on a frame
whose rows are sharded over every chip of the cell, as an H2O cloud of
that many nodes holds it.

One ``<builder>(**params).train(y=..., training_frame=...)`` of fixed
work on the host clock, sized, warmed and traced as ``train_mixed`` sets
out (its helpers and ``train_budgeted``'s are imported, nothing of them
is edited).  What differs:

* the cloud is booted over the cell's ``chips``, so every column lands
  row-sharded over the mesh, each shard straight on its chip
  (``core/landing.py``); ``shapes`` carries ``chips``;
* the data comes from the configuration's generator, which may be
  ``benchmark/data_airline_blocked.py`` (the airline population, rows
  made in seeded blocks on the host's cores);
* ``correct`` is decided by ``benchmark/reference/gbm_mixed_blocked.py``
  (``GbmMixedReference`` in row blocks over the host's processes), which
  also compares the program's split points with the exact order
  statistics of its own rank rule (``split_point_gap``);
* a program that cannot compute split points where the rows live (no
  ``shared_tree.quantile_split_points``: its sort would gather every
  column onto every chip) cannot run the configuration as written and is
  refused at once, before any data is made.

Traffic file parameters: as ``train_budgeted``'s, and ``processes``
(the reference's worker processes, 0 = one a core).
"""

from __future__ import annotations

import contextlib
import hashlib
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchmark import harness, trace as trace_mod
from benchmark.data_airline import GENERATORS as AIRLINE
from benchmark.data_airline import RESPONSE
from benchmark.data_airline_blocked import GENERATORS as BLOCKED
from benchmark.kinds.train_budgeted import (_TraceSlice, builder_class,
                                            planned_trees)
from benchmark.kinds.train_mixed import land, program_trees, spec_of
from benchmark.reference.gbm_mixed_blocked import GbmMixedBlockedReference

GENERATORS = {**AIRLINE, **BLOCKED}


_OUT = ("split_points", "nbins", "col_nbins", "is_cat", "split_col",
        "value", "bitset", "f0", "scoring_history", "ntrees_actual")


def model_numbers(out) -> Dict[str, Any]:
    """What ``compare`` reads of a trained model's output: the forest,
    its scoring history, and the rows the training metric counted (its
    ``nobs``, and the weight of its score table: the confusion matrix's
    four cells)."""
    tm = out["training_metrics"]
    return {**{k: out[k] for k in _OUT},
            "metric_rows": (float(tm.get("nobs")),
                            float(sum(tm.get("cm").values())))}


def compare(config, traffic, data, model_out, ntrees_planned: int
            ) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every number compared,
    beside its limit (``train_mixed.compare``'s, by the blocked
    reference, with ``split_point_gap``; and ``metric_rows_gap``, the
    training metric's rows against the frame's, which a shard left out
    of the metric kernel's sums or table moves by a quarter)."""
    limits = traffic["limits"]
    k = int(traffic["check_trees"])
    built = int(model_out["ntrees_actual"])
    rows = len(data.y)
    compared = {"trees_missing": (ntrees_planned - built,
                                  limits["trees_missing"]),
                "metric_rows_gap": (max(abs(r - rows) for r in
                                        model_out["metric_rows"]) / rows,
                                    limits["metric_rows_gap"])}
    with GbmMixedBlockedReference(
            data.cols, data.card, data.y, spec_of(config),
            processes=int(traffic.get("processes", 0))) as ref:
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
    from h2o_tpu.models.tree import shared_tree
    if not hasattr(shared_tree, "quantile_split_points"):
        raise harness.Refused(
            "this program sorts every column whole to find its split "
            "points: on a row-sharded frame that gathers the frame onto "
            "every chip")
    chips = int(job.cell["chips"])
    clocks: Dict[str, float] = {}
    t = time.monotonic()
    rows = int(config["rows"])
    data = GENERATORS[config["data"]](rows, job.seed)
    clocks["data_s"] = time.monotonic() - t

    t = time.monotonic()
    from h2o_tpu.core.diag import DispatchStats
    h2o_tpu.Cloud.boot(nodes=chips)
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
    model_out = model_numbers(out)
    padded_rows = int(frame.padded_rows)
    del model, builder, out, frame

    programs = DispatchStats.programs()
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
        # programs made ready in this run, and those whose compiled
        # module holds a collective no h2o.coll. scope owns
        "programs": len(programs),
        "gspmd_collectives": {p["fun"]: p["gspmd_collectives"]
                              for p in programs
                              if p.get("gspmd_collectives")}}
    tr = None
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, data, model_out, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    spec = spec_of(config)
    shapes = {"rows": rows, "cols": len(data.names),
              "nbins": int(model_out["nbins"]), "max_depth": spec.max_depth,
              "fine_nbins": 0, "chips": chips,
              "rows_per_chip": padded_rows // chips}
    return {
        "end_to_end": {"setup_s": clocks["setup_s"],
                       "train_rate": rows * built / clocks["window_s"]},
        "clocks": clocks, "counters": counters, "shapes": shapes,
        "device_kind": job.device.get("kind"), "trace": tr,
        "memory_peak_bytes": peak, "notes": notes,
        "attempted": ntrees, "failed": ntrees - built,
        "compared": verdict["compared"], "correct": verdict["correct"]}
