"""Traffic kind ``train_validated``: ``train_mixed``'s window with a
SECOND frame: the job is ``<builder>(**params).train(y=...,
training_frame=train, validation_frame=valid)``, the two frames landed
from two files of one split, each with the enum domains of its own file
(``benchmark/data_airline_split.py``), the forest scored on the
validation frame after every tree.

Sized, guarded and traced as ``train_budgeted`` sets out, landed and
judged on the training side as ``train_mixed`` does (their helpers are
imported, nothing of them is edited).  What differs:

* both frames are landed in set-up (``landing_s`` covers both) and the
  warm-up ``train()`` has the validation frame too, so the programs that
  carry its enum codes into the training domains, bin it and descend a
  block's trees over it are compiled before the window;
* ``train_rate`` counts TRAINING rows x trees built over the window's
  seconds: what the second frame costs shows as a lower rate than the
  one-frame cell's;
* ``correct`` is decided by ``benchmark/reference/gbm_valid.py``: every
  number of ``train_mixed`` on the training side, and on the second
  frame the validation log-loss after each tree and at the end of
  ``train()`` against rows the reference maps by level string and
  routes itself, the count of rows with a level training never saw, and
  ``predict`` on a probe cut from the validation file that holds every
  such row;
* a program that scores a second frame by that frame's OWN enum codes
  (it has no ``models/model.adapt_frame``) cannot run the configuration
  as written and is refused at once, before any data is made.

Traffic file parameters: as ``train_budgeted``'s, and ``probe_rows``,
the rows of the probe.
"""

from __future__ import annotations

import contextlib
import hashlib
import shutil
import time
from types import SimpleNamespace
from typing import Any, Dict

import numpy as np

from benchmark import harness, spans, trace as trace_mod
from benchmark.data_airline import RESPONSE
from benchmark.data_airline_split import (GENERATORS, AirlineSplit, Part,
                                          as_frame_columns)
from benchmark.kinds import train_mixed
from benchmark.kinds.train_budgeted import (_TraceSlice, builder_class,
                                            planned_trees)
from benchmark.reference.gbm_valid import GbmValidReference

# the numbers of the second frame that are counts: compared exactly
_EXACT = ("valid_points_missing", "unseen_rows_gap", "unseen_rows_unprobed")


def land(split: AirlineSplit, part: Part):
    """One file of the split -> a Frame on the device, its enum columns
    in the codes and under the domain of THIS file."""
    cols, domains = as_frame_columns(split, part)
    return train_mixed.land(SimpleNamespace(
        names=split.names, cols=cols, domains=domains, y=part.y))


def probe_of(split: AirlineSplit, rows: int) -> np.ndarray:
    """Validation rows for the ``predict`` probe: every row whose level
    the training file lacks in some enum column, then the file's first
    rows, ``rows`` in all (more if the first kind alone is more)."""
    unseen = np.zeros(len(split.valid.y), bool)
    for j, n in enumerate(split.names):
        if n in split.enum:
            ids = split.valid.cols[j]
            unseen |= (ids >= 0) & ~np.isin(ids, split.train.domain_ids(j))
    first = np.flatnonzero(~unseen)[:max(0, rows - int(unseen.sum()))]
    return np.sort(np.concatenate([np.flatnonzero(unseen), first]))


def predict_probe(model, split: AirlineSplit, probe: np.ndarray):
    """P(class 1) of the probe rows by ``model.predict_raw`` on a frame
    cut from the validation file: the file's own domains and codes."""
    import jax
    from h2o_tpu.core.frame import Frame, T_CAT, Vec
    cols, domains = as_frame_columns(split, split.valid)
    vecs = [Vec(c[probe], T_CAT, domain=list(domains[n]))
            if n in domains else Vec(c[probe])
            for n, c in zip(split.names, cols)]
    raw = model.predict_raw(Frame(list(split.names), vecs))
    return np.asarray(jax.device_get(raw))[:len(probe), 2]


def compare(config, traffic, split: AirlineSplit, model_out,
            ntrees_planned: int, threads: int = 4) -> Dict[str, Any]:
    """The comparison that decides ``correct``: ``train_mixed``'s on the
    training side, then the second frame's numbers, each beside its
    limit."""
    limits = traffic["limits"]
    is_enum = [n in split.enum for n in split.names]
    ref = GbmValidReference(split.train.cols, split.valid.cols, is_enum,
                            split.train.y, split.valid.y,
                            train_mixed.spec_of(config), threads=threads)
    # the training side, on the reference's own training codes
    verdict = train_mixed.compare(
        config, traffic, SimpleNamespace(cols=ref.train.cols,
                                         card=ref.card, y=split.train.y),
        model_out, ntrees_planned, threads=threads)
    built = int(model_out["ntrees_actual"])
    history = {int(r["number_of_trees"]): float(r["validation_logloss"])
               for r in model_out["scoring_history"]
               if "validation_logloss" in r}
    nums = ref.check_valid(
        train_mixed.program_trees(model_out, built),
        float(np.asarray(model_out["f0"])[0]), history,
        final=model_out["validation_logloss"],
        program_unseen_rows=model_out["unseen_rows"],
        probe_rows=model_out["probe_rows"],
        probe_p1=model_out["probe_p1"])
    want = len([n for n in range(1, built + 1)
                if n % int(traffic["score_tree_interval"]) == 0])
    nums["valid_points_missing"] = want - nums.pop("valid_logloss_points")
    # a program that states no count has not shown it matches levels
    nums.setdefault("unseen_rows_gap", float("inf"))
    compared, read_only = verdict["compared"], verdict["read_only"]
    for name, value in nums.items():
        if name in _EXACT:
            compared[name] = (value, 0)
        elif name in limits:
            compared[name] = (value, limits[name])
        else:
            read_only[name] = value
    read_only["unseen_rows"] = ref.unseen_rows
    ok = all(np.isfinite(v) and v <= lim for v, lim in compared.values())
    return {"compared": compared, "correct": bool(ok),
            "read_only": read_only}


def run(job: harness.Job) -> Dict[str, Any]:
    config, traffic = job.config, job.traffic
    import h2o_tpu
    from h2o_tpu.models import model as model_mod
    from h2o_tpu.models.tree.shared_tree import BinnedData
    if "col_nbins" not in BinnedData._fields:
        raise harness.Refused(
            "this program bins numeric columns on the widest enum "
            "column's grid, not on the nbins the configuration states")
    if not hasattr(model_mod, "adapt_frame"):
        raise harness.Refused(
            "this program scores a second frame by that frame's own enum "
            "codes, not by its level strings: it cannot run a validation "
            "frame that was parsed on its own")
    clocks: Dict[str, float] = {}
    t = time.monotonic()
    rows, valid_rows = int(config["rows"]), int(config["valid_rows"])
    split = GENERATORS[config["data"]](rows, valid_rows, job.seed)
    clocks["data_s"] = time.monotonic() - t

    t = time.monotonic()
    from h2o_tpu.core.diag import DispatchStats
    h2o_tpu.Cloud.boot(nodes=int(job.cell["chips"]))
    DispatchStats.install_xla_listener()
    clocks["boot_s"] = time.monotonic() - t

    t = time.monotonic()
    frame = land(split, split.train)
    valid = land(split, split.valid)
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
                          )).train(y=RESPONSE, training_frame=frame,
                                   validation_frame=valid)
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
        model = builder.train(y=RESPONSE, training_frame=frame,
                              validation_frame=valid)
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
    # the window's own spans, before the probe's predict writes more
    window = spans.window_spans()
    prepared = [e for e in window
                if (e["kind"], e["what"]) == ("train", "valid.prepare")]
    final_ll = float(out["training_metrics"].get("logloss"))
    model_out = {k: out[k] for k in (
        "split_points", "nbins", "col_nbins", "is_cat", "split_col",
        "value", "bitset", "f0", "scoring_history", "ntrees_actual")}
    model_out["validation_logloss"] = float(
        out["validation_metrics"].get("logloss"))
    model_out["unseen_rows"] = prepared[-1].get("unseen_rows") \
        if prepared else None
    t = time.monotonic()
    probe = probe_of(split, int(traffic["probe_rows"]))
    model_out["probe_rows"] = probe
    model_out["probe_p1"] = predict_probe(model, split, probe)
    clocks["probe_s"] = time.monotonic() - t
    # free the program's state before the reference runs
    del model, builder, out, frame, valid

    tr = None
    digest = hashlib.sha1()
    for k in ("split_col", "bitset", "value"):
        digest.update(np.ascontiguousarray(model_out[k]).tobytes())
    sc = np.asarray(model_out["split_col"])
    notes: Dict[str, Any] = {
        "clocks": clocks, "trees_planned": ntrees, "trees_built": built,
        "final_training_logloss": final_ll,
        "final_validation_logloss": model_out["validation_logloss"],
        "forest_sha1": digest.hexdigest(),
        "split_nodes": int((sc >= 0).sum()),
        "enum_split_nodes": int(np.asarray(model_out["is_cat"])[
            sc[sc >= 0]].sum()),
        "col_nbins": [int(b) for b in model_out["col_nbins"]],
        "valid_prepare": {k: prepared[-1].get(k) for k in (
            "rows", "cat_cols", "remapped_cols", "unseen_levels",
            "unseen_rows")} if prepared else None,
        "probe_rows": int(len(probe)),
        # [span, start ms, host ms] of the window's job: where its blocks
        # and scoring points fell (a plain run has no trace to say)
        "window_spans_ms": [
            [e["what"], round((e["ns"] - window[0]["ns"]) / 1e6, 1),
             round(e["dur_ns"] / 1e6, 1)]
            for e in sorted(window, key=lambda e: e["ns"])
            if e["kind"] == "train"] if window else []}
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            # [kind, self seconds, events, distinct ops] of the slice
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, split, model_out, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    spec = train_mixed.spec_of(config)
    # nbins: the one table's width, which sizes the narrowest bin index
    shapes = {"rows": rows, "cols": len(split.names),
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
