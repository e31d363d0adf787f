"""Traffic kind ``train_bagged``: one random-forest training job on a
landed frame, ``<builder>(**params).train(y=..., training_frame=...)``,
the entry the REST handler and AutoML call.

Sized and guarded as ``train_budgeted`` sets out (its helpers are
imported, nothing of them is edited): ``planned_trees`` trees in blocks
of ``score_tree_interval``, every block scored (the training frame's
out-of-bag votes, as H2O-3 scores a forest), ``max_runtime_secs`` at
``runtime_guard`` times ``--seconds``.  What differs:

* set-up's warm-up is one ``train()`` of ``warm_blocks`` blocks, and
  nothing more: the forest's training metrics are read from the votes
  the trainer carried, so no whole-forest scoring program is warmed;
* the traced slice opens at a PHASE, not a second: when the window's
  ``trace_after``-th ``train.block.pull`` span closes (block 1's pull:
  tree 2 is then running), and holds ``trace_seconds``;
* ``correct`` is decided by ``benchmark/reference/drf.py``: the
  program's split points against the uniform grid, tree 1 followed
  whole (bag, covers, means, each node's ``mtries`` draw and grid, every
  candidate of the grid, the frontier's best-first cut), every row
  routed down every tree of the window for its own out-of-bag votes,
  and the out-of-bag log-loss of every scoring point and of the end;
* a program whose deep levels contract every row against every node of
  the level (no ``histogram_window_traced``) cannot end the
  configuration's job inside a run: it is refused at once, before any
  data is made.

Traffic file parameters: as ``train_budgeted``'s, with ``trace_after``
(the pull span that opens the slice) in place of ``trace_start_s``.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import shutil
import threading
import time
from typing import Any, Dict

import numpy as np

from benchmark import harness, spans, trace as trace_mod
from benchmark.data_higgs_dense import GENERATORS
from benchmark.kinds.train_budgeted import (builder_class, land,
                                            planned_trees)
from benchmark.reference.drf import DrfReference, DrfSpec, PoolTree

# the numbers that are counts: compared exactly
_EXACT = ("trees_missing", "bag_gap", "mtries_gap", "frontier_gap",
          "oob_rows_gap", "oob_points_missing")
_ARTIFACT = ("split_points", "split_col", "thr_bin", "value", "child",
             "node_w", "scoring_history", "ntrees_actual")


def spec_of(config: Dict[str, Any], columns: int) -> DrfSpec:
    p = config["params"]
    mtries = int(p.get("mtries", -1))
    if mtries <= 0:
        mtries = max(1, int(np.sqrt(columns)))
    return DrfSpec(max_depth=int(p["max_depth"]), nbins=int(p["nbins"]),
                   fine=int(p.get("nbins_top_level", 1024)),
                   min_rows=float(p["min_rows"]),
                   min_split_improvement=float(
                       p.get("min_split_improvement", 1e-5)),
                   mtries=mtries, sample_rate=float(p["sample_rate"]),
                   cap=int(config["max_live_leaves"]))


def apply_cap(config: Dict[str, Any]) -> None:
    """The configuration's frontier cap (``max_live_leaves``, listed under
    ``reduced``) as the engine reads it: ``H2O_TPU_MAX_LIVE_LEAVES``."""
    os.environ["H2O_TPU_MAX_LIVE_LEAVES"] = str(
        int(config["max_live_leaves"]))


def pool_trees(out: Dict[str, Any]):
    """The artifact's trees in the reference's terms."""
    return [PoolTree(*(np.asarray(out[k][t, 0]).astype(dt) for k, dt in (
        ("split_col", np.int64), ("thr_bin", np.int64),
        ("value", np.float64), ("child", np.int64),
        ("node_w", np.float64))))
        for t in range(int(out["ntrees_actual"]))]


def compare(config, traffic, X, y, out: Dict[str, Any], seed: int,
            ntrees_planned: int, threads: int = 4) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every number compared,
    beside its limit."""
    limits = traffic["limits"]
    ref = DrfReference(X, y, spec_of(config, X.shape[0]), seed,
                       threads=threads)
    nums = ref.prepare(out["split_points"])
    history = {int(r["number_of_trees"]): float(r["training_logloss"])
               for r in out["scoring_history"] if "training_logloss" in r}
    nums.update(ref.check_forest(pool_trees(out), history,
                                 out["final_logloss"], out["final_rows"]))
    nums["trees_missing"] = ntrees_planned - int(out["ntrees_actual"])
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


def _pulls() -> int:
    from h2o_tpu.core.diag import TimeLine
    return sum(1 for e in TimeLine.snapshot() if "dur_ns" in e and
               (e.get("kind"), e.get("what")) == ("train", "block.pull"))


class _PhaseSlice:
    """Start the profiler when the ``after``-th ``train.block.pull`` span
    of the window has closed, and stop it ``seconds`` later or when the
    window ends, from a thread of its own: the window is one blocking
    call."""

    def __init__(self, logdir, after: int, seconds: float):
        self.logdir, self.after, self.seconds = logdir, after, seconds
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._base = _pulls()
        self.error = None
        self.opened_s = None

    def _run(self):
        import jax
        t0 = time.monotonic()
        while _pulls() < self._base + self.after:
            if self._stop.wait(0.005):
                return
        try:
            jax.profiler.start_trace(str(self.logdir))
            self.opened_s = time.monotonic() - t0
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
    apply_cap(config)
    from h2o_tpu.ops import histogram
    if not hasattr(histogram, "histogram_window_traced"):
        raise harness.Refused(
            "this program contracts every row against every node of a "
            "deep level (a depth-20 tree of the configuration is minutes "
            "a tree): it cannot end the configuration's job inside a run")
    import h2o_tpu
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

    t = time.monotonic()
    warm = Builder(**dict(params, ntrees=block * int(traffic["warm_blocks"])
                          )).train(y="y", training_frame=frame)
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
        slicer = _PhaseSlice(logdir, int(traffic["trace_after"]),
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
    window = spans.window_spans()
    art = {k: out[k] for k in _ARTIFACT}
    art["final_logloss"] = float(out["training_metrics"].get("logloss"))
    art["final_rows"] = float(out["training_metrics"].get("nobs"))
    # free the program's state before the reference runs
    del model, builder, out, frame

    digest = hashlib.sha1()
    for k in ("split_col", "thr_bin", "value", "child"):
        digest.update(np.ascontiguousarray(art[k]).tobytes())
    pulls = [e for e in window if (e["kind"], e["what"]) ==
             ("train", "block.pull")]
    notes: Dict[str, Any] = {
        "clocks": clocks, "trees_planned": ntrees, "trees_built": built,
        "final_training_logloss": art["final_logloss"],
        "final_training_rows": art["final_rows"],
        "forest_sha1": digest.hexdigest(),
        # [tree, ms into the window the pull ended, cut, split children,
        # capped levels] of each block
        "frontier": [[i + 1, round((e["ns"] + e["dur_ns"] - window[0]["ns"])
                                   / 1e6, 1) if window else None,
                      e.get("frontier_cut"), e.get("frontier_split_children"),
                      e.get("frontier_levels")]
                     for i, e in enumerate(pulls)],
        "final_metrics_source": next(
            (e.get("source") for e in window if (e["kind"], e["what"]) ==
             ("train", "final_metrics")), None)}
    tr = None
    if job.trace:
        xp = trace_mod.find_xplane(logdir)
        tr = trace_mod.reduce_xplane(xp) if xp is not None else None
        if tr is not None:
            # [kind, self seconds, events, distinct ops] of the slice
            notes["trace_groups"] = trace_mod.op_groups(tr["ops"])
        notes["trace_opened_s"] = slicer.opened_s
        if slicer.error:
            notes["trace_error"] = slicer.error

    t = time.monotonic()
    verdict = compare(config, traffic, X, y, art, job.seed, ntrees)
    clocks["reference_s"] = time.monotonic() - t
    notes["read_not_compared"] = verdict["read_only"]

    p = config["params"]
    shapes = {"rows": rows, "cols": cols, "nbins": int(p["nbins"]),
              "max_depth": int(p["max_depth"]),
              "fine_nbins": int(p.get("nbins_top_level", 1024)),
              "chips": int(job.cell["chips"])}
    return {
        "end_to_end": {"setup_s": clocks["setup_s"],
                       "train_rate": rows * built / clocks["window_s"]},
        "clocks": clocks, "counters": counters, "shapes": shapes,
        "device_kind": job.device.get("kind"), "trace": tr,
        "memory_peak_bytes": peak, "notes": notes,
        "attempted": ntrees, "failed": ntrees - built,
        "compared": verdict["compared"], "correct": verdict["correct"]}
