"""Readings of the four-chip cell's limits: the program with a fault
planted in its own path, at the cell's size, on the four-chip host; and
the blocked reference in the program's place, in a lower precision or
with a fault, on any host.

    python3 -m benchmark.tests.readings_x4 --seeds 1 2 [--rows N] \
        [--modes sound quantile_shard_out metric_shard_out control] \
        [--reference-modes bf16 half_batch na_flip cat_by_code] \
        [--processes N]

Program modes (a GBM of the cell's two trees, trained on the landed
frame and judged by the comparison that decides ``correct``):

* ``sound``: as the cell trains it;
* ``quantile_shard_out``: the split points computed with the last
  shard's rows left out of the counts (the rows past three quarters
  missing, as a selection that dropped a shard would leave them);
* ``metric_shard_out``: the binomial metric kernel with the last shard's
  rows left out of its table and sums;
* ``control``: ``bf16_histograms=true``, the program's own lower
  precision (compiles programs of its own).

Reference modes: ``benchmark/reference/gbm_mixed_blocked.py`` builds the
forest itself (``GbmMixedReference.build_forest``'s faults; and
``metric_shard_out``: its sound forest with the log-loss history taken
over every row but the last shard's) and is followed by a sound one.
One JSON line per (seed, mode).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import numpy as np

from benchmark import harness
from benchmark.data_airline import RESPONSE
from benchmark.kinds.train_budgeted import builder_class
from benchmark.kinds.train_mixed import land, spec_of
from benchmark.kinds.train_sharded import GENERATORS, compare, model_numbers
from benchmark.reference.gbm_mixed import LOG_EPS
from benchmark.reference.gbm_mixed_blocked import GbmMixedBlockedReference

CELL = "gbm-airline-xgbhist-x4.train"
PROGRAM_MODES = ("sound", "quantile_shard_out", "metric_shard_out",
                 "control")
REFERENCE_MODES = ("bf16", "half_batch", "stale_state", "cat_by_code",
                   "na_flip", "metric_shard_out")


@contextlib.contextmanager
def planted(mode: str, keep_rows: int):
    """The fault of ``mode`` planted in the program, rows from
    ``keep_rows`` on left out."""
    from h2o_tpu.models import metrics as mm
    from h2o_tpu.models.tree import shared_tree as st
    saved = (st.quantile_split_points, mm.binomial_kernel)
    if mode == "quantile_shard_out":
        st.quantile_split_points = lambda m, nrows, nbins: saved[0](
            m, min(nrows, keep_rows), nbins)
    elif mode == "metric_shard_out":
        import jax.numpy as jnp

        def kernel(p, y, w, valid, nbins=1024):
            keep = jnp.arange(jnp.shape(p)[0]) < keep_rows
            return saved[1](p, y, w, jnp.asarray(valid) & keep, nbins)
        mm.binomial_kernel = kernel
    try:
        yield
    finally:
        st.quantile_split_points, mm.binomial_kernel = saved


def program_readings(config, traffic, cell, data, seed, modes):
    import h2o_tpu
    chips = int(cell["chips"])
    h2o_tpu.Cloud.boot(nodes=chips)
    frame = land(data)
    keep = (chips - 1) * (int(frame.padded_rows) // chips)
    k = int(traffic["check_trees"])
    params = dict(config["params"], ntrees=k, seed=seed,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    for mode in modes:
        t = time.monotonic()
        p = dict(params, bf16_histograms=True) if mode == "control" \
            else params
        with planted(mode, keep):
            out = Builder(**p).train(y=RESPONSE, training_frame=frame).output
        train_s = time.monotonic() - t
        model_out = model_numbers(out)
        verdict = compare(config, traffic, data, model_out, k)
        yield mode, {"correct": verdict["correct"],
                     "compared": {n: v for n, (v, _) in
                                  verdict["compared"].items()},
                     "read_only": {n: v for n, v in
                                   verdict["read_only"].items()
                                   if n != "worst_leaf"},
                     "train_s": train_s,
                     "seconds": time.monotonic() - t}


def _history_without_last_shard(ref, trees, f0: float, chips: int):
    """The log-loss a tree at a time over every row but the last shard's
    (the rows past ``(chips - 1) / chips`` of the frame): a metric table
    that left one shard out."""
    keep = (chips - 1) * (ref.R // chips)
    F = np.full(ref.R, f0)
    y = ref.y[:keep]
    history = {}
    for i, t in enumerate(trees):
        F = F + ref.predict(t)
        p = 1.0 / (1.0 + np.exp(-F[:keep]))
        ll = np.where(y > 0.5, np.log(np.maximum(p, LOG_EPS)),
                      np.log(np.maximum(1.0 - p, LOG_EPS)))
        history[i + 1] = float(-ll.mean())
    return history


def reference_readings(config, traffic, data, modes, processes, chips):
    k, search = int(traffic["check_trees"]), int(traffic["search_trees"])
    with GbmMixedBlockedReference(data.cols, data.card, data.y,
                                  spec_of(config),
                                  processes=processes) as ref:
        ref.prepare()
        for mode in modes:
            t = time.monotonic()
            if mode == "metric_shard_out":
                trees, f0, history = ref.build_forest(k)
                history = _history_without_last_shard(ref, trees, f0, chips)
            else:
                kw = {"precision": "bf16"} if mode == "bf16" \
                    else {mode: True}
                trees, f0, history = ref.build_forest(k, **kw)
            nums = ref.check_forest(trees, f0, history, search)
            nums.pop("worst_leaf", None)
            nums.pop("logloss_points", None)
            lim = traffic["limits"]
            yield mode, {"correct": all(v <= lim[n] for n, v in nums.items()
                                        if n in lim),
                         "numbers": nums, "seconds": time.monotonic() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--modes", nargs="*", choices=PROGRAM_MODES,
                    default=list(PROGRAM_MODES[:3]))
    ap.add_argument("--reference-modes", nargs="*", choices=REFERENCE_MODES,
                    default=[])
    ap.add_argument("--processes", type=int, default=0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for key, v in (config.get("env") or {}).items():
        os.environ[key] = str(v)
    if args.modes:
        harness.require_accelerator(int(cell["chips"]))
    rows = args.rows or int(config["rows"])
    traffic = dict(traffic, processes=args.processes)
    for seed in args.seeds:
        data = GENERATORS[config["data"]](rows, seed)
        runs = []
        if args.modes:
            runs.append(program_readings(config, traffic, cell, data, seed,
                                         args.modes))
        if args.reference_modes:
            runs.append(reference_readings(config, traffic, data,
                                           args.reference_modes,
                                           args.processes,
                                           int(cell["chips"])))
        for gen in runs:
            for mode, got in gen:
                print(json.dumps({"workload": args.workload, "rows": rows,
                                  "seed": seed, "mode": mode, **got},
                                 default=float), flush=True)
        del data
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
