"""Readings of the planted faults of the DRF cell: ``benchmark/
reference/drf.py`` in the program's place, its forest grown with a fault,
judged by the comparison that decides ``correct``.

    python3 -m benchmark.tests.readings_drf --seeds 101 102 [--rows N] \
        [--cap N] [--modes sound all_rows mtries_per_tree bag_rate_1
        frontier_by_slot half_batch bf16_values]

``all_rows``          (a) the training metrics over every row's votes of
                      every tree (the parent's semantics);
``mtries_per_tree``   (b) one ``mtries`` draw a tree for all its nodes;
``bag_rate_1``        (c) every row in every tree's bag;
``frontier_by_slot``  (d) a capped level keeps its first children by
                      child index, not the most impure;
``half_batch``        (e) every other row counted;
``bf16_values``       the reference in the nearest precision below the
                      configuration's float32: leaf values and votes in
                      bfloat16 (the chip's own bfloat16 histograms move
                      nothing here: the statistics are 0/1 counts).

Pure numpy on the host: no accelerator is touched.  One JSON line per
(seed, mode).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmark import harness
from benchmark.data_higgs_dense import GENERATORS
from benchmark.kinds.train_bagged import spec_of
from benchmark.reference.drf import DrfReference, PoolTree
from benchmark.reference.gbm import uniform_split_points

MODES = ("sound", "all_rows", "mtries_per_tree", "bag_rate_1",
         "frontier_by_slot", "half_batch", "bf16_values")


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def readings(X, y, spec, seed, ntrees, modes=MODES, threads=4):
    """(mode, numbers) of each mode: the forest a program with that
    fault would hand over, checked as the kind checks the program's."""
    sp = np.stack([uniform_split_points(float(c.min()), float(c.max()),
                                        spec.fine) for c in X])
    for mode in modes:
        ref = DrfReference(X, y, spec, seed, threads=threads)
        ref.prepare(sp)
        fault = None if mode in ("sound", "bf16_values") else mode
        trees, history, final_ll, final_rows = ref.build_forest(ntrees,
                                                                fault)
        if mode == "bf16_values":
            trees = [t._replace(value=_bf16(t.value)) for t in trees]
            bags = [ref.bag(t) for t in range(ntrees)]
            points, _ = ref.oob_votes(trees, bags)
            history = {k: ref.logloss(_bf16(v), c)
                       for k, (v, c) in enumerate(points, start=1)}
            final_ll = history[ntrees]
        check = DrfReference(X, y, spec, seed, threads=threads)
        nums = check.prepare(sp)
        nums.update(check.check_forest(trees, history, final_ll,
                                       final_rows))
        yield mode, nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="drf-higgs-h2odefault.train")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--cap", type=int, default=0)
    ap.add_argument("--trees", type=int, default=4)
    ap.add_argument("--modes", nargs="+", default=list(MODES))
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, args.workload)
    if args.cap:
        config["max_live_leaves"] = args.cap
    rows = args.rows or int(config["rows"])
    spec = spec_of(config, int(config["cols"]))
    limits = traffic["limits"]
    for seed in args.seeds:
        X, y = GENERATORS[config["data"]](rows, int(config["cols"]), seed)
        t = time.monotonic()
        for mode, nums in readings(X, y, spec, seed, args.trees,
                                   args.modes, args.threads):
            failed = sorted(k for k, v in nums.items()
                            if (k in limits and not v <= limits[k]) or
                            (k.endswith(("_gap", "_missing")) and
                             k not in limits and
                             not k.startswith("cover_gap") and v != 0))
            print(json.dumps({"workload": args.workload, "rows": rows,
                              "cap": spec.cap, "seed": seed, "mode": mode,
                              "failed": failed, "numbers": nums,
                              "seconds": time.monotonic() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
