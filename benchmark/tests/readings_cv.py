"""Readings of the planted faults of a CROSS-VALIDATED job at the cell's
own size: ``benchmark/reference/gbm_cv.py`` in the program's place, its
answers made with a fault, judged by the comparison that decides
``correct`` (``tests/readings.py`` has the one-model faults; they apply
to the followed fold model unchanged).

    python3 -m benchmark.tests.readings_cv --seeds 101 102 [--rows N] \
        [--modes sound leak main_model next_fold stale in_fold]

The six forests are the reference's own sound ones (``build_job``); what
varies is how the job's cross-validation answers are made:

``leak``        (a) a fold's rows at weight 1 in their own fold model;
``main_model``  (b) the holdout predictions taken from the main model;
``next_fold``   (c) fold i's rows given fold i+1's model;
``stale``       (d) the holdout predictions one tree stale, and with
                them every holdout scoring point (a scorer that reads the
                F of the block before);
``in_fold``     (e) ``cross_validation_metrics``, the summary and every
                holdout scoring point computed over the in-fold
                predictions (the scorer handed the training weights: the
                fold models' training log-loss under the holdout's name).

Pure numpy on the host: no accelerator is touched.  One JSON line per
(seed, mode).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Sequence

import numpy as np

from benchmark import harness
from benchmark.data import GENERATORS
from benchmark.kinds.train_budgeted import spec_of
from benchmark.reference.gbm_cv import GbmCvReference, auc_of, logloss_of

MODES = ("sound", "leak", "main_model", "next_fold", "stale", "in_fold")


def answers(ref: GbmCvReference, folds, main, mode: str) -> Dict:
    """What a program with the fault ``mode`` would report:
    ``check_job``'s keyword arguments (``fold_models`` and ``main``
    apart)."""
    y, k = ref.base.y, len(main["trees"])
    if mode == "main_model":
        F, _ = ref.follow_rows(main["trees"], main["f0"], np.arange(ref.R))
    else:
        F = ref.holdout_F(folds, shift=1 if mode == "next_fold" else 0,
                          upto=k - 1 if mode == "stale" else None)
    p = 1.0 / (1.0 + np.exp(-F))
    cv_ll = logloss_of(F, y)
    fold_ll = [logloss_of(F[ref.fold == i], y[ref.fold == i])
               for i in range(ref.nfolds)]
    if mode == "in_fold":
        fold_ll = [m["train_history"][k] for m in folds]
        cv_ll = float(np.mean(fold_ll))
    return dict(fold_assignment=ref.fold, holdout_p1=p, cv_logloss=cv_ll,
                cv_auc=auc_of(p, y), fold_loglosses=fold_ll)


def with_history(ref: GbmCvReference, folds, mode: str):
    """The fold models with the holdout scoring points a program with
    the fault ``mode`` would report."""
    if mode not in ("stale", "in_fold"):
        return folds
    out = []
    for i, m in enumerate(folds):
        idx = np.flatnonzero(ref.fold == i)
        if mode == "stale":
            start = logloss_of(np.full(len(idx), m["f0"]), ref.base.y[idx])
            k = len(m["trees"])
            hist = {1: start, **{n + 1: m["holdout_history"][n]
                                 for n in range(1, k)}}
        else:
            hist = dict(m["train_history"])
        out.append(type(m)(m, holdout_history=hist))
    return out


def readings(ref: GbmCvReference, k: int, followed: int,
             search_trees: int, modes: Sequence[str] = MODES):
    sound = ref.build_job(k)
    for mode in modes:
        folds, main = ref.build_job(k, leak=True) if mode == "leak" \
            else sound
        folds = with_history(ref, folds, mode)
        yield mode, ref.check_job(
            folds, main, followed=followed, search_trees=search_trees,
            **answers(ref, folds, main, mode))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gbm-higgs-xgbhist-cv5.train")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--modes", nargs="+", default=list(MODES))
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, args.workload)
    rows = args.rows or int(config["rows"])
    nfolds = int(config["params"]["nfolds"])
    for seed in args.seeds:
        X, y = GENERATORS[config["data"]](rows, int(config["cols"]), seed)
        ref = GbmCvReference(X, y, spec_of(config), nfolds,
                             threads=args.threads)
        t = time.monotonic()
        ref.prepare()
        for mode, nums in readings(ref, int(traffic["check_trees"]),
                                   seed % nfolds,
                                   int(traffic["search_trees"]),
                                   args.modes):
            nums.pop("worst_leaf", None)
            print(json.dumps({"workload": args.workload, "rows": rows,
                              "seed": seed, "mode": mode, "numbers": nums,
                              "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
