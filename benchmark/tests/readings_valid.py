"""Readings of the planted faults of the SECOND frame at the validated
cell's own size: ``benchmark/reference/gbm_valid.py`` in the program's
place, its validation answers made with a fault, judged by the
comparison that decides ``correct`` (``tests/readings_mixed.py`` has the
training side's faults; they apply here unchanged).

    python3 -m benchmark.tests.readings_valid --seeds 101 102 [--rows N \
        --valid-rows M] [--modes sound unmapped ...]

The forest is the reference's own sound one (``build_forest``); what
varies is how the validation rows are scored:

``unmapped``      the validation frame's OWN codes are used as training
                  codes (the parent commit: no match by level string);
``unseen_last``   a level training never saw takes the training domain's
                  last level's bin, not the NA bucket;
``stale``         the validation F is one tree behind at every point;
``train_metric``  the validation metric is computed from the training
                  frame's F: the training log-loss under its name.

Pure numpy on the host: no accelerator is touched.  One JSON line per
(seed, mode).
"""

from __future__ import annotations

import argparse
import copy
import json
import time
from typing import Dict, Sequence

import numpy as np

from benchmark import harness
from benchmark.data_airline_split import GENERATORS
from benchmark.kinds.train_mixed import spec_of
from benchmark.kinds.train_validated import probe_of
from benchmark.reference.gbm_mixed import GbmMixedReference
from benchmark.reference.gbm_valid import GbmValidReference, codes_in

MODES = ("sound", "unmapped", "unseen_last", "stale", "train_metric")


def answers(ref: GbmValidReference, valid_ids: Dict[int, np.ndarray],
            trees, f0: float, train_history: Dict[int, float], mode: str,
            probe: np.ndarray) -> Dict:
    """What a program with the fault ``mode`` would report for the
    second frame: ``check_valid``'s keyword arguments."""
    scorer, unseen_rows = ref, ref.unseen_rows
    if mode in ("unmapped", "unseen_last"):
        cols = list(ref.valid.cols)
        for j, dom in ref.domains.items():
            ids = np.asarray(valid_ids[j])
            if mode == "unmapped":
                own = np.unique(ids[ids >= 0])
                c = np.where(ids >= 0, np.searchsorted(own, ids), -1)
            else:
                c, miss = codes_in(dom, ids)
                c = np.where(miss, len(dom) - 1, c)
            cols[j] = c.astype(np.int32)
        scorer = copy.copy(ref)
        scorer.valid = GbmMixedReference(cols, ref.card, ref.valid.y,
                                         ref.valid.spec,
                                         threads=ref.valid.threads)
        if mode == "unmapped":
            unseen_rows = 0         # it matches nothing, so misses nothing
    F, losses = scorer.follow_valid(trees, f0)
    history = dict(enumerate(losses, start=1))
    if mode == "stale":
        start = ref.valid.logloss(np.full(ref.valid.R, float(f0)))
        history = dict(enumerate([start] + losses[:-1], start=1))
    elif mode == "train_metric":
        history = dict(train_history)
    return dict(history=history, final=history[len(trees)],
                program_unseen_rows=unseen_rows, probe_rows=probe,
                probe_p1=1.0 / (1.0 + np.exp(-F[probe])))


def readings(ref: GbmValidReference, valid_ids, k: int, probe,
             modes: Sequence[str] = MODES):
    trees, f0, train_history = ref.train.build_forest(k)
    for mode in modes:
        nums = ref.check_valid(trees, f0, **answers(
            ref, valid_ids, trees, f0, train_history, mode, probe))
        nums["valid_points_missing"] = k - nums.pop("valid_logloss_points")
        yield mode, nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="gbm-airline-xgbhist-valid.train")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--valid-rows", type=int, default=0)
    ap.add_argument("--modes", nargs="+", default=list(MODES))
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, args.workload)
    rows = args.rows or int(config["rows"])
    valid_rows = args.valid_rows or int(config["valid_rows"])
    for seed in args.seeds:
        split = GENERATORS[config["data"]](rows, valid_rows, seed)
        is_enum = [n in split.enum for n in split.names]
        ref = GbmValidReference(split.train.cols, split.valid.cols, is_enum,
                                split.train.y, split.valid.y,
                                spec_of(config), threads=args.threads)
        t = time.monotonic()
        ref.prepare()
        ids = {j: split.valid.cols[j] for j in ref.domains}
        probe = probe_of(split, int(traffic["probe_rows"]))
        for mode, nums in readings(ref, ids, int(traffic["check_trees"]),
                                   probe, args.modes):
            print(json.dumps({"workload": args.workload, "rows": rows,
                              "valid_rows": valid_rows, "seed": seed,
                              "mode": mode, "numbers": nums,
                              "unseen_rows": ref.unseen_rows,
                              "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
