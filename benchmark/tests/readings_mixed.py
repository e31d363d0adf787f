"""Readings of the control and of the planted faults at the mixed cell's
own size: ``benchmark/reference/gbm_mixed.py``, put in the program's
place, in a lower precision or with a fault, judged by the comparison
that decides ``correct`` (``tests/readings.py`` for the numeric cells).

    python3 -m benchmark.tests.readings_mixed --seeds 1 2 3 [--rows N] \
        [--workload gbm-airline-xgbhist.train] [--modes sound bf16 ...]

The faults: the three the numeric cells have (``half_batch``,
``stale_state``, ``altered``) and two that only a mixed frame has:
``cat_by_code`` searches enum levels in code order, ``na_flip`` routes a
missing value to the other side than the node says.  Pure numpy on the
host: no accelerator is touched.  One JSON line per (seed, mode).
"""

from __future__ import annotations

import argparse
import json
import time

from benchmark import harness
from benchmark.data_airline import GENERATORS
from benchmark.kinds.train_mixed import spec_of
from benchmark.reference.gbm_mixed import GbmMixedReference

MODES = ("sound", "bf16", "half_batch", "stale_state", "altered",
         "cat_by_code", "na_flip")


def reading(ref: GbmMixedReference, mode: str, k: int, search_trees: int):
    kw = {"bf16": {"precision": "bf16"}}.get(
        mode, {} if mode in ("sound", "altered") else {mode: True})
    trees, f0, history = ref.build_forest(k, **kw)
    if mode == "altered":
        # the answers altered where they are produced: the last tree's
        # leaf values a hundredth larger
        trees[-1] = trees[-1]._replace(value=trees[-1].value * 1.01)
    nums = ref.check_forest(trees, f0, history, search_trees)
    nums.pop("worst_leaf", None)
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gbm-airline-xgbhist.train")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--modes", nargs="+", default=list(MODES))
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, args.workload)
    rows = args.rows or int(config["rows"])
    for seed in args.seeds:
        data = GENERATORS[config["data"]](rows, seed)
        ref = GbmMixedReference(data.cols, data.card, data.y,
                                spec_of(config), threads=args.threads)
        t = time.monotonic()
        ref.prepare()
        prep = time.monotonic() - t
        for mode in args.modes:
            t = time.monotonic()
            nums = reading(ref, mode, int(traffic["check_trees"]),
                           int(traffic["search_trees"]))
            print(json.dumps({"workload": args.workload, "rows": rows,
                              "seed": seed, "mode": mode, "numbers": nums,
                              "prepare_s": prep,
                              "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
