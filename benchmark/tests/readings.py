"""Readings of the control and of the planted faults at a cell's own
size: the reference, put in the program's place, in a lower precision or
with a fault, judged by the same comparison that decides ``correct``.

    python3 -m benchmark.tests.readings --workload <name> --seeds 1 2 3 \
        [--rows N] [--modes sound high bf16 half_batch stale_state altered]

Pure numpy on the host: no accelerator is touched.  One JSON line per
(seed, mode) on standard output.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmark import harness
from benchmark.data import GENERATORS
from benchmark.kinds.train_budgeted import spec_of
from benchmark.reference.gbm import GbmReference, Tree, round_like

MODES = ("sound", "high", "bf16", "half_batch", "stale_state", "altered")


def reading(ref: GbmReference, mode: str, k: int, search_trees: int):
    kw = {"high": {"precision": "high"}, "bf16": {"precision": "bf16"},
          "half_batch": {"half_batch": True},
          "stale_state": {"stale_state": True}}.get(mode, {})
    trees, f0, history = ref.build_forest(k, **kw)
    if mode == "altered":
        # the answers altered where they are produced: the last tree's
        # leaf values a hundredth larger
        t = trees[-1]
        trees[-1] = Tree(t.col, t.thr, t.value * 1.01)
    nums = ref.check_forest(trees, f0, history, search_trees)
    if kw.get("precision"):
        # the binning layer and f0 in that precision too
        f0c = float(round_like(np.array([f0]), kw["precision"])[0])
        nums["f0_gap"] = abs(f0c - f0)
        again = GbmReference(ref.X, ref.y, ref.spec, threads=ref.threads)
        nums.update(again.prepare(
            ref.control_split_points(kw["precision"])))
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--modes", nargs="+", default=list(MODES))
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, args.workload)
    rows = args.rows or int(config["rows"])
    for seed in args.seeds:
        X, y = GENERATORS[config["data"]](rows, int(config["cols"]), seed)
        ref = GbmReference(X, y, spec_of(config), threads=args.threads)
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
