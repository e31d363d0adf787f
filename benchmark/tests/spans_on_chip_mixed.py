"""``spans_on_chip`` for a cell of kind ``train_mixed``: a whole training
on the mixed frame under the profiler, by hand on the chip.

    python3 -m benchmark.tests.spans_on_chip_mixed --seed <n> \
        [--workload gbm-airline-xgbhist.train] [--rows N] [--trees 2]

``--rows`` defaults to the configuration's own.  After a warm-up train
of the same shapes, one traced train, then the table by scope and the
idle gaps by innermost span (``benchmark.scopes``), and the job's spans
from the ring with the fields the mixed path adds (``train.bin``:
``cat_cols``, ``max_card``, ``table_bins``; ``train.block.pull``:
``num_splits``, ``cat_splits``, ``na_left_splits``).  A look, not a
measurement: it prints no result line.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import harness, scopes, spans
from benchmark.data_airline import GENERATORS, RESPONSE
from benchmark.kinds.train_budgeted import builder_class
from benchmark.kinds.train_mixed import land

_FIELDS = ("cat_cols", "max_card", "table_bins", "num_splits",
           "cat_splits", "na_left_splits", "source")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="gbm-airline-xgbhist.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--trees", type=int, default=2)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    harness.require_accelerator(int(cell["chips"]))
    import jax
    import h2o_tpu
    h2o_tpu.Cloud.boot(nodes=int(cell["chips"]))
    frame = land(GENERATORS[config["data"]](
        args.rows or int(config["rows"]), args.seed))
    params = dict(config["params"], seed=args.seed, ntrees=args.trees,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    Builder(**params).train(y=RESPONSE, training_frame=frame)  # warm-up
    logdir = harness.OUT_DIR / "trace-spans-on-chip"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**params).train(y=RESPONSE, training_frame=frame)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    print("spans of the traced job (kind.what, start ms, host ms, fields):")
    window = sorted(spans.window_spans(), key=lambda e: e["ns"])
    t0 = window[0]["ns"] if window else 0
    for e in window:
        fields = {k: e[k] for k in _FIELDS if k in e}
        print(f"  {e['kind']}.{e['what']:<18} {(e['ns'] - t0) / 1e6:>10.1f}"
              f" {e['dur_ns'] / 1e6:>10.1f}  {fields}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
