"""A whole training under the profiler, by hand on the chip: the
program's ``h2o:`` host spans beside its ``h2o.*`` device scopes on one
clock.

    python3 -m benchmark.tests.spans_on_chip --workload <name> --seed <n> \
        [--rows 1000000] [--trees 4]

The cells trace a 6 s slice of a 50 s window, and a host span shows in a
profile only if it opens AND closes while the profile is taken: the long
waits (``train.block.pull``, ``train.block.score``: a tree long) straddle
that slice.  Here the cell's configuration is cut to ``--rows`` so that
the profiler can stay on for the whole ``train()``: after a warm-up
train of the same shapes, one traced train, then the table by scope and
the idle gaps by innermost span (``benchmark.scopes``), and the job's
spans from the ring (``benchmark.spans``).  A look, not a measurement:
it prints no result line.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import harness, scopes, spans
from benchmark.data import GENERATORS
from benchmark.kinds.train_budgeted import builder_class, land


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--trees", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    harness.require_accelerator(int(cell["chips"]))
    import jax
    import h2o_tpu
    h2o_tpu.Cloud.boot(nodes=int(cell["chips"]))
    X, y = GENERATORS[config["data"]](args.rows, int(config["cols"]),
                                      args.seed)
    frame = land(config, X, y)
    params = dict(config["params"], seed=args.seed, ntrees=args.trees,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    Builder(**params).train(y="y", training_frame=frame)      # warm-up
    logdir = harness.OUT_DIR / "trace-spans-on-chip"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**params).train(y="y", training_frame=frame)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    print("spans of the traced job (kind.what, start ms, host ms, parent):")
    window = sorted(spans.window_spans(), key=lambda e: e["ns"])
    t0 = window[0]["ns"] if window else 0
    for e in window:
        print(f"  {e['kind']}.{e['what']:<18} {(e['ns'] - t0) / 1e6:>10.1f}"
              f" {e['dur_ns'] / 1e6:>10.1f}  id {e['id']} <- {e['parent']}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
