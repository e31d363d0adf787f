"""The cross-validated cell's whole window under the profiler, by hand on
the chip: ``spans_on_chip.py`` for a job of K + 1 models.

    python3 -m benchmark.tests.spans_on_chip_cv --seed <n> [--rows N] \
        [--trees 2]

After a warm-up job of the same shapes, one cross-validated ``train()``
with the profiler on from the call to the model: the table of device
time by ``h2o.*`` scope and the idle gaps by host span
(``benchmark.scopes``), the seconds under each ``h2o.cv.*`` and
``h2o.score.*`` scope (no ``h2o.score.bin`` and no ``h2o.score.descent``
may be among them), and the job's spans from the ring with the fields the
orchestration writes.  A look, not a measurement: it prints no result
line.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import harness, scopes, spans, trace
from benchmark.data import GENERATORS
from benchmark.kinds.train_budgeted import builder_class, land
from benchmark.kinds.train_cv import drop_job_keys

_FIELDS = ("nfolds", "scheme", "rows", "fold", "rows_in", "rows_out",
           "bins", "source", "holdout_rows", "table_bins", "onehot_bins")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="gbm-higgs-xgbhist-cv5.train")
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
    X, y = GENERATORS[config["data"]](args.rows or int(config["rows"]),
                                      int(config["cols"]), args.seed)
    frame = land(config, X, y)
    params = dict(config["params"], seed=args.seed, ntrees=args.trees,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    drop_job_keys(Builder(**params).train(y="y", training_frame=frame))
    logdir = harness.OUT_DIR / "trace-spans-on-chip"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**params).train(y="y", training_frame=frame)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    xp = trace.find_xplane(logdir)
    tr = trace.reduce_xplane(xp) if xp is not None else None
    if tr is not None:
        named = scopes.by_scope(tr["ops"], scopes.op_paths(xp))
        print("seconds under each h2o.cv.* and h2o.score.* scope:")
        for name in sorted(n for n in named
                           if n.startswith(("h2o.cv.", "h2o.score."))):
            print(f"  {name:<24}{named[name]:>12.6f}")
    print("spans of the traced job (kind.what, start ms, host ms, fields):")
    window = sorted(spans.window_spans(), key=lambda e: e["ns"])
    t0 = window[0]["ns"] if window else 0
    for e in window:
        fields = {k: e[k] for k in _FIELDS if k in e}
        print(f"  {e['kind']}.{e['what']:<20} {(e['ns'] - t0) / 1e6:>10.1f}"
              f" {e['dur_ns'] / 1e6:>10.1f}  {fields}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
