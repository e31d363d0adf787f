"""``spans_on_chip`` for a cell of kind ``train_validated``: a whole
training with a validation frame under the profiler, by hand on the chip.

    python3 -m benchmark.tests.spans_on_chip_valid --seed <n> \
        [--workload gbm-airline-xgbhist-valid.train] [--rows N] \
        [--valid-rows M] [--trees 2] [--file-width]

Sizes default to the configuration's own.  After a warm-up train of the
same shapes (validation frame included), one traced train, then the
table by scope and the idle gaps by innermost span
(``benchmark.scopes``), the seconds under each ``h2o.score.*`` scope
(where the second frame's time goes), and the job's spans from the ring
with the fields the second frame adds (``train.valid.prepare``: ``rows``,
``cat_cols``, ``remapped_cols``, ``unseen_levels``, ``unseen_rows``;
``train.block.score``: ``valid_rows``; ``train.final_metrics.valid``:
``source``).  A look, not a measurement: it prints no result line.

``--file-width`` leaves the table as wide as the training file's level
count makes it (``shared_tree.table_width`` rounds it to a size class,
so the cell itself always runs 353 bins): another width, and another
set of programs, every seed.  Seed 2147493153 gives 338 bins, at which
the parent's histogram came back all zero on the chip and the job grew
no split (PERF.md, PR 34): the ``num_splits`` of the ``train.block.pull``
spans below say whether trees split.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import harness, scopes, spans, trace
from benchmark.data_airline import RESPONSE
from benchmark.data_airline_split import GENERATORS
from benchmark.kinds.train_budgeted import builder_class
from benchmark.kinds.train_validated import land

_FIELDS = ("rows", "cat_cols", "remapped_cols", "unseen_levels",
           "unseen_rows", "valid_rows", "num_splits", "cat_splits",
           "source")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload",
                    default="gbm-airline-xgbhist-valid.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--valid-rows", type=int, default=0)
    ap.add_argument("--trees", type=int, default=2)
    ap.add_argument("--file-width", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    harness.require_accelerator(int(cell["chips"]))
    import jax
    import h2o_tpu
    if args.file_width:
        from h2o_tpu.models.tree import shared_tree
        shared_tree.table_width = lambda levels: levels
    h2o_tpu.Cloud.boot(nodes=int(cell["chips"]))
    split = GENERATORS[config["data"]](
        args.rows or int(config["rows"]),
        args.valid_rows or int(config["valid_rows"]), args.seed)
    frames = dict(training_frame=land(split, split.train),
                  validation_frame=land(split, split.valid))
    params = dict(config["params"], seed=args.seed, ntrees=args.trees,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    Builder(**params).train(y=RESPONSE, **frames)           # warm-up
    logdir = harness.OUT_DIR / "trace-spans-on-chip"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**params).train(y=RESPONSE, **frames)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    xp = trace.find_xplane(logdir)
    tr = trace.reduce_xplane(xp) if xp is not None else None
    if tr is not None:
        named = scopes.by_scope(tr["ops"], scopes.op_paths(xp))
        print("seconds under each h2o.score.* scope:")
        for name in sorted(n for n in named if n.startswith("h2o.score.")):
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
