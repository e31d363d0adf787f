"""``spans_on_chip`` for the four-chip cell ``gbm-airline-xgbhist-x4.train``
(kind ``train_sharded``): a whole training on the row-sharded frame under
the profiler, by hand on the four-chip host.

    python3 -m benchmark.tests.spans_on_chip_x4 --seed <n> [--rows N] \
        [--trees 2]

After a warm-up train of the same shapes, one traced train, then the
table by scope (``h2o.coll.*`` among them: each collective's own scope)
and the idle gaps by innermost span (``benchmark.scopes``), the
slice's collective operations (``benchmark/collectives.py``: owned or
not, seconds, events, payload), and the job's spans from the ring with
the fields the sharded path adds: ``train.bin`` ``shards``;
``train.bin.quantile`` ``shards``, ``rows_per_shard``, ``rounds``,
``ici_bytes``; ``train.block.launch`` ``ici_bytes``.  Last, every
program made ready in the process whose compiled module holds a
collective no ``h2o.coll.`` scope owns (``exec.ready``'s
``gspmd_collectives``).  A look, not a measurement: it prints no result
line.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import collectives, harness, scopes, spans
from benchmark.data_airline import RESPONSE
from benchmark.kinds.train_budgeted import builder_class
from benchmark.kinds.train_mixed import land
from benchmark.kinds.train_sharded import GENERATORS
from benchmark.trace import reduce_xplane, find_xplane

CELL = "gbm-airline-xgbhist-x4.train"
_FIELDS = ("shards", "rows_per_shard", "rounds", "ici_bytes", "cat_cols",
           "table_bins", "num_splits", "cat_splits", "source")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--trees", type=int, default=2)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    device = harness.require_accelerator(int(cell["chips"]))
    import jax
    import h2o_tpu
    from h2o_tpu.core.diag import DispatchStats
    data = GENERATORS[config["data"]](args.rows or int(config["rows"]),
                                      args.seed)
    h2o_tpu.Cloud.boot(nodes=int(cell["chips"]))
    DispatchStats.install_xla_listener()
    frame = land(data)
    params = dict(config["params"], seed=args.seed, ntrees=args.trees,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    Builder(**params).train(y=RESPONSE, training_frame=frame)  # warm-up
    logdir = harness.OUT_DIR / "trace-spans-on-chip-x4"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**params).train(y=RESPONSE, training_frame=frame)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    xp = find_xplane(logdir)
    ctx = {"trace": reduce_xplane(xp) if xp else None,
           "shapes": {"chips": int(cell["chips"])},
           "device_kind": device["kind"]}
    colls = collectives.collectives(ctx) or []   # the newest trace file
    print("collectives of the whole window (scope, half, seconds, "
          "events, payload bytes):")
    for c in sorted(colls, key=lambda c: -c.seconds):
        print(f"  {c.scope:<24} {c.half or 'sync':<7} {c.seconds:>12.6f} "
              f"{c.events:>6} {c.nbytes:>12}  {c.name[:70]}")
    print("spans of the traced job (kind.what, start ms, host ms, fields):")
    window = sorted(spans.window_spans(), key=lambda e: e["ns"])
    t0 = window[0]["ns"] if window else 0
    for e in window:
        fields = {k: e[k] for k in _FIELDS if k in e}
        print(f"  {e['kind']}.{e['what']:<18} {(e['ns'] - t0) / 1e6:>10.1f}"
              f" {e['dur_ns'] / 1e6:>10.1f}  {fields}")
    unowned = [(p["fun"], p["gspmd_collectives"])
               for p in DispatchStats.programs() if p["gspmd_collectives"]]
    print(f"programs made ready: {len(DispatchStats.programs())}; with a "
          f"collective no h2o.coll. scope owns: {unowned}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
