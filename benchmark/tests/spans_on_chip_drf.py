"""The DRF cell's whole window under the profiler, by hand on the chip:
``spans_on_chip.py`` for a random forest at H2O-3's defaults.

    python3 -m benchmark.tests.spans_on_chip_drf --seed <n> [--rows N] \
        [--trees 4]

After a warm-up ``train()`` of one tree, one ``train()`` of ``--trees``
trees in blocks of one with the profiler on from the call to the model:
the table of device time by ``h2o.*`` scope and the idle gaps by host
span (``benchmark.scopes``), the seconds and events under
``h2o.tree.partition`` and ``h2o.tree.hist.*``, and the job's spans from
the ring with the fields the tree driver writes (the pull's frontier
counters, the scorer's source).  A look, not a measurement: it prints no
result line.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from benchmark import harness, scopes, spans, trace
from benchmark.data_higgs_dense import GENERATORS
from benchmark.kinds.train_bagged import apply_cap
from benchmark.kinds.train_budgeted import builder_class, land

_FIELDS = ("num_splits", "frontier_cut", "frontier_split_children",
           "frontier_levels", "source", "route_levels",
           "route_select_levels", "table_bins")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="drf-higgs-h2odefault.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--trees", type=int, default=4)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    apply_cap(config)
    harness.require_accelerator(int(cell["chips"]))
    import jax
    import h2o_tpu
    h2o_tpu.Cloud.boot(nodes=int(cell["chips"]))
    X, y = GENERATORS[config["data"]](args.rows or int(config["rows"]),
                                      int(config["cols"]), args.seed)
    frame = land(config, X, y)
    params = dict(config["params"], seed=args.seed,
                  score_tree_interval=int(traffic["score_tree_interval"]),
                  max_runtime_secs=3600.0)
    Builder = builder_class(config)
    Builder(**dict(params, ntrees=1)).train(y="y", training_frame=frame)
    logdir = harness.OUT_DIR / "trace-spans-on-chip"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        Builder(**dict(params, ntrees=args.trees)).train(
            y="y", training_frame=frame)
    finally:
        jax.profiler.stop_trace()
    rc = scopes.main(["scopes", str(logdir)])
    xp = trace.find_xplane(logdir)
    tr = trace.reduce_xplane(xp) if xp is not None else None
    if tr is not None:
        paths = scopes.op_paths(xp)
        print("seconds, events and the costliest operations under "
              "h2o.tree.partition and h2o.tree.hist.*:")
        for scope in ("h2o.tree.partition", "h2o.tree.hist.window",
                      "h2o.tree.hist.onehot", "h2o.tree.hist.contract"):
            ops = sorted(((s, ev, n) for n, (s, ev) in tr["ops"].items()
                          if scopes.scope_of(paths.get(n)) == scope),
                         reverse=True)
            print(f"  {scope:<24}{sum(o[0] for o in ops):>12.6f} s "
                  f"{sum(o[1] for o in ops):>9} events")
            for s, ev, n in ops[:4]:
                print(f"      {s:>10.6f} s {ev:>7}  {n[:110]}")
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
