"""Every per-layer reader on one traced run of a cell, by hand on the
chip: how a PR that adds a cell finds which accepted metrics have
something to read there before it appends the cell to their lists.

    python3 -m benchmark.tests.readers_on_chip --workload <name> --seed <n>

Runs the cell as ``benchmark.run`` does with ``--trace 1``, then calls
the reader of every ``per_layer`` entry, listed for the cell or not, and
prints ``readers`` (name -> value or null) before the run's usual last
line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import harness
from benchmark.run import _T0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    job = harness.Job(cell=cell, config=config, traffic=traffic,
                      seed=args.seed,
                      seconds=args.seconds or float(bench["run_seconds"]),
                      trace=True, t_start=_T0)
    job.device = harness.require_accelerator(int(cell["chips"]))
    ctx = harness.load_module("kinds", traffic["kind"]).run(job)
    readers = {m["name"]: harness.load_module("metrics", m["name"]).read(ctx)
               for m in bench["per_layer"]}
    print("readers " + json.dumps(readers), flush=True)
    harness.emit(harness.result_line(bench, job, ctx))
    return 0


if __name__ == "__main__":
    sys.exit(main())
