"""One run of one benchmark cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Prints one JSON object as the last line of standard output.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()      # set-up is counted from here

import argparse             # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402

from benchmark import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             params=None):
    """One run of the named cell; returns the result line.  ``params`` is
    merged into the configuration's builder parameters (the control on
    the chip switches a lower-precision path on with it; the command
    line has no such option)."""
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, workload)
    if params:
        config["params"].update(params)
    # the configuration's switches, before the program is imported
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = str(v)
    job = harness.Job(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=seconds, trace=trace, t_start=_T0)
    job.device = harness.require_accelerator(int(cell["chips"]))
    ctx = harness.load_module("kinds", traffic["kind"]).run(job)
    return harness.result_line(bench, job, ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.emit(run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
