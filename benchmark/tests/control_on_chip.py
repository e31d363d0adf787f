"""The control on the chip: the program itself, with a lower-precision
path of its own switched on, run through the harness at the cell's own
size.  ``correct`` has to come out false.

    python3 -m benchmark.tests.control_on_chip --workload <name> --seed <n> \
        [--params '{"bf16_histograms": true}'] [--seconds <run_seconds>]

``--params`` is merged into the configuration's builder parameters (the
default is the tree builders' bfloat16 histogram path, the precision
below the float32 the configurations state).  The window is the cell's
own (``--seconds`` defaults to ``run_seconds``: the runtime guard scales
with it, so a shorter one lets the clock cut the job).  Prints the run's
usual last line; exits 0 when the control came out as not correct, 1
when it passed as correct.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import harness, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--params", default='{"bf16_histograms": true}')
    args = ap.parse_args(argv)
    params = json.loads(args.params)
    seconds = args.seconds or float(harness.load_benchmark()["run_seconds"])
    line = run.run_cell(args.workload, args.seed, seconds, False, params)
    line["notes"]["control"] = params
    harness.emit(line)
    return 1 if line["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
