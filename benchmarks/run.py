"""python benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

One command, one cell, one process.  Builds the cell's program through
the program's normal entry points, makes weights and inputs from the
seed, warms up the cell's own shapes, checks correctness against the
plain reference, measures for --seconds, and prints as its last line of
standard output one JSON object: correct, attempted, failed, metrics,
device (and breakdown with --trace 1).  With --trace 0 the metrics are
the cell's end-to-end metrics, with --trace 1 its per-layer metrics.

Exits with a code other than 0, and prints no result, when jax finds no
TPU or another number of chips than the cell asks for.  Never starts a
child process.  Cells, configurations, jobs and metrics are data:
BENCHMARK.json and the files it names (harness.py, README.md).
"""

import time

_CLOCK_START = time.perf_counter()   # setup_s counts from here

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import harness

    try:
        harness.run_cell(os.path.dirname(here), args.workload, args.seed,
                         args.seconds, args.trace,
                         clock_start=_CLOCK_START)
    except harness.Refused as e:
        print("benchmarks/run.py: %s" % e, file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
