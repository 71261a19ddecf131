#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload dpd.reconf --seed 7 --seconds 40 --trace 0

Set-up (building, weights, warm-up of every shape the window uses, with
compilation on a cell's first run in a checkout), then a window of
``--seconds`` of back-to-back calls (``--trace 1``: a shorter window under
the profiler, reporting the per-layer metrics), then the comparison with
the plain reference.  The last line of standard output is the result, as
JSON; the numbers compared, each with its limit, are the last lines of
standard error and the ``checks`` key of the result.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.resolve(harness.load_spec(), args.workload)
    try:
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
