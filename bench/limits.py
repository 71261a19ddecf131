#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on this machine's
chip: for each seed, one process runs the cell's set-up, a short window at
its own load (compiles in it bypass the persistent cache, as in a run of
the benchmark), and then reads the compared
number twice, for the program and for the control (the reference one
precision lower in the program's place, as the cell's driver's ``CONTROL``
says: bfloat16 for a float32 streamed graph, fp8 for the bfloat16 LM).
One JSON line per seed.

    python3 bench/limits.py --workload dpd.reconf --seconds 2 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.resolve(harness.load_spec(), args.workload)
    try:
        harness.device_info(cell.chips, require_chip=True)
    except harness.NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 1
    driver = harness.load_module("drivers", cell.config["driver"])
    for seed in args.seeds:
        harness.enable_compile_cache()
        sess = driver.Session(cell, seed)
        harness.disable_compile_cache()
        calls, _ = harness.window(sess, args.seconds)
        sess.release()
        gc.collect()
        (name, program, limit), = sess.check(calls)
        (_, control, _), = sess.check(calls, **driver.CONTROL)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "calls": len(calls), "number": name,
                          "program": program, "control": control,
                          "limit": limit}), flush=True)
        del sess
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
