"""Traced fast bench -> Chrome trace-event JSON, validated (CI `trace` job).

Runs the fast DPD workload once per traceable backend (host dynamic,
single-core megakernel, grid k=2) with ``ExecutionPlan(trace=True)``,
exports each run's firing trace with ``Trace.to_perfetto``, then
validates every document against the Chrome trace-event schema
(``repro.core.validate_chrome_trace``: required keys per phase type,
monotonic timestamps per track) and cross-checks the exported per-actor
firing events against ``RunResult.fire_counts``.

Exits non-zero on any validation problem, so CI fails when the export
format drifts.  The ``.trace.json`` files land in ``--out`` (default
``results/``) and are uploaded as a CI artifact — drag one into
https://ui.perfetto.dev to inspect the firing schedule.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core import ExecutionPlan, validate_chrome_trace
from repro.graphs.factories import make_dpd

BACKENDS = {
    "dynamic": lambda: ExecutionPlan(mode="dynamic", donate=False,
                                     trace=True),
    "megakernel": lambda: ExecutionPlan(mode="megakernel", specialize=False,
                                        trace=True),
    "grid2": lambda: ExecutionPlan(mode="megakernel", specialize=False,
                                   cores=2, trace=True),
}


def export_traces(out_dir: str) -> List[str]:
    """Write one validated ``dpd_<backend>.trace.json`` per backend;
    returns the list of validation problems (empty == all clean)."""
    os.makedirs(out_dir, exist_ok=True)
    net, _ = make_dpd(n_firings=4, block_l=256)
    problems: List[str] = []
    for backend, plan in BACKENDS.items():
        res = net.compile(plan()).run()
        path = os.path.join(out_dir, f"dpd_{backend}.trace.json")
        res.trace.to_perfetto(path)
        with open(path) as f:
            doc = json.load(f)
        for p in validate_chrome_trace(doc):
            problems.append(f"{backend}: {p}")
        names = res.trace.actor_names
        fired = {nm: 0 for nm in names}
        for ev in doc["traceEvents"]:
            if ev["ph"] == "X":
                fired[names[ev["tid"] - 1]] += 1
        want = {k: int(v) for k, v in res.fire_counts.items()}
        if fired != want:
            problems.append(f"{backend}: exported firing events {fired} "
                            f"!= fire_counts {want}")
        print(f"{backend}: {res.trace.n_events} events, "
              f"{sum(fired.values())} firings -> {path}")
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "results"))
    args = ap.parse_args()
    problems = export_traces(args.out)
    for p in problems:
        print(f"INVALID: {p}", file=sys.stderr)
    print("trace export:", "FAILED" if problems else "ok",
          f"({len(BACKENDS)} backends)")
    return 1 if problems else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
