"""Bench-regression gate: compare a fresh ``--fast`` run to the committed
``BENCH_executors.json`` / ``BENCH_megakernel.json`` /
``BENCH_serving.json`` baselines.

Two kinds of comparison, per record (keyed by ``name``):

  * **structure fields** — everything except the timing pair
    (``sweeps``, ``cores``, ``scratch_bytes``, ``shared_scratch_bytes``,
    ``forwarded_fifos``, and any future field) — compared **exactly**:
    a sweep-count change is a scheduler-semantics change and a scratch /
    forwarding-count drift is a memory-footprint regression, not noise;
    either fails the gate outright, as does a baseline row missing from
    the fresh run;
  * **tokens_per_s** — compared against a ``--floor`` (default 0.85x)
    after machine-speed calibration: the committed baselines were
    produced on one container and CI runners differ in absolute speed,
    so the gate normalizes every per-row fresh/baseline ratio by the
    **median ratio across all rows of the suite** (the machine-speed
    estimate, shared by every executor) and flags rows whose calibrated
    ratio drops below the floor.  This catches *relative* regressions —
    one executor slowing down against the fleet — which is the only
    signal absolute tok/s can carry across machines; on the baseline
    machine the median is ~1 and the gate degenerates to the plain
    0.85x floor.

Shared-CPU timing noise (±40% between runs, see the verify skill) would
make one-shot throughput floors flake, so a row only **fails** the gate
when it stays under the floor in every one of ``--attempts`` fresh runs
(default 3, early exit on a clean run): genuine regressions are
persistent, noise bounces back.  Structure mismatches are deterministic
and fail on the first attempt.

The guards-on rows (``exec_*_dynamic_guarded``,
``mega_*_megakernel_guarded``) are gated exactly like every other row:
their tok/s must hold the calibrated floor, so a PR that bloats the
health-guard overhead fails CI even if the unguarded paths are intact.

Prints a markdown comparison table (also appended to
``$GITHUB_STEP_SUMMARY`` when set, so the job summary shows the full
table) and exits non-zero on any regression.

Invocation (CI and local): ``PYTHONPATH=src python
benchmarks/check_regression.py --fast``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
from typing import Dict, List

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITES = ("BENCH_executors.json", "BENCH_megakernel.json",
          "BENCH_resilience.json", "BENCH_serving.json",
          "BENCH_shard.json")
TIMING_FIELDS = ("us_per_call", "tokens_per_s")


def _load(path: str) -> Dict[str, dict]:
    with open(path) as f:
        return {r["name"]: r for r in json.load(f)}


def _fresh_run(fast: bool, out_dir: str) -> Dict[str, Dict[str, dict]]:
    """Run both bench suites into ``out_dir``; returns suite -> records."""
    from benchmarks.bench_executors import bench_executors
    from benchmarks.bench_megakernel import bench_megakernel
    from benchmarks.bench_resilience import bench_resilience
    from benchmarks.bench_serving import bench_serving
    from benchmarks.bench_shard import bench_shard

    paths = {s: os.path.join(out_dir, s) for s in SUITES}
    bench_executors(fast=fast, json_path=paths["BENCH_executors.json"])
    bench_megakernel(fast=fast, json_path=paths["BENCH_megakernel.json"])
    bench_resilience(fast=fast, json_path=paths["BENCH_resilience.json"])
    bench_serving(fast=fast, json_path=paths["BENCH_serving.json"])
    bench_shard(fast=fast, json_path=paths["BENCH_shard.json"])
    return {s: _load(p) for s, p in paths.items()}


def compare(base: Dict[str, dict], fresh: Dict[str, dict],
            floor: float) -> Dict[str, dict]:
    """Per-row verdicts for one suite in one attempt.

    Returns ``name -> {status, reason, base, fresh, calibrated}`` where
    status is ``ok`` / ``slow`` (under the calibrated floor) /
    ``structure`` / ``missing``.
    """
    ratios = {n: fresh[n]["tokens_per_s"] / base[n]["tokens_per_s"]
              for n in base if n in fresh and base[n].get("tokens_per_s")}
    machine = statistics.median(ratios.values()) if ratios else 1.0
    out: Dict[str, dict] = {}
    for name, brec in base.items():
        frec = fresh.get(name)
        if frec is None:
            out[name] = dict(status="missing", base=brec["tokens_per_s"],
                             fresh=None, calibrated=None,
                             reason="row missing from fresh run")
            continue
        b_struct = {k: v for k, v in brec.items()
                    if k not in TIMING_FIELDS and k != "name"}
        f_struct = {k: v for k, v in frec.items()
                    if k not in TIMING_FIELDS and k != "name"}
        calibrated = ratios.get(name, 1.0) / machine
        rec = dict(status="ok", reason="", base=brec["tokens_per_s"],
                   fresh=frec["tokens_per_s"], calibrated=calibrated,
                   machine=machine)
        if b_struct != f_struct:
            rec.update(status="structure",
                       reason=f"structure fields changed "
                              f"{b_struct} -> {f_struct}")
        elif calibrated < floor:
            rec.update(status="slow",
                       reason=f"tokens_per_s {frec['tokens_per_s']} is "
                              f"{calibrated:.2f}x of baseline "
                              f"{brec['tokens_per_s']} (machine-calibrated; "
                              f"floor {floor}x)")
        out[name] = rec
    for name in set(fresh) - set(base):
        out[name] = dict(status="new", reason="", base=None,
                         fresh=fresh[name]["tokens_per_s"], calibrated=None)
    return out


def _merge(attempts: List[Dict[str, dict]]) -> Dict[str, dict]:
    """Best verdict per row across attempts: ``slow`` must persist in
    every attempt to stick; structure/missing verdicts are deterministic
    drifts, so they stick from the first attempt a row shows one — a
    later lucky rerun must NOT launder them back to ok."""
    merged: Dict[str, dict] = {}
    for att in attempts:
        for name, rec in att.items():
            cur = merged.get(name)
            if cur is None:
                merged[name] = dict(rec)
            elif cur["status"] in ("structure", "missing"):
                continue                      # sticky: deterministic drift
            elif rec["status"] in ("structure", "missing"):
                merged[name] = dict(rec)      # upgrade slow/ok -> sticky
            elif rec["status"] == "ok" or (
                    cur["status"] == "slow"
                    and (rec.get("calibrated") or 0)
                    > (cur.get("calibrated") or 0)):
                merged[name] = dict(rec)
    return merged


def render(suite: str, merged: Dict[str, dict], n_attempts: int) -> str:
    lines = [f"### {suite} ({n_attempts} attempt(s))", "",
             "| row | baseline tok/s | fresh tok/s | calibrated | status |",
             "|---|---|---|---|---|"]
    for name in sorted(merged):
        r = merged[name]
        cal = f"{r['calibrated']:.2f}x" if r.get("calibrated") else "—"
        status = {"ok": "ok", "new": "new (no baseline)",
                  "slow": "REGRESSION", "structure": "STRUCTURE",
                  "missing": "MISSING"}[r["status"]]
        lines.append(f"| {name} | {r['base'] if r['base'] is not None else '—'}"
                     f" | {r['fresh'] if r['fresh'] is not None else '—'}"
                     f" | {cal} | {status} |")
    return "\n".join(lines) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="fast bench configuration (the CI mode)")
    ap.add_argument("--floor", type=float, default=0.85,
                    help="calibrated tok/s floor (default 0.85)")
    ap.add_argument("--attempts", type=int, default=3,
                    help="max fresh runs; a throughput row fails only if "
                         "under the floor in all of them (default 3)")
    ap.add_argument("--baseline-dir", default=REPO_ROOT,
                    help="directory holding the committed BENCH_*.json")
    ap.add_argument("--keep-fresh", default=None, metavar="DIR",
                    help="also write each attempt's fresh BENCH_*.json "
                         "under DIR/attempt<N>/ (CI uploads these as the "
                         "fresh-run artifact)")
    args = ap.parse_args()

    baselines = {s: _load(os.path.join(args.baseline_dir, s)) for s in SUITES}
    attempts: Dict[str, List[Dict[str, dict]]] = {s: [] for s in SUITES}
    for i in range(max(1, args.attempts)):
        if args.keep_fresh:
            out_dir = os.path.join(args.keep_fresh, f"attempt{i + 1}")
            os.makedirs(out_dir, exist_ok=True)
            fresh = _fresh_run(args.fast, out_dir)
        else:
            with tempfile.TemporaryDirectory() as tmp:
                fresh = _fresh_run(args.fast, tmp)
        clean = True
        retryable = False
        for s in SUITES:
            verdicts = compare(baselines[s], fresh[s], args.floor)
            attempts[s].append(verdicts)
            statuses = {v["status"] for v in verdicts.values()}
            clean &= statuses <= {"ok", "new"}
            retryable |= "slow" in statuses
        # Retrying only helps throughput noise; structure/missing drifts
        # are deterministic (and sticky in _merge), so don't burn two
        # more full bench runs on them.
        if clean or not retryable:
            break

    failures: List[str] = []
    report = []
    for s in SUITES:
        merged = _merge(attempts[s])
        failures += [f"{s}: {n}: {r['reason']}"
                     for n, r in sorted(merged.items())
                     if r["status"] not in ("ok", "new")]
        report.append(render(s, merged, len(attempts[s])))
    text = "\n".join(report)
    print(text)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write("## Bench-regression gate\n\n" + text + "\n")
    if failures:
        print("REGRESSIONS:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"bench-regression gate: OK ({args.floor}x calibrated floor, "
          f"{len(attempts[SUITES[0]])} attempt(s))")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
