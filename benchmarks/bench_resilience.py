"""Resilience benchmarks: what the PR 10 serving-resilience layer costs
and what it buys, under open-loop Poisson overload with injected faults.

Rows (all on the admission/decode/retire serving network of
``repro.graphs.serving``, host-dynamic plan, ``eos_id=None`` so budgets
— not token values — decide retirement and every count is seed-
deterministic):

  * ``resil_baseline`` — no deadlines, unbounded queue: every request
    completes; the throughput yardstick.
  * ``resil_deadline_light`` / ``resil_deadline_tight`` — per-request
    deadlines of ``arrival + allowance`` under a bursty Poisson trace.
    Expired or queue-overflowed requests retire as rate-0 shed firings
    (status timeout/shed); *goodput* is completed-request tokens only,
    and each cell reports p50/p99 completed-request latency in decode
    steps (seed-exact, gated as structure fields).
    The acceptance claim is the proportionality row: the goodput
    fraction tracks 1 - shed fraction, i.e. shedding costs the work
    shed and nothing more (no head-of-line blocking from doomed
    requests).
  * ``resil_quarantine`` — one poisoned request (out-of-domain prompt,
    DOMAIN write guard) under ``generate(on_fault="quarantine")``: the
    cost of fault-map + survivor re-run, vs the survivors run clean.
  * ``resil_ckpt_off`` / ``resil_ckpt_every_2`` / ``resil_ckpt_every_8``
    — ``run_checkpointed`` durability cadence sweep: segmented
    execution plus CRC'd atomic snapshots vs one plain ``run()``.

Timing rows are medians of interleaved reps (same discipline as
``bench_executors``); every structural field (status counts, shed/
goodput fractions, sweeps, segments) is exact and gates in
``check_regression.py``.  CPU caveat: numbers measure scheduling + I/O
structure, not accelerator kernel perf.

Writes ``BENCH_resilience.json`` (``name``/``us_per_call``/
``tokens_per_s`` + structure fields) for the bench-regression gate.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Tuple

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core import ExecutionPlan
from repro.graphs.serving import (STATUS_OK, STATUS_SHED, STATUS_TIMEOUT,
                                  poisson_trace)
from repro.models import init_params
from repro.serve import ActorEngine, Request, ServeConfig

Row = Tuple[str, float, str]

JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_resilience.json")

POISON = -(2 ** 20)


def _status_counts(status: np.ndarray) -> Dict[str, int]:
    return {"n_ok": int((status == STATUS_OK).sum()),
            "n_timeout": int((status == STATUS_TIMEOUT).sum()),
            "n_shed": int((status == STATUS_SHED).sum())}


def bench_resilience(fast: bool = False,
                     json_path: str = JSON_PATH) -> List[Row]:
    from benchmarks.bench_executors import _interleaved_medians

    reps = 3 if fast else 5
    rows: List[Row] = []
    records: List[Dict] = []

    def record(name: str, dt: float, tokens: int, derived: str,
               **structure) -> None:
        rows.append((name, dt * 1e6, derived))
        records.append({"name": name, "us_per_call": round(dt * 1e6, 1),
                        "tokens_per_s": round(tokens / dt, 1), **structure})

    # ---- workload: Poisson overload, variable budgets ------------------
    if fast:
        R, scfg = 6, ServeConfig(batch_size=2, max_prompt=8, max_new=6,
                                 eos_id=None)
    else:
        R, scfg = 12, ServeConfig(batch_size=2, max_prompt=12, max_new=8,
                                  eos_id=None)
    cfg = smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    budgets = np.array([scfg.max_new if i % 2 == 0 else 2
                        for i in range(R)])
    reqs = [Request(prompt=rng.integers(1, cfg.vocab,
                                        size=scfg.max_prompt - 1 - (i % 3))
                    .astype(np.int32), max_new=int(budgets[i]))
            for i in range(R)]
    # B=2 slots with a fast arrival trace: a deep backlog forms, so tight
    # deadlines expire waiting requests instead of merely trimming tails.
    arrivals = poisson_trace(R, rate=2.0, seed=7)
    eng = ActorEngine(cfg, params, scfg)

    def staged_run(deadlines):
        net = eng.build_network(reqs, arrivals=arrivals, deadlines=deadlines)
        prog = net.compile(eng.plan)
        res = prog.run()
        sink = prog.collect("retire", res.state)
        return prog, res, sink

    def goodput_tokens(sink) -> int:
        status = np.asarray(sink["status"])
        lens = np.asarray(sink["lens"])
        return int(lens[status == STATUS_OK].sum())

    cells = {"baseline": None}
    if fast:
        cells["deadline_light"] = arrivals + 24
        cells["deadline_tight"] = arrivals + 8
    else:
        cells["deadline_light"] = arrivals + 40
        cells["deadline_tight"] = arrivals + 12

    progs, telem = {}, {}
    for name, dls in cells.items():
        prog, res, sink = staged_run(dls)
        progs[name] = prog
        telem[name] = (res, sink)
    med = _interleaved_medians(
        {name: (lambda p=progs[name]: jax.block_until_ready(p.run().state))
         for name in cells}, reps)

    base_good = goodput_tokens(telem["baseline"][1])
    for name in cells:
        res, sink = telem[name]
        status = np.asarray(sink["status"])
        counts = _status_counts(status)
        good = goodput_tokens(sink)
        shed_frac = (counts["n_timeout"] + counts["n_shed"]) / R
        # Completed-request latency in decode steps (admission -> retire);
        # seed-exact, so both percentiles gate as structure fields.
        lat = np.asarray(sink["lat"])[status == STATUS_OK]
        record(f"resil_{name}", med[name], max(good, 1),
               f"{counts['n_ok']}/{R} completed, goodput {good} tokens "
               f"over {int(res.sweeps)} sweeps, p50/p99 "
               f"{int(np.percentile(lat, 50))}/{int(np.percentile(lat, 99))}"
               " steps",
               sweeps=int(res.sweeps), total_requests=R, **counts,
               goodput_tokens=good,
               shed_fraction=round(shed_frac, 3),
               goodput_fraction=round(good / base_good, 3),
               p50_latency_steps=int(np.percentile(lat, 50)),
               p99_latency_steps=int(np.percentile(lat, 99)))
    lt = next(r for r in records if r["name"] == "resil_deadline_tight")
    rows.append(("resil_goodput_proportional", 0.0,
                 f"shed fraction {lt['shed_fraction']} -> goodput fraction "
                 f"{lt['goodput_fraction']}; degrades proportionally: "
                 f"{abs((1 - lt['shed_fraction']) - lt['goodput_fraction']) <= 0.35}"))

    # ---- quarantine: fault-map + survivor re-run cost ------------------
    qr = min(4, R)
    qreqs = [Request(prompt=np.asarray(r.prompt), max_new=r.max_new)
             for r in reqs[:qr]]
    bad = list(qreqs)
    bad[1] = Request(prompt=np.full(4, POISON, np.int32),
                     max_new=int(budgets[1]))
    geng = ActorEngine(cfg, params, scfg,
                       plan=ExecutionPlan(mode="dynamic", guards=True))
    qmed = _interleaved_medians({
        "clean": lambda: geng.generate(
            [q for j, q in enumerate(qreqs) if j != 1]),
        "quarantine": lambda: geng.generate(bad, on_fault="quarantine"),
    }, reps)
    out = geng.generate(bad, on_fault="quarantine")
    surv_tokens = sum(len(r.tokens) for r in out)
    record("resil_survivors_clean", qmed["clean"], max(surv_tokens, 1),
           f"{qr - 1} survivors run clean (quarantine oracle)",
           requests=qr - 1, survivor_tokens=surv_tokens)
    record("resil_quarantine", qmed["quarantine"], max(surv_tokens, 1),
           f"1 poisoned of {qr} quarantined in {geng.last_retries} "
           f"retry(ies), {surv_tokens} survivor tokens",
           requests=qr, n_fault=geng.last_status.count("fault"),
           retries=geng.last_retries, survivor_tokens=surv_tokens)
    rows.append(("resil_quarantine_overhead", 0.0,
                 f"{qmed['quarantine'] / qmed['clean']:.2f}x vs survivors "
                 "clean (fault run + rebuild + re-run)"))

    # ---- durable checkpoint cadence sweep ------------------------------
    net = eng.build_network(reqs, arrivals=arrivals)
    prog = net.compile(eng.plan)
    ref = prog.run()
    sweeps = int(ref.sweeps)
    total_tokens = int(budgets.sum())
    ckroot = tempfile.mkdtemp(prefix="bench_resil_ck_")
    cprog = net.compile(eng.plan)     # segment twins cache inside
    try:
        def ckpt_run(every, tag):
            d = os.path.join(ckroot, tag)
            shutil.rmtree(d, ignore_errors=True)
            return jax.block_until_ready(
                cprog.run_checkpointed(d, every_sweeps=every).state)

        cad = {"off": lambda: jax.block_until_ready(prog.run().state),
               "every_2": lambda: ckpt_run(2, "e2"),
               "every_8": lambda: ckpt_run(8, "e8")}
        cmed = _interleaved_medians(cad, reps)
        record("resil_ckpt_off", cmed["off"], total_tokens,
               f"plain run, {sweeps} sweeps", sweeps=sweeps)
        for every in (2, 8):
            segs = -(-sweeps // every)
            record(f"resil_ckpt_every_{every}", cmed[f"every_{every}"],
                   total_tokens,
                   f"{segs} segments, CRC'd snapshot each",
                   sweeps=sweeps, segments=segs, every_sweeps=every)
        rows.append(("resil_ckpt_overhead", 0.0,
                     f"every_2 {cmed['every_2'] / cmed['off']:.2f}x, "
                     f"every_8 {cmed['every_8'] / cmed['off']:.2f}x vs "
                     "plain run (segment re-entry + snapshot I/O)"))
    finally:
        shutil.rmtree(ckroot, ignore_errors=True)

    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)
        f.write("\n")
    rows.append(("resil_bench_json", 0.0, json_path))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for name, us, derived in bench_resilience(fast=fast):
        print(f"{name},{us:.1f},{derived}")
