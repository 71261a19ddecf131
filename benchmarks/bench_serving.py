"""Serving benchmarks: continuous batching on the actor runtime vs the
fixed-batch engine, under a seeded Poisson open-loop arrival trace.

Two serving rows, same request set (variable per-request budgets, so
fixed batches strand idle slots on the short requests while the actor
network re-admits them):

  * ``serve_legacy_fixed_batch`` — ``repro.serve.Engine`` (early-stop
    enabled): groups requests into arrival-order batches, each batch
    holds every slot until its slowest member finishes.  Wall time is
    the measured ``generate`` call; per-request completion latency
    comes from the deterministic queueing timeline in decode-steps
    (batch g starts at max(last member's arrival, batch g-1's finish)).
  * ``serve_actor_continuous`` — the admission/decode/retire network of
    ``repro.graphs.serving`` under the host-dynamic plan, open-loop
    arrivals fed from the trace; latency is the retire sink's
    per-request step count.

Latency percentiles are reported in *steps* (deterministic given the
seeds — token values never matter because ``eos_id=None`` retires by
budget), so they gate as structure fields in ``check_regression.py``
alongside sweep/fire counts; only the tok/s pair is timing.  The
``serve_stream_*`` rows time ``Program.stream`` chunked vs
persistent-feed on the DPD megakernel subnetwork and record the staged
bytes from ``Program.stats()`` — the before/after table of
EXPERIMENTS.md §Serving.  Caveat: CPU numbers measure scheduling
structure (megakernel rows run Pallas interpret mode), not kernel perf.

Writes ``BENCH_serving.json`` (same contract as the other suites:
``name``/``us_per_call``/``tokens_per_s`` plus exact-compare structure
fields) for the bench-regression gate.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Tuple

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core import ExecutionPlan
from repro.graphs.factories import make_dpd
from repro.graphs.serving import poisson_trace
from repro.models import init_params
from repro.serve import ActorEngine, Engine, Request, ServeConfig

Row = Tuple[str, float, str]

JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serving.json")


def _legacy_timeline(arrivals: np.ndarray, budgets: np.ndarray,
                     batch_size: int) -> Tuple[np.ndarray, int]:
    """Deterministic queueing simulation of the fixed-batch engine, in
    decode-steps: batch g admits the next ``batch_size`` requests in
    arrival order, starts at max(its last member's arrival, batch g-1's
    finish), and runs until its slowest member's budget (the early-stop
    loop).  A request's own tokens complete at start + its budget.
    Returns (per-request completion latency in steps, total steps)."""
    order = np.argsort(arrivals, kind="stable")
    lat = np.zeros(len(arrivals), np.int64)
    finish_prev = 0
    total_steps = 0
    for lo in range(0, len(order), batch_size):
        grp = order[lo:lo + batch_size]
        start = max(int(arrivals[grp].max()), finish_prev)
        steps = int(budgets[grp].max())        # prefill + (max-1) decodes
        lat[grp] = start + budgets[grp] - arrivals[grp]
        finish_prev = start + steps
        total_steps += steps
    return lat, total_steps


def bench_serving(fast: bool = False, json_path: str = JSON_PATH) -> List[Row]:
    from benchmarks.bench_executors import _interleaved_medians

    reps = 3 if fast else 5
    rows: List[Row] = []
    records: List[Dict] = []

    def record(name: str, dt: float, tokens: int, derived: str,
               **structure) -> None:
        rows.append((name, dt * 1e6, derived))
        records.append({"name": name, "us_per_call": round(dt * 1e6, 1),
                        "tokens_per_s": round(tokens / dt, 1), **structure})

    # ---- workload: variable budgets + Poisson open-loop arrivals -------
    if fast:
        R, scfg = 6, ServeConfig(batch_size=2, max_prompt=8, max_new=6,
                                 eos_id=None)
    else:
        R, scfg = 12, ServeConfig(batch_size=4, max_prompt=16, max_new=8,
                                  eos_id=None)
    cfg = smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    # Long/short alternation: the idle-slot workload fixed batches waste.
    budgets = np.array([scfg.max_new if i % 2 == 0 else 1 for i in range(R)])
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=scfg.max_prompt
                                        - 1 - (i % 3)).astype(np.int32),
                    max_new=int(budgets[i])) for i in range(R)]
    arrivals = poisson_trace(R, rate=2.0, seed=7)
    total_tokens = int(budgets.sum())

    legacy = Engine(cfg, params, scfg)
    actor = ActorEngine(cfg, params, scfg)
    net = actor.build_network(reqs, arrivals=arrivals)
    prog = net.compile(actor.plan)

    # One telemetry run (the timed reps reuse the compiled program).
    res = prog.run()
    sink = prog.collect("retire", res.state)
    assert int(np.asarray(sink["done"]).sum()) == R, "request starved"
    actor_lat = np.asarray(sink["lat"])
    sweeps = int(res.sweeps)
    decode_fires = int(res.fire_counts["decode"])
    legacy_lat, legacy_steps = _legacy_timeline(arrivals, budgets,
                                                scfg.batch_size)

    med = _interleaved_medians({
        "legacy": lambda: legacy.generate(reqs),
        "actor": lambda: jax.block_until_ready(prog.run().state),
    }, reps)

    p50_l, p99_l = np.percentile(legacy_lat, [50, 99])
    p50_a, p99_a = np.percentile(actor_lat, [50, 99])
    record("serve_legacy_fixed_batch", med["legacy"], total_tokens,
           f"{legacy_steps} steps, p50/p99 latency {p50_l:.0f}/{p99_l:.0f} "
           "steps (queueing timeline)",
           total_tokens=total_tokens, steps=legacy_steps,
           p50_latency_steps=round(float(p50_l), 1),
           p99_latency_steps=round(float(p99_l), 1))
    record("serve_actor_continuous", med["actor"], total_tokens,
           f"{decode_fires} decode firings over {sweeps} sweeps, p50/p99 "
           f"latency {p50_a:.0f}/{p99_a:.0f} steps",
           total_tokens=total_tokens, sweeps=sweeps,
           decode_fires=decode_fires,
           p50_latency_steps=round(float(p50_a), 1),
           p99_latency_steps=round(float(p99_a), 1))
    rows.append(("serve_actor_vs_legacy", 0.0,
                 f"{med['legacy'] / med['actor']:.2f}x sustained tok/s vs "
                 f"fixed batches, beats: {med['actor'] < med['legacy']} "
                 f"(continuous batching re-admits freed slots)"))

    # ---- Program.stream: chunked vs persistent-feed staging ------------
    n_firings, block_l = (8, 128) if fast else (8, 1024)
    dnet, _ = make_dpd(n_firings=n_firings, block_l=block_l, seed=1)
    accel = tuple(n for n in dnet.actors if n not in ("source", "sink"))
    sprog = dnet.compile(ExecutionPlan(mode="megakernel", n_iterations=4,
                                       accelerated=accel, specialize=False))
    sig = np.random.default_rng(0).normal(
        size=(n_firings, 1, 2, block_l)).astype(np.float32)
    feeds = {"f_in": sig}
    smed = _interleaved_medians({
        "chunked": lambda: jax.block_until_ready(
            list(sprog.stream(feeds).values())),
        "persistent": lambda: jax.block_until_ready(
            list(sprog.stream(feeds, persistent=True).values())),
    }, reps)
    sprog.stream(feeds)
    st_c = sprog.stats()
    sprog.stream(feeds, persistent=True)
    st_p = sprog.stats()
    record("serve_stream_chunked", smed["chunked"], n_firings,
           f"{st_c.last_stream_chunks} chunks, "
           f"{st_c.last_stream_staged_bytes_per_chunk} B staged/chunk",
           chunks=st_c.last_stream_chunks,
           staged_bytes_per_chunk=st_c.last_stream_staged_bytes_per_chunk,
           total_staged_bytes=st_c.last_stream_total_staged_bytes)
    record("serve_stream_persistent", smed["persistent"], n_firings,
           f"{st_p.last_stream_staged_bytes_per_chunk} B staged/chunk "
           "(rings stay resident)",
           chunks=st_p.last_stream_chunks,
           staged_bytes_per_chunk=st_p.last_stream_staged_bytes_per_chunk,
           total_staged_bytes=st_p.last_stream_total_staged_bytes)
    rows.append(("serve_stream_staging_cut", 0.0,
                 f"per-chunk staged bytes "
                 f"{st_c.last_stream_staged_bytes_per_chunk} -> "
                 f"{st_p.last_stream_staged_bytes_per_chunk}, reduces: "
                 f"{st_p.last_stream_staged_bytes_per_chunk < st_c.last_stream_staged_bytes_per_chunk}"))

    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)
        f.write("\n")
    rows.append(("serve_bench_json", 0.0, json_path))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for name, us, derived in bench_serving(fast=fast):
        print(f"{name},{us:.1f},{derived}")
