"""Executor hot-path benchmarks (trace-time specialization PR).

Proves the two tentpole claims on real paper graphs:

  * static mode with ``ExecutionPlan(specialize=True)`` — transient-
    channel register allocation + phase-specialized ring offsets — vs the
    dynamic-cursor baseline (``specialize=False``), on the DPD network
    (paper §4.2, the dynamic-rate showcase) and motion detection (paper
    §4.1, the delay-channel showcase).  Target: >= 1.5x on DPD.
  * dynamic mode with ``ExecutionPlan(multi_firing=True)`` — occupancy-
    bounded fori_loop firing — reaches quiescence in strictly fewer
    sweeps than the one-firing-per-actor-per-sweep baseline, with
    bit-identical final states.

Timing interleaves baseline/specialized reps and takes medians so shared-
machine noise hits both arms equally.  Besides the CSV rows, writes
``BENCH_executors.json``: ``{name, us_per_call, tokens_per_s}`` per
executor x graph (tokens = MoC source-channel tokens: signal blocks for
DPD, frames for MD) so later PRs can track the throughput trajectory.

The ``exec_*_dynamic_guarded`` rows time ``ExecutionPlan(guards=True)``
(the in-kernel health layer) against the unguarded dynamic executor and
inline-check its contract: a clean guarded run must be bit-identical and
report no faults.  Their tok/s rides the same calibrated regression
floor as every other row once committed to the baseline JSON.

The ``exec_*_dynamic_traced`` rows do the same for the firing-level
trace ring (``ExecutionPlan(trace=True)``): bit-identical states/sweeps,
recorded firings equal to ``fire_counts``, and the overhead gated by the
committed baseline like every other timing row.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ExecutionPlan
from repro.graphs.factories import states_identical as _states_identical

Row = Tuple[str, float, str]

JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "BENCH_executors.json")


def _interleaved_medians(fns: Dict[str, Callable[[], None]],
                         reps: int) -> Dict[str, float]:
    """Median seconds per call, reps interleaved across all candidates."""
    for fn in fns.values():  # compile + warm
        fn()
    times: Dict[str, List[float]] = {k: [] for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            times[k].append(time.perf_counter() - t0)
    return {k: float(np.median(v)) for k, v in times.items()}


def bench_executors(fast: bool = False,
                    json_path: str = JSON_PATH) -> List[Row]:
    from repro.graphs import dpd, motion_detection

    reps = 3 if fast else 9
    rows: List[Row] = []
    records: List[Dict] = []

    def record(name: str, dt: float, tokens: int, derived: str) -> None:
        rows.append((name, dt * 1e6, derived))
        records.append({"name": name, "us_per_call": round(dt * 1e6, 1),
                        "tokens_per_s": round(tokens / dt, 1)})

    # ------------------------------------------------------------------ #
    # Graph workloads: (name, network, n_iterations, tokens/run, unit str).
    # ------------------------------------------------------------------ #
    if fast:
        workloads = [
            ("dpd", dpd.bench_workload(4, block_l=1024), 4, 4,
             lambda dt: f"{4 * 1024 / dt / 1e6:.1f} Msamples/s"),
            ("md", motion_detection.bench_workload(8, rate=4), 2, 8,
             lambda dt: f"{8 / dt:.0f} fps"),
        ]
    else:
        workloads = [
            ("dpd", dpd.bench_workload(8), 8, 8,
             lambda dt: f"{8 * dpd.BLOCK_L / dt / 1e6:.1f} Msamples/s"),
            ("md", motion_detection.bench_workload(24, rate=4), 6, 24,
             lambda dt: f"{24 / dt:.0f} fps"),
        ]

    for gname, net, n_iter, tokens, fmt in workloads:
        # -- static executors: baseline vs specialized (+ donation) ------ #
        st = net.init_state()
        run_base = net.compile(mode="static", n_iterations=n_iter,
                               specialize=False)
        run_spec = net.compile(mode="static", n_iterations=n_iter,
                               specialize=True)
        med = _interleaved_medians({
            "base": lambda: jax.block_until_ready(run_base.run(st).state),
            "spec": lambda: jax.block_until_ready(run_spec.run(st).state),
        }, reps)
        record(f"exec_{gname}_static_baseline", med["base"], tokens,
               fmt(med["base"]))
        record(f"exec_{gname}_static_specialized", med["spec"], tokens,
               fmt(med["spec"]))
        speedup = med["base"] / med["spec"]
        rows.append((f"exec_{gname}_static_specialization_speedup", 0.0,
                     f"{speedup:.2f}x (target >= 1.5x on dpd)"))

        # Donated run: every call consumes a fresh state (in-place buffers).
        # Deep-copy each pooled state: init_state shares the staged source
        # slab across states, and donating it once would kill the pool.
        run_don = net.compile(mode="static", n_iterations=n_iter,
                              specialize=True, donate=True)
        pool = [jax.tree.map(jnp.copy, net.init_state())
                for _ in range(reps + 1)]
        med_d = _interleaved_medians(
            {"don": lambda: jax.block_until_ready(run_don.run(pool.pop()).state)},
            reps)
        record(f"exec_{gname}_static_specialized_donated", med_d["don"],
               tokens, fmt(med_d["don"]))

        # -- dynamic executors: single- vs multi-firing sweeps ----------- #
        # donate=False pins the measurement to the executor itself: the
        # "auto" default would donate run(None)'s private copy on graphs
        # passing the buffered-bytes heuristic, adding a tree copy to
        # every timed call.
        dyn_base = net.compile(ExecutionPlan(mode="dynamic",
                                             multi_firing=False,
                                             donate=False))
        dyn_mf = net.compile(ExecutionPlan(mode="dynamic", multi_firing=True,
                                           donate=False))
        dyn_grd = net.compile(ExecutionPlan(mode="dynamic", multi_firing=True,
                                            donate=False, guards=True))
        dyn_trc = net.compile(ExecutionPlan(mode="dynamic", multi_firing=True,
                                            donate=False, trace=True))
        rb, rm, rg = dyn_base.run(), dyn_mf.run(), dyn_grd.run()
        rt = dyn_trc.run()
        sb, cb, swb = rb.state, rb.fire_counts, rb.sweeps
        sm, cm, swm = rm.state, rm.fire_counts, rm.sweeps
        identical = (_states_identical(sb, sm) and
                     {k: int(v) for k, v in cb.items()} ==
                     {k: int(v) for k, v in cm.items()})
        # Health-guard contract: a clean guarded run is bit-identical to
        # the unguarded one and reports no faults.
        guard_clean = (_states_identical(sm, rg.state)
                       and int(swm) == int(rg.sweeps)
                       and rg.diagnostics.ok)
        # Trace contract: a traced run is bit-identical to the untraced
        # one, and the recorded firings agree with fire_counts.
        trace_clean = (_states_identical(sm, rt.state)
                       and int(swm) == int(rt.sweeps)
                       and rt.trace.firing_counts() ==
                       {k: int(v) for k, v in rt.fire_counts.items()})
        med = _interleaved_medians({
            "base": lambda: jax.block_until_ready(dyn_base.run().state),
            "mf": lambda: jax.block_until_ready(dyn_mf.run().state),
            "grd": lambda: jax.block_until_ready(dyn_grd.run().state),
            "trc": lambda: jax.block_until_ready(dyn_trc.run().state),
        }, reps)
        record(f"exec_{gname}_dynamic_baseline", med["base"], tokens,
               f"{int(swb)} sweeps")
        record(f"exec_{gname}_dynamic_multi_firing", med["mf"], tokens,
               f"{int(swm)} sweeps")
        record(f"exec_{gname}_dynamic_guarded", med["grd"], tokens,
               f"{med['grd'] / med['mf']:.2f}x of unguarded, "
               f"clean + bit-identical: {guard_clean}")
        record(f"exec_{gname}_dynamic_traced", med["trc"], tokens,
               f"{med['trc'] / med['mf']:.2f}x of untraced, "
               f"{rt.trace.n_events} events, bit-identical: {trace_clean}")
        rows.append((f"exec_{gname}_dynamic_sweep_reduction", 0.0,
                     f"{int(swb)} -> {int(swm)} sweeps "
                     f"(strictly fewer: {int(swm) < int(swb)}), "
                     f"bit-identical states: {identical}"))

    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)
        f.write("\n")
    rows.append(("exec_bench_json", 0.0, json_path))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, us, derived in bench_executors():
        print(f"{name},{us:.1f},{derived}")
