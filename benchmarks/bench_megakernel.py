"""Megakernel benchmarks: the device-resident scheduler vs the host-built
executors, including the grid-parallel multi-core sweeps.

For the two genuinely dynamic-rate paper graphs — DPD (rate-0 branch
firings) and MoE-as-actors (idle experts) — times the persistent-Pallas
megakernel (``ExecutionPlan(mode=MEGAKERNEL)``, interpret mode on CPU)
against the token-driven dynamic executor it is bit-identical to and the
specialized static executor, then sweeps the grid partition counts
(``cores`` in 1/2/4): per-core-count round (sweep) counts, tok/s, and
the private-vs-shared ring byte split from ``Program.stats``.

Bit-identity is *checked inline* (states, fire counts — and sweeps for
the single-core kernel) so a silent divergence fails the bench contract,
exactly like the dynamic sweep-reduction rows in bench_executors.
Besides the CSV rows, writes ``BENCH_megakernel.json``: ``{name,
us_per_call, tokens_per_s}`` per executor x graph, with ``sweeps`` /
``cores`` / ``scratch_bytes`` / ``shared_scratch_bytes`` /
``forwarded_fifos`` structure fields on the kernel rows (compared
exactly by ``benchmarks/check_regression.py`` — a scratch or
forwarding regression fails CI like a sweep-count drift does).  The
``mega_*_megakernel_guarded`` row times the in-kernel health layer
(``ExecutionPlan(guards=True)``) against the unguarded kernel, inline-
checking that the clean guarded run stays bit-identical and fault-free.
The ``mega_*_megakernel_traced`` row does the same for the in-kernel
trace ring (``ExecutionPlan(trace=True)``): a traced run must stay
bit-identical, its recorded firings must equal ``fire_counts``, and its
overhead is gated by the committed baseline.

Caveat printed with the numbers: on CPU the megakernel runs in Pallas
*interpret* mode — the comparison measures the scheduling structure, not
a compiled-kernel win, and the grid partition loop runs sequentially
(fixed partition-order tie-break), so multi-core rows measure the
partitioned schedule's overhead, not a parallel speedup; the Mosaic /
Megacore path is a ROADMAP open item.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Tuple

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from repro.core import MEGAKERNEL, ExecutionPlan
from repro.graphs.factories import make_dpd, make_moe, states_identical

Row = Tuple[str, float, str]

GRID_CORES = (1, 2, 4)

JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_megakernel.json")


def bench_megakernel(fast: bool = False,
                     json_path: str = JSON_PATH) -> List[Row]:
    from benchmarks.bench_executors import _interleaved_medians

    reps = 3 if fast else 7
    rows: List[Row] = []
    records: List[Dict] = []

    def record(name: str, dt: float, tokens: int, derived: str,
               **structure) -> None:
        rows.append((name, dt * 1e6, derived))
        records.append({"name": name, "us_per_call": round(dt * 1e6, 1),
                        "tokens_per_s": round(tokens / dt, 1), **structure})

    if fast:
        workloads = [
            ("dpd", *make_dpd(n_firings=4, block_l=512, seed=1), 4),
            ("moe", *make_moe(n_firings=3, n_tokens=16, d_model=32), 3),
        ]
    else:
        workloads = [
            ("dpd", *make_dpd(n_firings=6, block_l=4096, seed=1), 6),
            ("moe", *make_moe(n_firings=4, n_tokens=64, d_model=64,
                              d_ff=128), 4),
        ]

    for gname, net, n_iter, tokens in workloads:
        # donate=False: time the executors, not the auto-donation copy.
        dyn = net.compile(ExecutionPlan(mode="dynamic", donate=False))
        static = net.compile(mode="static", n_iterations=n_iter,
                             donate=False)
        grid = {c: net.compile(ExecutionPlan(mode=MEGAKERNEL, cores=c))
                for c in GRID_CORES}
        mega = grid[1]
        guarded = net.compile(ExecutionPlan(mode=MEGAKERNEL, guards=True))
        traced = net.compile(ExecutionPlan(mode=MEGAKERNEL, trace=True))

        rd = dyn.run()
        grid_runs = {c: p.run() for c, p in grid.items()}
        rm = grid_runs[1]
        rg = guarded.run()
        rt = traced.run()
        guard_clean = (states_identical(rm.state, rg.state)
                       and int(rm.sweeps) == int(rg.sweeps)
                       and rg.diagnostics.ok)
        trace_clean = (states_identical(rm.state, rt.state)
                       and int(rm.sweeps) == int(rt.sweeps)
                       and rt.trace.firing_counts() ==
                       {k: int(v) for k, v in rt.fire_counts.items()})
        identical = (states_identical(rd.state, rm.state)
                     and {k: int(v) for k, v in rd.fire_counts.items()}
                     == {k: int(v) for k, v in rm.fire_counts.items()}
                     and int(rd.sweeps) == int(rm.sweeps))
        # Grid bit-identity: states + fire counts for every core count
        # (rounds may differ from host sweeps only under a custom assign).
        grid_identical = all(
            states_identical(rd.state, r.state)
            and {k: int(v) for k, v in rd.fire_counts.items()}
            == {k: int(v) for k, v in r.fire_counts.items()}
            for r in grid_runs.values())

        candidates = {
            "dyn": lambda dyn=dyn: jax.block_until_ready(dyn.run().state),
            "static": lambda static=static: jax.block_until_ready(
                static.run().state),
        }
        for c, p in grid.items():
            candidates[f"grid{c}"] = (
                lambda p=p: jax.block_until_ready(p.run().state))
        candidates["guarded"] = (
            lambda guarded=guarded: jax.block_until_ready(
                guarded.run().state))
        candidates["traced"] = (
            lambda traced=traced: jax.block_until_ready(
                traced.run().state))
        med = _interleaved_medians(candidates, reps)

        st1 = grid[1].stats()
        record(f"mega_{gname}_dynamic_host", med["dyn"], tokens,
               f"{int(rd.sweeps)} sweeps")
        record(f"mega_{gname}_megakernel", med["grid1"], tokens,
               f"{int(rm.sweeps)} sweeps, interpret mode, "
               f"{len(st1.forwarded_fifos)} forwarded",
               sweeps=int(rm.sweeps), cores=1,
               scratch_bytes=int(st1.scratch_bytes),
               shared_scratch_bytes=int(st1.shared_scratch_bytes),
               forwarded_fifos=len(st1.forwarded_fifos))
        record(f"mega_{gname}_megakernel_guarded", med["guarded"], tokens,
               f"{med['guarded'] / med['grid1']:.2f}x of unguarded, "
               f"clean + bit-identical: {guard_clean}",
               sweeps=int(rg.sweeps), cores=1)
        record(f"mega_{gname}_megakernel_traced", med["traced"], tokens,
               f"{med['traced'] / med['grid1']:.2f}x of untraced, "
               f"{rt.trace.n_events} events, bit-identical: {trace_clean}",
               sweeps=int(rt.sweeps), cores=1)
        record(f"mega_{gname}_static_specialized", med["static"], tokens,
               "fused scan reference")
        for c in GRID_CORES[1:]:
            st = grid[c].stats()
            record(
                f"mega_{gname}_grid{c}", med[f"grid{c}"], tokens,
                f"{int(grid_runs[c].sweeps)} rounds, {c} cores, "
                f"{st.shared_scratch_bytes} B shared rings+semaphores",
                sweeps=int(grid_runs[c].sweeps), cores=c,
                scratch_bytes=int(st.scratch_bytes),
                shared_scratch_bytes=int(st.shared_scratch_bytes),
                forwarded_fifos=len(st.forwarded_fifos))
        rows.append((f"mega_{gname}_vs_dynamic", 0.0,
                     f"{med['dyn'] / med['grid1']:.2f}x vs host dynamic "
                     f"(interpret-mode CPU; structure not kernel perf), "
                     f"bit-identical: {identical}"))
        rows.append((f"mega_{gname}_grid_vs_single", 0.0,
                     f"grid2 {med['grid1'] / med['grid2']:.2f}x / grid4 "
                     f"{med['grid1'] / med['grid4']:.2f}x vs 1-core "
                     f"(sequential partition loop; parity expected), "
                     f"grid bit-identical: {grid_identical}"))
        st = mega.stats()
        rows.append((f"mega_{gname}_scratch_bytes", 0.0,
                     f"{st.scratch_bytes} scratch after forwarding "
                     f"({st.reclaimed_scratch_bytes} reclaimed from "
                     f"{len(st.forwarded_fifos)} transient rings) vs "
                     f"{st.hbm_state_bytes} HBM operands"))
        splits = []
        for c in GRID_CORES[1:]:
            s = grid[c].stats()
            splits.append(f"{c}c: {list(s.core_scratch_bytes)} private / "
                          f"{s.shared_scratch_bytes} shared")
        rows.append((f"mega_{gname}_grid_ring_split", 0.0, "; ".join(splits)))

    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)
        f.write("\n")
    rows.append(("mega_bench_json", 0.0, json_path))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for name, us, derived in bench_megakernel(fast=fast):
        print(f"{name},{us:.1f},{derived}")
