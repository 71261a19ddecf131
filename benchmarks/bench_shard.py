"""Sharded-executor benchmarks: ``ExecutionPlan(devices=k)`` vs the
single-device dynamic executor it is bit-identical to.

For DPD and MoE-as-actors, times the mesh-sharded dynamic executor at
``devices`` in 1/2/4 and reports the sharding structure from
``Program.stats``: barrier rounds (each one progress all-reduce),
``collective_bytes_per_sweep`` (the crossing rings + cursor pairs every
barrier exchange moves — the collective analogue of the grid
megakernel's shared-scratch polling surface) and the device partition.
Bit-identity (states + fire counts vs ``devices=1``) is checked inline
and committed as a structure field, so a silent divergence fails
``check_regression.py`` exactly like a sweep-count drift.

The measurement runs in process whenever ``max(DEVICES)`` devices are
visible (a four-chip host, or a parent started under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Otherwise, and
only if the parent's backend is the CPU, it runs in a child process
under that flag; the child writes the JSON records and the parent forms
the human rows from them.  A TPU parent with too few chips raises: the
parent holds the chip, so a child could not get it.

Caveat printed with the numbers: the forced host "mesh" is one CPU —
rows measure the collective schedule's overhead (ppermute exchanges +
quiescence all-reduces per round), not a parallel speedup.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List, Tuple

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

Row = Tuple[str, float, str]

DEVICES = (1, 2, 4)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(REPO_ROOT, "BENCH_shard.json")

_CHILD = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
from benchmarks.bench_shard import measure
measure(sys.argv[1], sys.argv[2] == "1")
"""


def measure(json_path: str, fast: bool) -> None:
    """Time ``devices`` in :data:`DEVICES` in this process and write the
    JSON records to ``json_path``."""
    import jax
    from benchmarks.bench_executors import _interleaved_medians
    from repro.core import ExecutionPlan
    from repro.graphs.factories import make_dpd, make_moe, states_identical

    reps = 3 if fast else 7
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

    records = []
    for gname, net, n_iter, tokens in workloads:
        progs = {k: net.compile(ExecutionPlan(mode="dynamic", devices=k,
                                              donate=False))
                 for k in DEVICES}
        runs = {k: p.run() for k, p in progs.items()}
        ref = runs[1]
        ref_counts = {n: int(v) for n, v in ref.fire_counts.items()}
        med = _interleaved_medians(
            {f"dev{k}": (lambda p=p: jax.block_until_ready(p.run().state))
             for k, p in progs.items()}, reps)
        for k in DEVICES:
            r, st = runs[k], progs[k].stats()
            identical = (states_identical(ref.state, r.state)
                         and {n: int(v) for n, v in r.fire_counts.items()}
                         == ref_counts)
            rec = {"name": f"shard_{gname}_dev{k}",
                   "us_per_call": round(med[f"dev{k}"] * 1e6, 1),
                   "tokens_per_s": round(tokens / med[f"dev{k}"], 1),
                   "devices": k, "rounds": int(r.sweeps),
                   "bit_identical": bool(identical)}
            if k > 1:
                rec["collective_bytes_per_sweep"] = int(
                    st.collective_bytes_per_sweep)
            records.append(rec)

    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)


def _measure_in_child(json_path: str, fast: bool) -> None:
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [REPO_ROOT, os.path.join(REPO_ROOT, "src")]))
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, json_path, "1" if fast else "0"],
        capture_output=True, text=True, timeout=3600, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_shard child failed:\n{out.stdout}\n{out.stderr}")


def bench_shard(fast: bool = False, json_path: str = JSON_PATH) -> List[Row]:
    import jax
    if jax.device_count() >= max(DEVICES):
        measure(json_path, fast)
    elif jax.default_backend() == "cpu":
        _measure_in_child(json_path, fast)
    else:
        raise RuntimeError(
            f"bench_shard needs {max(DEVICES)} devices; this "
            f"{jax.default_backend()} host has {jax.device_count()}")
    with open(json_path) as f:
        records = json.load(f)

    rows: List[Row] = []
    by_name = {r["name"]: r for r in records}
    for rec in records:
        if rec["devices"] == 1:
            derived = (f"{rec['rounds']} sweeps, host dynamic reference, "
                       f"bit-identical: {rec['bit_identical']}")
        else:
            derived = (f"{rec['rounds']} rounds, {rec['devices']} devices, "
                       f"{rec['collective_bytes_per_sweep']} B/round "
                       f"collective, bit-identical: {rec['bit_identical']}")
        rows.append((rec["name"], rec["us_per_call"], derived))
    for gname in ("dpd", "moe"):
        d1 = by_name.get(f"shard_{gname}_dev1")
        if d1 is None:
            continue
        ratios = []
        for k in DEVICES[1:]:
            dk = by_name[f"shard_{gname}_dev{k}"]
            ratios.append(f"dev{k} {d1['us_per_call'] / dk['us_per_call']:.2f}x")
        rows.append((f"shard_{gname}_vs_dynamic", 0.0,
                     ", ".join(ratios) + " vs 1-device (forced host mesh; "
                     "collective-schedule overhead, not parallel speedup)"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, us, derived in bench_shard(fast="--fast" in sys.argv):
        print(f"{name:36s} {us:10.1f} us  {derived}")
