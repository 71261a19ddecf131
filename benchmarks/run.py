"""Benchmark harness: one section per paper table + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).

Invocation (the one used by CI, EXPERIMENTS.md and the verify skill):
``PYTHONPATH=src python benchmarks/run.py`` — the scripts bootstrap the
repo root onto ``sys.path`` themselves, so ``PYTHONPATH=src`` alone is
enough for every bench entrypoint.
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):   # script invocation: PYTHONPATH=src is enough
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    rows = []
    from benchmarks.bench_paper_tables import (bench_buffers, bench_dpd,
                                               bench_motion_detection)
    from benchmarks.bench_executors import bench_executors
    from benchmarks.bench_kernels import bench_kernels
    from benchmarks.bench_megakernel import bench_megakernel
    from benchmarks.bench_serving import bench_serving
    from benchmarks.roofline import bench_roofline

    sections = [
        ("Table 1 (buffer memory)", bench_buffers),
        ("Table 3 (Motion Detection)", bench_motion_detection),
        ("Table 4 (DPD + 5x claim)", bench_dpd),
        ("Executors (specialization + multi-firing)", bench_executors),
        ("Megakernel (device-resident dynamic scheduling)", bench_megakernel),
        ("Serving (continuous batching on the actor runtime)", bench_serving),
        ("Kernels", bench_kernels),
        ("Roofline (from dry-run)", bench_roofline),
    ]
    print("name,us_per_call,derived")
    failed = []
    for title, fn in sections:
        print(f"# --- {title} ---", file=sys.stderr)
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            print(f"{title}_ERROR,0,{type(e).__name__}: {e}")
            failed.append(title)
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
