"""The benchmark harness: resolves a cell of ``BENCHMARK.json`` to its
files, runs its set-up, its measured or traced window and its correctness
check, and builds the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json`` (via ``configs[].file``): sizes, plan,
  limits, and ``driver``, the module of ``bench/drivers/`` that runs it;
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench/lib/traffic``;
* ``bench/metrics/<metric>.py``: ``read(obs)`` returns the per-layer
  metric from a traced window, or None where it finds nothing to read.

A driver module (``bench/drivers/<driver>.py``, named by the
configuration's ``driver``) defines:

* ``Session(cell, seed)``, whose construction is the set-up (weights,
  build, warm-up of every shape the window uses), with ``call()`` (one
  unit of the window's work, the same for every seed, returning a record
  once its outputs are on the host), ``end_to_end(calls, window_s)``,
  ``attempted_failed(calls)``, ``observe(calls)`` (counts for the
  per-layer readers), ``release()`` (drops the program's state) and
  ``check(calls, **control)`` (the comparison with the plain reference,
  as ``[(name, value, limit)]``);
* ``CONTROL``: the keyword arguments of ``check`` that put the control
  (the reference one precision lower) in the program's place;
* ``smoke(config, mix)``: the (config, mix) cut to sizes a CPU test run
  holds;
* ``corrupt(monkeypatch)``: alters the answer where the program produces
  it, so that a run's check must fail.

A driver may find further pieces by name in the configuration, as the
stream driver finds its graph adapter in ``bench/graphs/<graph>.py``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: Longest traced window: traces are large and tracing slows the host.
TRACE_SECONDS = 6.0
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[str] = field(default_factory=list)
    per_layer: List[str] = field(default_factory=list)
    units: Dict[str, str] = field(default_factory=dict)


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> Cell:
    from bench.lib import traffic
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    e2e = [m["name"] for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m["name"] for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and ("workloads" in m or m["moves"] in e2e)]
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return Cell(workload, int(w["chips"]), config, traffic.load(w["traffic"]),
                e2e, per_layer, units)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program, so that
    only a cell's first run in a checkout compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


class CompileClock:
    """Sums JAX's own trace, lower and backend-compile durations (a backend
    compile served from the persistent cache included) while open."""

    def __init__(self):
        self.totals: Dict[str, float] = {v: 0.0 for v in
                                         _COMPILE_EVENTS.values()}
        self.backend_compiles = 0

    def _listen(self, event, duration, **_):
        key = _COMPILE_EVENTS.get(event)
        if key is not None:
            self.totals[key] += duration
            self.backend_compiles += key == "backend_s"

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


def disable_compile_cache() -> bool:
    """Stop reading and writing the persistent cache: a program that the
    window compiles is compiled in full in every run.  Returns whether the
    cache was on."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return was_on


@contextlib.contextmanager
def compile_cache_off():
    """The persistent cache off inside, as it was outside."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was_on = disable_compile_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def window(sess, seconds: float) -> tuple:
    """Whole calls, back to back, until ``seconds`` have passed: (calls,
    window_s).  Each record gets ``start_s``, the call's start in the
    window, and ``latency_s``, from its start to its outputs on the
    host."""
    calls = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        c0 = time.perf_counter()
        rec = sess.call()
        rec["start_s"] = c0 - t0
        rec["latency_s"] = time.perf_counter() - c0
        calls.append(rec)
    return calls, time.perf_counter() - t0


def traced_window(sess, seconds: float, log_dir: str):
    """``window`` under the profiler (no Python tracer), inside one host
    span ``bench.window``; returns (calls, TraceSummary)."""
    import jax
    from bench.lib import trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            calls, _ = window(sess, seconds)
    return calls, trace_reduce.summarize(trace_reduce.find_xspace(log_dir))


def device_info(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if require_chip and len(devices) < chips:
        raise NoChip(f"cell needs {chips} chip(s), JAX sees {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result line (a dict).  ``t_start``
    is when the process started, so that ``setup_s`` counts everything
    before the window.  ``require_chip=False`` runs on whatever JAX has,
    without the persistent cache (the CPU rehearsal of the tests)."""
    if require_chip:
        enable_compile_cache()
    devices = device_info(cell.chips, require_chip)
    driver = load_module("drivers", cell.config["driver"])
    sess = driver.Session(cell, seed)
    setup_s = time.perf_counter() - t_start
    if require_chip:
        disable_compile_cache()
    breakdown = None
    with CompileClock() as cc:
        if trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            try:
                calls, summary = traced_window(
                    sess, min(seconds, TRACE_SECONDS), log_dir)
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
        else:
            calls, window_s = window(sess, seconds)
    if trace:
        from bench.lib import work
        obs = {"calls": calls, "trace": summary,
               "window_s": summary.window_s, "compile": cc.totals,
               "peaks": work.peaks(devices[0].device_kind)
               if require_chip else None,
               **sess.observe(calls)}
        metrics = {}
        for name in cell.per_layer:
            value = load_module("metrics", name).read(obs)
            if value is not None:
                metrics[name] = value
        breakdown = summary.breakdown()
    else:
        metrics = dict(sess.end_to_end(calls, window_s))
        metrics["setup_s"] = setup_s
        metrics = {k: metrics[k] for k in cell.end_to_end}
    attempted, failed = sess.attempted_failed(calls)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak(devices)}
    if trace:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    slowest = sorted(calls, key=lambda r: -r["latency_s"])[:5]
    print(f"window: {len(calls)} calls, {cc.backend_compiles} backend "
          f"compiles, compile clock {cc.totals}; slowest calls (start_s, "
          f"latency_s): {[(r['start_s'], r['latency_s']) for r in slowest]}",
          file=sys.stderr)
    sess.release()
    gc.collect()
    checks = sess.check(calls)
    correct = all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        print(f"check {name}={v!r} limit={lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": {k: {"value": v, "unit": cell.units[k]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return line
