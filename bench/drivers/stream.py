"""Driver: a signal graph streamed through ``Program.stream``.

The configuration's ``graph`` names its adapter, ``bench/graphs/<graph>.py``,
which holds what belongs to that graph: the network, the feed pool drawn
from the seed, the feed's and the fetch's channel names, the work counts,
the comparison with the plain reference and the CPU test sizes.

Set-up builds the graph, compiles it under the configuration's plan with
every actor but source and sink on the accelerator, makes the feed pool,
and streams twice (every call has the same shapes).  A call streams one
pool entry (in chunks of the plan's ``n_iterations`` firings) and brings
the output to the host.  The check compares a seeded sample of the
window's calls, whole, with the adapter's reference.
"""
from __future__ import annotations

import jax
import ml_dtypes
import numpy as np

from bench import harness
from bench.lib import traffic

#: Calls of the window whose outputs are kept and compared.
CHECKED_CALLS = 3
#: ``check`` arguments that put the control in the program's place: the
#: reference one precision below the configurations' float32.
CONTROL = {"oracle_dtype": ml_dtypes.bfloat16}


def graph(c: dict):
    """The adapter module of the configuration's graph."""
    return harness.load_module("graphs", c["graph"])


def smoke(c: dict, mix: dict):
    """The graph's CPU test sizes: (config, mix)."""
    return graph(c).smoke(c, mix)


def corrupt(monkeypatch):
    """Alter one value of every stream call's output where the program
    produces it."""
    import jax.numpy as jnp
    from repro.core.program import Program
    real = Program.stream

    def stream(self, feeds, **kw):
        out = real(self, feeds, **kw)
        return {k: v.at[3, 0, 1, 5].add(
            1 if jnp.issubdtype(v.dtype, jnp.integer) else 0.5)
            for k, v in out.items()}

    monkeypatch.setattr(Program, "stream", stream)


class Session:
    def __init__(self, cell, seed: int):
        from repro.core import ExecutionPlan
        c = cell.config
        adapter = graph(c)
        self.feed, self.fetch = adapter.FEED, adapter.FETCH
        rng = np.random.default_rng(seed)
        self.graph = adapter.Graph(c, cell.traffic, rng)
        self.pool = self.graph.pool
        self.order = traffic.Cycle(len(self.pool), rng)
        self.keep_rng = np.random.default_rng([seed, 1])
        self.kept = []          # reservoir of (pool index, output)
        self.n_calls = 0
        net = self.graph.network()
        plan = c["plan"]
        # The paper's heterogeneous mapping: all but source and sink on
        # the accelerator.
        accel = tuple(n for n in net.actors if n not in ("source", "sink"))
        self.prog = net.compile(ExecutionPlan(
            mode=plan["mode"], n_iterations=plan["n_iterations"],
            accelerated=accel))
        self.work = self.graph.work()
        for j in range(2):      # compiles, then one call as the window's
            self._stream(j)

    def _stream(self, j: int) -> np.ndarray:
        with jax.profiler.TraceAnnotation("stream.call"):
            out = self.prog.stream({self.feed: self.pool[j]})[self.fetch]
        with jax.profiler.TraceAnnotation("stream.fetch"):
            return np.asarray(out)

    def call(self) -> dict:
        j = self.order.next()
        out = self._stream(j)
        rec = {"samples": self.graph.samples_per_call,
               "sweeps": int(self.prog.last_stream_sweeps)}
        # Reservoir sample of the window's calls, drawn from the seed.
        self.n_calls += 1
        if len(self.kept) < CHECKED_CALLS:
            self.kept.append((j, out))
        else:
            r = int(self.keep_rng.integers(self.n_calls))
            if r < CHECKED_CALLS:
                self.kept[r] = (j, out)
        return rec

    def end_to_end(self, calls, window_s):
        lat = np.array([r["latency_s"] for r in calls])
        return {"msamples_per_s":
                sum(r["samples"] for r in calls) / window_s / 1e6,
                "block_p95_ms": float(np.percentile(lat, 95)) * 1e3}

    def attempted_failed(self, calls):
        return len(calls), 0

    def observe(self, calls):
        return dict(self.work)

    def release(self):
        self.prog = None

    def check(self, calls, oracle_dtype=None):
        """The adapter's comparison of the checked calls; with
        ``oracle_dtype`` its reference in that precision takes the
        program's place: the control."""
        return self.graph.check(self.kept, oracle_dtype)
