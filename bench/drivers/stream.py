"""Driver: a signal graph streamed through ``Program.stream``.

Set-up builds the configuration's graph with the mix's schedule, compiles
it under the configuration's plan with every actor but source and sink on
the accelerator, makes a pool of input signals from the seed, and streams
twice (every call has the same shapes).  A call streams one pool signal (the mix's
``windows_per_call`` windows, in chunks of the plan's ``n_iterations``) and
brings the output to the host.  The check compares a seeded sample of the
window's calls, whole, with the benchmark's copy of the NumPy oracle in
the configuration's precision.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.lib import dpd_ref, traffic, work

#: Calls of the window whose outputs are kept and compared.
CHECKED_CALLS = 3


class Session:
    def __init__(self, cell, seed: int):
        from repro.core import ExecutionPlan
        from repro.graphs.dpd import build_dpd
        from repro.kernels.dyn_fir import N_TAPS
        c, mix = cell.config, cell.traffic
        if c["n_taps"] != N_TAPS:
            raise ValueError(f"config says {c['n_taps']} taps, the program "
                             f"has {N_TAPS}")
        self.L, self.nb = c["block_l"], c["n_branches"]
        self.limit = c["limits"]["max_abs_err"]
        self.dtype = np.dtype(c["dtype"])
        self.n_win = mix["windows_per_call"]
        self.schedule = traffic.dpd_schedule(
            mix, self.n_win, c["reconf_period_samples"] // self.L)
        rng = np.random.default_rng(seed)
        signals = [traffic.dpd_signal(rng, self.n_win * self.L)
                   for _ in range(mix["signal_pool"])]
        # Feeds in the channel's window layout, (n, rate=1, 2, L), made
        # once here so that a call stages a ready host array.
        self.pool = [np.ascontiguousarray(
            s.reshape(2, self.n_win, self.L).transpose(1, 0, 2)[:, None])
            for s in signals]
        self.order = traffic.Cycle(len(self.pool), rng)
        self.keep_rng = np.random.default_rng([seed, 1])
        self.kept = []          # reservoir of (pool index, output)
        self.n_calls = 0
        net = build_dpd(self.n_win, active_schedule=self.schedule,
                        block_l=self.L, n_branches=self.nb,
                        fir_impl=c["fir_impl"])
        plan = c["plan"]
        # The paper's heterogeneous mapping: all but source and sink on
        # the accelerator.
        accel = tuple(n for n in net.actors if n not in ("source", "sink"))
        self.prog = net.compile(ExecutionPlan(
            mode=plan["mode"], n_iterations=plan["n_iterations"],
            accelerated=accel))
        self.call_flops, self.call_bytes = work.dpd_call(
            self.L, self.schedule, c["n_taps"])
        self.fir_flops, self.fir_bytes = work.fir_call(
            self.L, self.schedule, c["n_taps"])
        for j in range(2):      # compiles, then one call as the window's
            self._stream(j)

    def _stream(self, j: int) -> np.ndarray:
        with jax.profiler.TraceAnnotation("stream.call"):
            out = self.prog.stream({"f_in": self.pool[j]})["f_out"]
        with jax.profiler.TraceAnnotation("stream.fetch"):
            return np.asarray(out)

    def call(self) -> dict:
        j = self.order.next()
        out = self._stream(j)
        rec = {"samples": self.n_win * self.L,
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
        return {"call_flops": self.call_flops, "call_bytes": self.call_bytes,
                "fir_flops": self.fir_flops, "fir_bytes": self.fir_bytes}

    def release(self):
        self.prog = None

    def check(self, calls, oracle_dtype=None):
        """Widest gap between a checked call's output and the float32
        oracle's, over every sample of every checked call.  With
        ``oracle_dtype`` the oracle in that precision takes the program's
        place: the control."""
        worst = 0.0
        for j, out in self.kept:
            sig = self.pool[j][:, 0].transpose(1, 0, 2).reshape(2, -1)
            ref = dpd_ref.dpd_oracle(sig, self.schedule, self.L, self.nb,
                                     dtype=self.dtype)
            if oracle_dtype is None:
                got = out[:, 0].transpose(1, 0, 2).reshape(2, -1)
            else:
                got = dpd_ref.dpd_oracle(sig, self.schedule, self.L,
                                         self.nb, dtype=oracle_dtype)
            worst = max(worst, float(np.abs(got - ref).max()))
        return [("dpd_max_abs_err", worst, self.limit)]
