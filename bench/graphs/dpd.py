"""Graph adapter of the stream driver: the paper's DPD network (section
4.2), streamed window by window.

The feed is one pool signal in the channel's window layout, (n, rate=1,
2, L) float32; the fetch is the sink's input in the same layout.  The
comparison is the widest gap between a checked call's output and the
NumPy oracle (``bench.lib.dpd_ref``) in the configuration's precision.
"""
from __future__ import annotations

import numpy as np

from bench.lib import dpd_ref, traffic, work

FEED, FETCH = "f_in", "f_out"


def smoke(c: dict, mix: dict):
    """CPU test sizes: 1024-sample windows, 8 per call in chunks of 2."""
    c = dict(c, block_l=1024, reconf_period_samples=2048)
    c["plan"] = dict(c["plan"], n_iterations=2)
    return c, dict(mix, windows_per_call=8, signal_pool=3)


class Graph:
    """The network, the feed pool drawn from ``rng``, the work counts and
    the comparison of one configuration and mix."""

    def __init__(self, c: dict, mix: dict, rng: np.random.Generator):
        from repro.kernels.dyn_fir import N_TAPS
        if c["n_taps"] != N_TAPS:
            raise ValueError(f"config says {c['n_taps']} taps, the program "
                             f"has {N_TAPS}")
        self.c = c
        self.L, self.nb = c["block_l"], c["n_branches"]
        self.limit = c["limits"]["max_abs_err"]
        self.dtype = np.dtype(c["dtype"])
        self.n_win = mix["windows_per_call"]
        self.schedule = traffic.dpd_schedule(
            mix, self.n_win, c["reconf_period_samples"] // self.L)
        signals = [traffic.dpd_signal(rng, self.n_win * self.L)
                   for _ in range(mix["signal_pool"])]
        # Feeds in the channel's window layout, (n, rate=1, 2, L), made
        # once here so that a call stages a ready host array.
        self.pool = [np.ascontiguousarray(
            s.reshape(2, self.n_win, self.L).transpose(1, 0, 2)[:, None])
            for s in signals]
        self.samples_per_call = self.n_win * self.L

    def network(self):
        from repro.graphs.dpd import build_dpd
        return build_dpd(self.n_win, active_schedule=self.schedule,
                         block_l=self.L, n_branches=self.nb,
                         fir_impl=self.c["fir_impl"])

    def work(self) -> dict:
        """Counts for the per-layer readers: the whole call's and the FIR
        kernel launches' (flops, bytes)."""
        n_taps = self.c["n_taps"]
        call_flops, call_bytes = work.dpd_call(self.L, self.schedule, n_taps)
        fir_flops, fir_bytes = work.fir_call(self.L, self.schedule, n_taps)
        return {"call_flops": call_flops, "call_bytes": call_bytes,
                "fir_flops": fir_flops, "fir_bytes": fir_bytes}

    def check(self, kept, oracle_dtype=None):
        """Widest gap between a checked call's output and the float32
        oracle's, over every sample of every checked call.  With
        ``oracle_dtype`` the oracle in that precision takes the program's
        place: the control."""
        worst = 0.0
        for j, out in kept:
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
