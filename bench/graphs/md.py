"""Graph adapter of the stream driver: the paper's motion detection
network (section 4.1, Fig. 4), streamed four frames a firing.

The feed is one pool clip in the channel's window layout, (n, rate, H, W)
u8, into Gauss; the fetch is Med's output to the sink in the same layout.
The comparison counts the pixels of the checked calls that differ from
the NumPy oracle (``bench.lib.md_ref``), in which float32 is exact.
"""
from __future__ import annotations

import numpy as np

from bench.lib import md_ref, traffic, work

FEED, FETCH = "f_src_gauss", "f_med_sink"


def smoke(c: dict, mix: dict):
    """CPU test sizes: 48x64 frames, 24 a call in chunks of 3 firings."""
    c = dict(c, frame_h=48, frame_w=64)
    c["plan"] = dict(c["plan"], n_iterations=3)
    return c, dict(mix, frames_per_call=24, clip_pool=3)


class Graph:
    """The network, the feed pool drawn from ``rng``, the work counts and
    the comparison of one configuration and mix."""

    def __init__(self, c: dict, mix: dict, rng: np.random.Generator):
        self.c = c
        self.H, self.W, self.rate = c["frame_h"], c["frame_w"], c["rate"]
        self.n = mix["frames_per_call"]
        if self.n % self.rate:
            raise ValueError(f"{self.n} frames a call is no whole number of "
                             f"firings at rate {self.rate}")
        self.threshold = float(c["threshold"])
        self.dtype = np.dtype(c["dtype"])
        self.limit = c["limits"]["md_mismatched_pixels"]
        self.pool = [np.ascontiguousarray(traffic.md_clip(
            rng, mix, self.n, self.H, self.W).reshape(
                self.n // self.rate, self.rate, self.H, self.W))
            for _ in range(mix["clip_pool"])]
        self.samples_per_call = self.n * self.H * self.W

    def network(self):
        from repro.graphs.motion_detection import build_motion_detection
        return build_motion_detection(
            self.n, rate=self.rate, frame_hw=(self.H, self.W),
            threshold=self.threshold, gauss_impl=self.c["gauss_impl"])

    def work(self) -> dict:
        """Counts for the per-layer readers: the whole call's and the Gauss
        kernel launches' (flops, bytes)."""
        call_flops, call_bytes = work.md_call(self.H, self.W, self.n)
        gauss_flops, gauss_bytes = work.gauss_call(self.H, self.W, self.n)
        return {"call_flops": call_flops, "call_bytes": call_bytes,
                "gauss_flops": gauss_flops, "gauss_bytes": gauss_bytes}

    def check(self, kept, oracle_dtype=None):
        """Pixels, over every frame of every checked call, that differ
        from the oracle's in the configuration's float32.  With
        ``oracle_dtype`` the oracle with its Gauss sums in that precision
        takes the program's place: the control."""
        shape = (self.n, self.H, self.W)
        wrong = 0
        for j, out in kept:
            video = self.pool[j].reshape(shape)
            ref = md_ref.md_oracle(video, self.threshold, dtype=self.dtype)
            if oracle_dtype is None:
                got = out.reshape(shape)
            else:
                got = md_ref.md_oracle(video, self.threshold,
                                       dtype=oracle_dtype)
            wrong += int(np.count_nonzero(got != ref))
        return [("md_mismatched_pixels", wrong, self.limit)]
