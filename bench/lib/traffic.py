"""The one traffic generator: turns a mix's data file and a seed into inputs.

A mix is ``bench/traffic/<name>.json``; its ``kind`` says which of the
generators below reads it.  Every size and rate lives in the file, so a new
mix is a new file.  The generators copy the program's own (the DPD signal of
``chip_smoke.py`` and ``repro.graphs.serving.poisson_trace``) so that no
later change to the program moves the yardstick; the video clips are the
benchmark's own.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def load(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from a seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


class Cycle:
    """Indices ``0..n-1`` in a fresh seeded permutation per round."""

    def __init__(self, n: int, rng: np.random.Generator):
        self.n, self.rng, self.left = n, rng, []

    def next(self) -> int:
        if not self.left:
            self.left = list(self.rng.permutation(self.n))
        return int(self.left.pop())


# --------------------------------------------------------------------- #
# Signal streams (kind "dpd_blocks").
# --------------------------------------------------------------------- #
def dpd_signal(rng: np.random.Generator, n_samples: int) -> np.ndarray:
    """(2, n) re/im planes of a complex Gaussian baseband normalised to unit
    peak amplitude, as a DPD sees its input scaled to the amplifier's
    saturation level."""
    sig = rng.normal(size=(2, n_samples))
    return (sig / np.sqrt((sig ** 2).sum(0)).max()).astype(np.float32)


def dpd_schedule(mix: dict, n_windows: int, windows_per_period: int
                 ) -> np.ndarray:
    """Active branch count of every window: the mix's per-period counts,
    each held for one reconfiguration period."""
    per = np.asarray(mix["active_per_period"], np.int32)
    n_periods = -(-n_windows // windows_per_period)
    if len(per) < n_periods:
        raise ValueError(f"mix gives {len(per)} periods, call needs "
                         f"{n_periods}")
    return np.repeat(per[:n_periods], windows_per_period)[:n_windows]


# --------------------------------------------------------------------- #
# Video clips (kind "md_frames").
# --------------------------------------------------------------------- #
def _bounce(p0: float, v: float, n: int, room: int) -> np.ndarray:
    """Positions ``p0 + v t`` for ``t < n``, reflected at ``0`` and
    ``room``: an object that bounces off the frame's edges."""
    if room <= 0:
        return np.zeros(n, np.int64)
    q = np.mod(p0 + v * np.arange(n), 2 * room)
    return np.rint(np.where(q > room, 2 * room - q, q)).astype(np.int64)


def md_clip(rng: np.random.Generator, mix: dict, n_frames: int, h: int,
            w: int) -> np.ndarray:
    """(n_frames, h, w) u8 frames of a fixed camera: a smooth background
    (a level plus a few low-frequency plane waves), the mix's rectangles
    moving at constant speed and bouncing off the edges, each brighter or
    darker than what it covers by its contrast, and Gaussian sensor noise
    on every pixel of every frame."""
    bg_spec, ob, sigma = mix["background"], mix["objects"], mix["noise_sigma"]
    y = (np.arange(h, dtype=np.float32) / h)[:, None]
    x = (np.arange(w, dtype=np.float32) / w)[None, :]
    bg = np.full((h, w), bg_spec["level"], np.float32)
    for _ in range(bg_spec["waves"]):
        fy, fx = rng.uniform(-bg_spec["max_cycles"], bg_spec["max_cycles"],
                             2)
        phase = rng.uniform(0, 2 * np.pi)
        bg += bg_spec["amplitude"] / bg_spec["waves"] * np.cos(
            2 * np.pi * (fy * y + fx * x) + phase).astype(np.float32)
    frames = np.repeat(bg[None], n_frames, axis=0)
    lo, hi = ob["size_frac"]
    for _ in range(ob["count"]):
        oh = max(1, int(round(rng.uniform(lo, hi) * h)))
        ow = max(1, int(round(rng.uniform(lo, hi) * w)))
        contrast = rng.choice([-1.0, 1.0]) * rng.uniform(*ob["contrast"])
        speed = rng.uniform(*ob["speed_px"])
        angle = rng.uniform(0, 2 * np.pi)
        ys = _bounce(rng.uniform(0, h - oh), speed * np.sin(angle),
                     n_frames, h - oh)
        xs = _bounce(rng.uniform(0, w - ow), speed * np.cos(angle),
                     n_frames, w - ow)
        for t in range(n_frames):
            frames[t, ys[t]:ys[t] + oh, xs[t]:xs[t] + ow] += contrast
    frames += sigma * rng.standard_normal(frames.shape, dtype=np.float32)
    return np.clip(np.rint(frames), 0, 255).astype(np.uint8)


# --------------------------------------------------------------------- #
# Request sets (kind "chat_requests").
# --------------------------------------------------------------------- #
def poisson_trace(n: int, rate: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """``n`` ascending integer arrival steps, exponential gaps of mean
    ``1/rate``."""
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(np.int32)


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Log-normal lengths clipped to ``[lo, hi]`` (``median == lo == hi``
    gives one fixed length)."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if lo == hi:
        return np.full(n, lo, np.int64)
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


@dataclass
class RequestSet:
    prompts: List[np.ndarray]
    budgets: np.ndarray
    arrivals: np.ndarray


@dataclass
class RequestSizes:
    """What every call of a run sends, whatever the seed: prompt lengths,
    answer budgets and arrival steps, drawn once from the mix's
    ``sizes_seed``."""
    prompt_lens: np.ndarray
    budgets: np.ndarray
    arrivals: np.ndarray

    @classmethod
    def from_mix(cls, mix: dict) -> "RequestSizes":
        rng = np.random.default_rng(mix["sizes_seed"])
        n = mix["requests_per_call"]
        return cls(_lengths(mix["prompt_len"], n, rng),
                   _lengths(mix["budget"], n, rng).astype(np.int32),
                   poisson_trace(n, mix["arrival_rate_per_step"], rng))

    def draw(self, vocab: int, rng: np.random.Generator) -> RequestSet:
        """A request set of these sizes with fresh prompt tokens."""
        return RequestSet([rng.integers(0, vocab, int(n)).astype(np.int32)
                           for n in self.prompt_lens],
                          self.budgets, self.arrivals)
