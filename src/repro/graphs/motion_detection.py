"""The Motion Detection application — paper §4.1, Fig. 4.

Five actors: Source -> Gauss -> Thres -> Med -> Sink.  Gauss feeds Thres
through *two* channels, one of which carries an initial (delay) token: the
one-frame delay that enables consecutive-frame subtraction (the dotted
channel in Fig. 4).

Frame size 320x240, 8-bit grayscale: FIFO tokens are uint8 frames of
76 800 bytes exactly as in the paper (so Eq. 1 reproduces Table 1's buffer
memory); arithmetic inside actors runs in f32 and is rounded back to u8 at
every port — the 8-bit inter-actor contract of the original.  Token rate
r=1 for GPP-style execution, r=4 for the accelerated configuration
(paper §4.3).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Network, NetworkBuilder, static_actor
from repro.kernels.gauss5x5 import gauss5x5
from repro.kernels.motion_post import DEFAULT_THRESHOLD, med_ref, thres_ref

FRAME_H, FRAME_W = 240, 320


def build_motion_detection(n_frames: int, rate: int = 1,
                           frame_hw: Tuple[int, int] = (FRAME_H, FRAME_W),
                           threshold: float = DEFAULT_THRESHOLD,
                           video: Optional[jax.Array] = None,
                           gauss_impl: str = "xla") -> Network:
    """Build the 5-actor MD network for ``n_frames`` total frames.

    ``n_frames`` must be divisible by ``rate`` (windows of ``rate`` frames
    per firing).  ``video``: optional (n_frames, H, W) f32 array staged
    into the source actor; defaults to zeros (benchmarks stage real data
    via the source state).
    """
    H, W = frame_hw
    if n_frames % rate:
        raise ValueError(f"n_frames={n_frames} not divisible by rate={rate}")
    n_iter = n_frames // rate
    tok = (H, W)

    def to_u8(x):
        return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)

    def src_fire(state, inputs, rates):
        data, idx = state
        win = jax.lax.dynamic_slice_in_dim(data, idx * rate, rate, axis=0)
        return (data, idx + 1), {"out": win}

    def src_init():
        data = video if video is not None else jnp.zeros((n_frames, H, W), jnp.uint8)
        return (to_u8(jnp.asarray(data)), jnp.int32(0))

    source = static_actor("source", (), ("out",), src_fire, init=src_init,
                          ready=lambda st: st[1] < n_iter)

    def gauss_fire(state, inputs, rates):
        del rates
        out = jax.vmap(lambda f: gauss5x5(f, impl=gauss_impl))(
            inputs["in"].astype(jnp.float32))
        out = to_u8(out)
        # One filtered stream feeds two channels (direct + delayed).
        return state, {"out": out, "out_d": out}

    gauss = static_actor("gauss", ("in",), ("out", "out_d"), gauss_fire,
                         cost_flops=rate * H * W * 10 * 2)  # separable 5+5 MACs

    def thres_fire(state, inputs, rates):
        del rates
        out = jax.vmap(lambda c, p: thres_ref(c, p, threshold))(
            inputs["cur"].astype(jnp.float32), inputs["prev"].astype(jnp.float32))
        return state, {"out": to_u8(out)}

    thres = static_actor("thres", ("cur", "prev"), ("out",), thres_fire,
                         cost_flops=rate * H * W * 3)

    def med_fire(state, inputs, rates):
        del rates
        out = jax.vmap(med_ref)(inputs["in"].astype(jnp.float32))
        return state, {"out": to_u8(out)}

    med = static_actor("med", ("in",), ("out",), med_fire,
                       cost_flops=rate * H * W * 12)

    def sink_fire(state, inputs, rates):
        del rates
        data, idx = state
        data = jax.lax.dynamic_update_slice_in_dim(data, inputs["in"], idx * rate, axis=0)
        return (data, idx + 1), {}

    sink = static_actor("sink", ("in",), (), sink_fire,
                        init=lambda: (jnp.zeros((n_frames, H, W), jnp.uint8), jnp.int32(0)),
                        finish=lambda st: st[0])

    u8 = jnp.uint8
    b = NetworkBuilder()
    b.actors(source, gauss, thres, med, sink)
    b.connect("source.out", "gauss.in", rate=rate, token_shape=tok, dtype=u8,
              name="f_src_gauss")
    b.connect("gauss.out", "thres.cur", rate=rate, token_shape=tok, dtype=u8,
              name="f_gauss_thres")
    # The dotted Fig. 4 channel: one initial (delay) token -> Eq. 1 triple
    # buffer, enabling consecutive-frame subtraction.
    b.connect("gauss.out_d", "thres.prev", rate=rate, token_shape=tok,
              dtype=u8, delay=1, name="f_gauss_thres_d")
    b.connect("thres.out", "med.in", rate=rate, token_shape=tok, dtype=u8,
              name="f_thres_med")
    b.connect("med.out", "sink.in", rate=rate, token_shape=tok, dtype=u8,
              name="f_med_sink")
    return b.build()


def md_oracle(video: np.ndarray,
              threshold: float = DEFAULT_THRESHOLD) -> np.ndarray:
    """Plain NumPy reference of the network's sink output.

    ``video``: (n_frames, H, W) values in [0, 255], rounded to u8 as the
    source does.  Per frame: 25-tap binomial Gauss (2-pixel border passed
    through), rounded to u8; ``|g_t - g_{t-1}| > threshold`` -> 255 else
    0 against the previous filtered frame (zeros before the first, the
    delay token); plus-shaped 5-point median with edge padding.  Every
    partial sum is a multiple of 1/256 below 2^16, so the result is exact
    in float32 whatever the summation order.
    """
    def u8(x):
        return np.clip(np.round(x), 0, 255).astype(np.uint8)

    vid = u8(np.asarray(video)).astype(np.float32)
    n, H, W = vid.shape
    w1 = np.array([1.0, 4.0, 6.0, 4.0, 1.0], np.float32) / 16
    w2 = np.outer(w1, w1)
    border = np.zeros((H, W), bool)
    border[:2] = border[-2:] = True
    border[:, :2] = border[:, -2:] = True
    out = np.zeros((n, H, W), np.uint8)
    prev = np.zeros((H, W), np.float32)
    for i in range(n):
        pad = np.pad(vid[i], 2, mode="edge")
        acc = np.zeros((H, W), np.float32)
        for dy in range(5):
            for dx in range(5):
                acc += w2[dy, dx] * pad[dy:dy + H, dx:dx + W]
        g = u8(np.where(border, vid[i], acc)).astype(np.float32)
        m = np.where(np.abs(g - prev) > threshold, 255.0, 0.0)
        prev = g
        p = np.pad(m, 1, mode="edge")
        taps = np.stack([p[0:H, 1:W + 1], p[2:H + 2, 1:W + 1],
                         p[1:H + 1, 0:W], p[1:H + 1, 2:W + 2],
                         p[1:H + 1, 1:W + 1]])
        out[i] = u8(np.median(taps, axis=0))
    return out


def bench_workload(n_frames: int, rate: int = 4,
                   frame_hw: Tuple[int, int] = (FRAME_H, FRAME_W),
                   seed: int = 0, **build_kw) -> Network:
    """MD network staged with reproducible random frames.

    Shared by benchmarks/bench_executors.py and tests/test_perf_smoke.py.
    All channels here sit between static actors, so the specialized
    executor keeps them ring-buffered with trace-time phase offsets
    (period = LCM(2, 3) over the double buffers and the Fig. 2 delayed
    triple buffer); only the fps accounting lives here.
    """
    rng = np.random.default_rng(seed)
    video = jnp.asarray(
        rng.uniform(0, 255, (n_frames,) + tuple(frame_hw)).astype(np.float32))
    return build_motion_detection(n_frames, rate=rate, frame_hw=frame_hw,
                                  video=video, **build_kw)
