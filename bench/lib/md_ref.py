"""Plain NumPy reference of the motion-detection network's sink output
(paper section 4.1, Fig. 4).

A copy of the program's oracle (``md_oracle``), kept with the benchmark so
that no change to the program can move it.  ``dtype`` is the precision in
which the Gauss sums accumulate: float32 is the reference, in which every
partial sum (a multiple of 1/256 below 2^16) is exact; a lower ``dtype``
(bfloat16) is the control that the comparison must reject.
"""
from __future__ import annotations

import numpy as np

#: Thres's threshold when the configuration gives none (the program's
#: ``DEFAULT_THRESHOLD``).
THRESHOLD = 40.0


def _u8(x):
    return np.clip(np.round(x), 0, 255).astype(np.uint8)


def md_oracle(video: np.ndarray, threshold: float = THRESHOLD,
              dtype=np.float32) -> np.ndarray:
    """Sink output (n_frames, H, W) u8 for ``video`` (n_frames, H, W) in
    [0, 255], rounded to u8 as the source does.  Per frame: the 25-tap
    binomial Gauss (2-pixel border passed through), rounded to u8;
    ``|g_t - g_{t-1}| > threshold`` -> 255 else 0 against the previous
    filtered frame (zeros before the first: the delay token); the
    plus-shaped 5-point median with edge padding."""
    vid = _u8(np.asarray(video)).astype(np.float32)
    n, H, W = vid.shape
    w1 = np.array([1.0, 4.0, 6.0, 4.0, 1.0], np.float32) / 16
    w2 = np.outer(w1, w1).astype(dtype)
    border = np.zeros((H, W), bool)
    border[:2] = border[-2:] = True
    border[:, :2] = border[:, -2:] = True
    out = np.zeros((n, H, W), np.uint8)
    prev = np.zeros((H, W), np.float32)
    for i in range(n):
        pad = np.pad(vid[i], 2, mode="edge").astype(dtype)
        acc = np.zeros((H, W), dtype)
        for dy in range(5):
            for dx in range(5):
                acc += w2[dy, dx] * pad[dy:dy + H, dx:dx + W]
        g = _u8(np.where(border, vid[i], acc.astype(np.float32))
                ).astype(np.float32)
        m = np.where(np.abs(g - prev) > threshold, 255.0, 0.0)
        prev = g
        p = np.pad(m, 1, mode="edge")
        taps = np.stack([p[0:H, 1:W + 1], p[2:H + 2, 1:W + 1],
                         p[1:H + 1, 0:W], p[1:H + 1, 2:W + 2],
                         p[1:H + 1, 1:W + 1]])
        out[i] = _u8(np.median(taps, axis=0))
    return out
