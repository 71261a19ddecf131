"""Plain NumPy reference of the DPD network's output (paper section 4.2).

A copy of the program's oracle and branch taps, kept with the benchmark so
that no change to the program can move it.  ``dtype=np.float32`` is the
reference; a lower ``dtype`` (bfloat16) is the control that the comparison
must reject.
"""
from __future__ import annotations

import numpy as np

N_TAPS = 10


def branch_taps(k: int) -> np.ndarray:
    """Poly branch ``k``'s (2, N_TAPS) float32 complex taps (re, im)."""
    return np.random.default_rng(100 + k).normal(
        scale=0.3, size=(2, N_TAPS)).astype(np.float32)


def dpd_oracle(signal: np.ndarray, active_schedule: np.ndarray,
               block_l: int, n_branches: int = 10,
               dtype=np.float32) -> np.ndarray:
    """Sink output for ``signal`` (2, n_windows * block_l): window ``f``
    sums branches ``k < active_schedule[f]``, each ``x * |x|^(2k)`` through
    its 10-tap complex FIR with a 9-sample history that advances only when
    the branch runs."""
    L = block_l
    sig = np.asarray(signal).astype(dtype)
    hist = [np.zeros((2, N_TAPS - 1), dtype) for _ in range(n_branches)]
    taps = [branch_taps(k).astype(dtype) for k in range(n_branches)]
    out = np.zeros(sig.shape, dtype)
    for f, n_active in enumerate(np.asarray(active_schedule)):
        win = sig[:, f * L:(f + 1) * L]
        for k in range(int(n_active)):
            x = np.concatenate([hist[k], win], axis=1)
            hist[k] = x[:, -(N_TAPS - 1):]
            xr, xi = x
            scale = (xr * xr + xi * xi) ** k
            br, bi = xr * scale, xi * scale
            hr, hi = taps[k]
            yr = np.zeros(L, dtype)
            yi = np.zeros(L, dtype)
            for t in range(N_TAPS):
                sr = br[N_TAPS - 1 - t:N_TAPS - 1 - t + L]
                si = bi[N_TAPS - 1 - t:N_TAPS - 1 - t + L]
                yr = yr + hr[t] * sr - hi[t] * si
                yi = yi + hr[t] * si + hi[t] * sr
            out[0, f * L:(f + 1) * L] += yr
            out[1, f * L:(f + 1) * L] += yi
    return out.astype(np.float32)
