"""Operations and bytes that the benchmarked work needs, from shapes.

These are the counts the roofline shares and utilisations divide by: the
least that an implementation has to compute and move, never what one
particular implementation happens to do.
"""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")
F32_BYTES = 4


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def least_s(flops: float, nbytes: float, pk: dict) -> float:
    """Least time on the chip: the larger of the compute and memory bounds
    (bf16 peak for operations, HBM bandwidth for bytes).  The table has no
    float32 rate, so float32 work (the DPD) is held to the bf16 peak, which
    no float32 path exceeds: its compute term can only come out too small,
    and such work is bounded by its bytes."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# --------------------------------------------------------------------- #
# DPD (paper section 4.2).
# --------------------------------------------------------------------- #
def fir_branch(block_l: int, order: int, n_taps: int = 10):
    """One Poly branch firing (basis ``x|x|^(2(order-1))`` and the complex
    FIR) on ``block_l`` samples plus the ``n_taps - 1`` history:
    (flops, bytes).  Basis: |x|^2 (3), the power (order - 1), x * scale
    (2) per input sample; FIR: 8 per tap per output sample.  Bytes: both
    planes of the input with history and the taps read, both output
    planes written."""
    n_in = block_l + n_taps - 1
    flops = (3 + (order - 1) + 2) * n_in + 8 * n_taps * block_l
    nbytes = F32_BYTES * (2 * n_in + 2 * n_taps + 2 * block_l)
    return flops, nbytes


def dpd_window(block_l: int, n_active: int, n_taps: int = 10):
    """One window of the whole network with ``n_active`` branches:
    (flops, bytes).  Each actor reads its inputs and writes its outputs
    once: the feed writes the window, the fork reads it, each active branch
    reads it and writes its output, the adder reads those and writes the
    sum, the fetch reads it."""
    w = 2 * block_l * F32_BYTES
    flops = 2 * block_l * max(n_active - 1, 0)
    nbytes = 4 * w
    for k in range(n_active):
        f, b = fir_branch(block_l, k + 1, n_taps)
        flops += f
        nbytes += b + w                       # + the adder's read
    return flops, nbytes


def dpd_call(block_l: int, schedule: np.ndarray, n_taps: int = 10):
    """(flops, bytes) of one call over its per-window schedule."""
    fl = by = 0
    for n in np.asarray(schedule):
        f, b = dpd_window(block_l, int(n), n_taps)
        fl, by = fl + f, by + b
    return fl, by


def fir_call(block_l: int, schedule: np.ndarray, n_taps: int = 10):
    """(flops, bytes) of the FIR kernel launches of one call."""
    fl = by = 0
    for n in np.asarray(schedule):
        for k in range(int(n)):
            f, b = fir_branch(block_l, k + 1, n_taps)
            fl, by = fl + f, by + b
    return fl, by


# --------------------------------------------------------------------- #
# Decoder-only LM forward (Llama-style, grouped-query attention).
# --------------------------------------------------------------------- #
def lm_matmul_flops_per_token(c: dict) -> int:
    """2 x the weights every token multiplies: attention projections,
    SwiGLU and the output head (the embedding is a lookup)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return 2 * (c["num_hidden_layers"] * per_layer + d * c["vocab_size"])


def lm_request_flops(c: dict, prompt_len: int, n_generated: int) -> int:
    """Model flops of one greedy request: a forward over every prompt token
    and over every generated token but the last, each attending causally
    to the positions before it (4 x heads x head_dim per layer and
    position attended)."""
    n_fwd = prompt_len + max(n_generated - 1, 0)
    attended = n_fwd * (n_fwd + 1) // 2
    att = 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"]
    return n_fwd * lm_matmul_flops_per_token(c) + attended * att
