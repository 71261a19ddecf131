"""Jitted entry point for the RG-LRU kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.rglru.kernel import rglru_pallas
from repro.kernels.rglru.ref import rglru_scan


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def rglru(log_a, gx, *, chunk: int = 128, impl: str = "pallas",
          interpret: Optional[bool] = None):
    """RG-LRU recurrence. Returns (h_seq, hT)."""
    if impl == "pallas":
        return rglru_pallas(log_a, gx, chunk=chunk,
                            interpret=resolve_interpret(interpret))
    return rglru_scan(log_a, gx)
