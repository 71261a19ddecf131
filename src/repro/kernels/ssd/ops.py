"""Jitted entry point for the SSD kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import ssd_chunked


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def ssd(x, dt, A, B_, C_, *, chunk: int = 256, impl: str = "pallas",
        interpret: Optional[bool] = None):
    """Chunked SSD scan. Returns (y, final_state)."""
    if impl == "pallas":
        return ssd_pallas(x, dt, A, B_, C_, chunk=chunk,
                          interpret=resolve_interpret(interpret))
    return ssd_chunked(x, dt, A, B_, C_, chunk=chunk)
