"""Jitted public entry points for the Gauss 5x5 actor kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.gauss5x5.kernel import BLOCK_H, gauss5x5_pallas
from repro.kernels.gauss5x5.ref import gauss5x5_ref


@functools.partial(jax.jit, static_argnames=("impl", "block_h", "interpret"))
def gauss5x5(frame: jax.Array, *, impl: str = "xla", block_h: int = BLOCK_H,
             interpret: Optional[bool] = None) -> jax.Array:
    """5x5 binomial Gaussian filter, border-skipping per the paper.

    impl="xla"    — pure-jnp reference path (used by dry-run / CPU).
    impl="pallas" — TPU Pallas kernel (interpret mode off-TPU).
    """
    frame = frame.astype(jnp.float32)
    if impl == "pallas":
        return gauss5x5_pallas(frame, block_h=block_h,
                               interpret=resolve_interpret(interpret))
    return gauss5x5_ref(frame)
