"""Jitted entry points for the fused Thres+Med kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.motion_post.kernel import BLOCK_H, motion_post_pallas
from repro.kernels.motion_post.ref import DEFAULT_THRESHOLD, motion_post_ref


@functools.partial(jax.jit, static_argnames=("impl", "threshold", "block_h", "interpret"))
def motion_post(cur: jax.Array, prev: jax.Array, *,
                threshold: float = DEFAULT_THRESHOLD, impl: str = "xla",
                block_h: int = BLOCK_H,
                interpret: Optional[bool] = None) -> jax.Array:
    cur = cur.astype(jnp.float32)
    prev = prev.astype(jnp.float32)
    if impl == "pallas":
        return motion_post_pallas(cur, prev, threshold=threshold,
                                  block_h=block_h,
                                  interpret=resolve_interpret(interpret))
    return motion_post_ref(cur, prev, threshold)
