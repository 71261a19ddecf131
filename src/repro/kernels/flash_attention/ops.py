"""Jitted entry point: dispatches flash attention to pallas or the oracle."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "impl", "bq", "bk",
                                    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "pallas",
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk,
                                      interpret=resolve_interpret(interpret))
    return flash_attention_ref(q, k, v, causal=causal, window=window)
