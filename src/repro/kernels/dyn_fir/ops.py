"""Jitted entry points for the DPD branch kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.dyn_fir.kernel import dpd_branch_pallas
from repro.kernels.dyn_fir.ref import branch_ref


@functools.partial(jax.jit, static_argnames=("order", "impl", "block", "interpret"))
def dpd_branch(x_re: jax.Array, x_im: jax.Array, h_re: jax.Array,
               h_im: jax.Array, *, order: int, impl: str = "xla",
               block: int = 1024, interpret: Optional[bool] = None):
    """One Poly actor's computation (basis + complex FIR)."""
    if impl == "pallas":
        return dpd_branch_pallas(x_re, x_im, h_re, h_im, order=order,
                                 block=block,
                                 interpret=resolve_interpret(interpret))
    return branch_ref(x_re, x_im, h_re, h_im, order)
