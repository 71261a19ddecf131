"""Pallas TPU kernels (target: pl.pallas_call + BlockSpec VMEM tiling).
Each subpackage: kernel.py (pallas), ops.py (jitted dispatch), ref.py
(pure-jnp oracle).

Every public wrapper takes ``interpret=None``: compiled by Mosaic on a TPU
backend, Pallas interpret mode on any other backend (the CPU tier-1
tests).  :func:`resolve_interpret` is the one place that decides it."""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> compiled on a TPU backend, interpret mode elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


