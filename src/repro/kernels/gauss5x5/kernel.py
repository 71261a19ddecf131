"""Pallas TPU kernel for the 5x5 Gaussian actor.

TPU adaptation of the paper's OpenCL Gauss kernel: instead of a work-item
per pixel, the frame is processed in VMEM-resident row slabs and the 2-D
binomial kernel is applied **separably** (vertical then horizontal 5-tap
passes: 10 multiplies/pixel instead of 25) — the VPU is an (8,128) vector
unit, so row-contiguous slabs are the natural tiling.

Tiling: the (edge-padded) frame is small enough to live in VMEM whole
(QVGA f32 = 300 KB, VGA = 1.2 MB « 16 MB), so the input BlockSpec maps the
full array and the grid walks output row slabs; each step slices its
haloed slab with ``pl.ds``.  This trades a little VMEM for zero re-DMA of
halo rows — the same contiguous-window reasoning as the paper's Eq. 1
buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gauss5x5.ref import KERNEL_1D

_W1D = [float(w) for w in KERNEL_1D]

#: Output rows per grid step: a multiple of the (8, 128) tile's 8 rows
#: that divides QVGA's 240.
BLOCK_H = 48


def _gauss_kernel(x_ref, o_ref, *, block_h: int, H: int):
    """One grid step: filter ``block_h`` output rows from the padded frame."""
    i = pl.program_id(0)
    W = o_ref.shape[1]
    # Haloed slab: padded rows [i*block_h, i*block_h + block_h + 4).
    x = x_ref[pl.ds(i * block_h, block_h + 4), :]

    # Vertical 5-tap pass -> (block_h, W).
    v = jnp.zeros((block_h, W), jnp.float32)
    for t in range(5):
        v = v + _W1D[t] * x[t:t + block_h, :]

    # Horizontal 5-tap pass on edge-padded columns.
    hpad = jnp.concatenate([v[:, :1], v[:, :1], v, v[:, -1:], v[:, -1:]], axis=1)
    h = jnp.zeros((block_h, W), jnp.float32)
    for t in range(5):
        h = h + _W1D[t] * hpad[:, t:t + W]

    # Border policy (paper §4.1): skip 2 rows top/bottom (+2 cols, see ref).
    centre = x[2:2 + block_h, :]  # the unfiltered pixels of this block
    row_ids = i * block_h + jax.lax.broadcasted_iota(jnp.int32, (block_h, W), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (block_h, W), 1)
    border = (row_ids < 2) | (row_ids >= H - 2) | (col_ids < 2) | (col_ids >= W - 2)
    o_ref[...] = jnp.where(border, centre, h)


def gauss5x5_pallas(frame: jax.Array, *, block_h: int = BLOCK_H,
                    interpret: bool = False) -> jax.Array:
    """frame: (H, W) f32 in [0,255]. H must be divisible by block_h."""
    H, W = frame.shape
    if H % block_h:
        raise ValueError(f"H={H} not divisible by block_h={block_h}")
    grid = (H // block_h,)

    # Edge-pad 2 rows each side so halo slicing needs no clamping.
    padded = jnp.concatenate([frame[:1], frame[:1], frame, frame[-1:], frame[-1:]],
                             axis=0).astype(jnp.float32)

    kern = functools.partial(_gauss_kernel, block_h=block_h, H=H)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((H + 4, W), lambda i: (0, 0))],  # whole padded frame
        out_specs=pl.BlockSpec((block_h, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.float32),
        interpret=interpret,
    )(padded)
