"""Pallas TPU kernel fusing the Thres and Med actors (motion detection).

Both actors are elementwise/stencil ops on the same frame pair, so on TPU
they fuse into a single VMEM pass: |cur - prev| > T, then a plus-shaped
5-point median via a min/max network (VPU-friendly — no data-dependent
branches).  This is the actor-merging optimization the paper applies on
the accelerated path ([22]) expressed as one kernel.

Tiling mirrors gauss5x5: whole padded frames resident in VMEM, grid walks
output row slabs with a 2-row halo (1 for the median + 1 safety margin is
not needed — exactly 1 row halo required; we keep the gauss slab walker
shape for uniformity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gauss5x5.kernel import BLOCK_H
from repro.kernels.motion_post.ref import DEFAULT_THRESHOLD, median5


def _motion_post_kernel(cur_ref, prev_ref, o_ref, *, block_h: int, H: int,
                        threshold: float):
    i = pl.program_id(0)
    W = o_ref.shape[1]
    # Slabs of the 1-row edge-padded difference map: rows [i*bh, i*bh+bh+2).
    cur = cur_ref[pl.ds(i * block_h, block_h + 2), :]
    prev = prev_ref[pl.ds(i * block_h, block_h + 2), :]
    m = jnp.where(jnp.abs(cur - prev) > threshold, 255.0, 0.0)

    # Plus-shaped median on the slab; columns edge-padded locally.
    mp = jnp.concatenate([m[:, :1], m, m[:, -1:]], axis=1)
    c = mp[1:block_h + 1, 1:W + 1]
    u = mp[0:block_h, 1:W + 1]
    d = mp[2:block_h + 2, 1:W + 1]
    l = mp[1:block_h + 1, 0:W]
    r = mp[1:block_h + 1, 2:W + 2]
    o_ref[...] = median5(u, d, l, r, c)


def motion_post_pallas(cur: jax.Array, prev: jax.Array, *,
                       threshold: float = DEFAULT_THRESHOLD,
                       block_h: int = BLOCK_H, interpret: bool = False) -> jax.Array:
    """cur/prev: (H, W) f32. Fused thres+median motion map."""
    H, W = cur.shape
    if H % block_h:
        raise ValueError(f"H={H} not divisible by block_h={block_h}")

    def pad1(x):
        return jnp.concatenate([x[:1], x, x[-1:]], axis=0).astype(jnp.float32)

    kern = functools.partial(_motion_post_kernel, block_h=block_h, H=H,
                             threshold=float(threshold))
    return pl.pallas_call(
        kern,
        grid=(H // block_h,),
        in_specs=[pl.BlockSpec((H + 2, W), lambda i: (0, 0)),
                  pl.BlockSpec((H + 2, W), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_h, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.float32),
        interpret=interpret,
    )(pad1(cur), pad1(prev))
