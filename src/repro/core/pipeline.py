"""Pipeline-parallel execution of actor chains — shard_map + ppermute.

The paper's heterogeneous runtime streams tokens between processors through
Eq. 1 double buffers.  On a TPU mesh the same structure is the classic
double-buffered microbatch pipeline: each mesh slice owns one *stage*
(a fused run of actors / LM layers), stage-to-stage FIFO channels become
``lax.ppermute`` transfers, and the Eq. 1 ``2r`` capacity is exactly the
send/recv double buffer that lets transfer i+1 overlap compute i.

``pipeline_spmd`` implements the GPipe-style schedule with B microbatches
over S stages in B + S - 1 ticks.  It is expressed with ``shard_map`` so
the ppermute is explicit (not GSPMD-inferred) and composes with the data/
model axes of the production mesh.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_spmd(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any,
                  microbatches: jax.Array,
                  mesh: Mesh,
                  axis: str = "stage") -> jax.Array:
    """Run ``microbatches`` through a pipeline of identical-signature stages.

    Args:
      stage_fn: ``(params_for_stage, x) -> y`` with ``y.shape == x.shape``
        (LM blocks satisfy this; heterogeneous IO needs a wrapper pair).
      stage_params: pytree whose leaves have a leading ``n_stages`` axis,
        sharded along ``axis``.
      microbatches: ``(n_micro, *x_shape)`` array, replicated along ``axis``.
      mesh: mesh containing ``axis`` (size = n_stages).
      axis: mesh axis name carrying the pipeline.

    Returns ``(n_micro, *x_shape)`` outputs of the final stage (valid on
    every shard — gathered via the closing ppermute ring).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    n_ticks = n_micro + n_stages - 1

    def per_shard(params, mb):
        # params: leaves (1, ...) — this stage's slice;  mb: (n_micro, *x).
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        x_shape = mb.shape[1:]

        def tick(carry, t):
            recv, outs = carry
            # Stage 0 ingests microbatch t (when in range); others use recv.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fed = jax.lax.dynamic_index_in_dim(mb, mb_idx, 0, keepdims=False)
            x = jnp.where(stage == 0, fed, recv)
            active = jnp.logical_and(t - stage >= 0, t - stage < n_micro)
            y = jax.lax.cond(active, lambda v: stage_fn(params, v), lambda v: v, x)
            # Collect at the last stage: microbatch (t - (S-1)) completes at t.
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            do_collect = jnp.logical_and(stage == n_stages - 1,
                                         t - (n_stages - 1) >= 0)
            outs = jax.lax.cond(
                do_collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                lambda o: o,
                outs)
            # Double-buffered shift to the next stage (Eq. 1 2r analogue:
            # ppermute's send buffer + next tick's recv).
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        outs0 = jnp.zeros((n_micro,) + x_shape, microbatches.dtype)
        (_, outs), _ = jax.lax.scan(tick, (jnp.zeros(x_shape, mb.dtype), outs0),
                                    jnp.arange(n_ticks))
        # Broadcast final-stage results so every shard returns the same
        # (replicated-out) value: only the last stage contributes to the
        # psum (a one-hop broadcast in disguise).
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, microbatches)


def pipeline_reference(stage_fn: Callable[[Any, jax.Array], jax.Array],
                       stage_params: Any,
                       microbatches: jax.Array) -> jax.Array:
    """Oracle: run stages sequentially (no mesh) — for pipeline tests."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_one(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda l: l[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(run_one)(microbatches)
