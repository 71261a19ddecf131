"""Multi-device sharded actor networks — the paper's multi-processor
platform mapped onto a JAX device mesh.

The paper splits one actor network across heterogeneous command queues
(GPP + GPU, §3.3); the JAX-native equivalent of "another processor" is
another device of a 1-D ``Mesh``.  ``ExecutionPlan(devices=k)`` reuses
the megakernel grid's partition machinery (``partition_layout`` with
``cores`` = devices: contiguous crossing-bytes cut, delay-channel
endpoints glued, partition-crossing channels classified ``SHARED``) and
replaces its *same-address-space* synchronization — polled cursor
semaphore rows in shared scratch — with *collective* synchronization:

  * every device holds a full replica of the :class:`NetworkState`
    pytree but sweeps ONLY its own partition of the firing table
    (``lax.switch`` on ``axis_index``, one traced sub-sweep per device,
    each reusing the exact per-actor visit body of the single-device
    dynamic executor — ``repro.core.executor._make_visit_body``);
  * at each sweep barrier every SHARED crossing channel exchanges its
    ring buffer + write cursor producer -> consumer and its read cursor
    consumer -> producer via ``jax.lax.ppermute``, then every replica
    recomputes occupancy from the channel invariant
    ``occ = delay + (wr - rd) * rate`` — the collective analogue of the
    packed cursor semaphore rows;
  * global quiescence is an all-reduce (``psum``) of the per-device
    fired-this-round flags, replacing the single scheduler's
    ``fired_any`` loop carry.

Correctness leans on two properties.  *Conservative staleness*: between
barriers a producer sees a stale (low) read cursor, so its occupancy
view is >= the truth and it can never overflow; a consumer sees a stale
(low) write cursor, so its view is <= the truth and it can never
underflow — exactly the monotonic-cursor argument that makes the grid's
polled semaphores safe (EXPERIMENTS.md §Megakernel), transplanted to a
message-passing platform.  *Kahn determinism*: blocking reads + single
writer per channel make the quiescent state independent of firing
order, so final states / ring bytes / cursors / fire counts are
bit-identical to the single-device dynamic executor for every device
count (the sharded run takes more *rounds* — barrier rounds are not
sweeps, and sweep counts are deliberately outside the contract).

Delay channels keep the grid rule: ``delay < rate`` channels may not
cross devices (``Network.validate_partition``, same Fig. 2 copy-back
race) — the copy-back executes on the producer, whose ring replica is
authoritative and is what the barrier ships.

The exit-merge is also what makes the PR 10 durability layer free at
``devices=k``: the runner takes a *host* replicated NetworkState and
returns one, so ``Program.run_checkpointed`` can cut a sharded run at
any sweep boundary, snapshot the merged state, and resume on a fresh
process/mesh — the restored state re-enters through the same
``in_specs=(P(),)`` replication, and Kahn determinism makes the resumed
run bit-identical to the uninterrupted one.

Everything here is testable on a CPU host via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see
``tests/test_shard.py``); no TPU is needed to pin the semantics.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.executor import (RuntimeMode, _make_visit_body,
                                 assert_mode_allows)
from repro.core.fifo import FifoState
from repro.core.health import HealthState, init_health
from repro.core.megakernel.lower import (GridPartition, MegakernelLayout,
                                         SHARED, _CURSOR_ITEMSIZE,
                                         lower_network, partition_layout)
from repro.core.network import Network, NetworkState
from repro.core.trace import (Trace, TraceState, decode_trace, init_trace,
                              merge_device_traces)

#: The mesh axis name of the 1-D device partition.
AXIS = "dev"


def build_device_partition(network: Network, devices: int,
                           device_assign: Optional[Mapping[str, int]] = None,
                           cut_objective: str = "crossing",
                           profile: Optional[Mapping[str, Any]] = None
                           ) -> Tuple[MegakernelLayout, GridPartition]:
    """Partition the firing table across ``devices`` mesh devices.

    Pure build-time metadata: the megakernel's ``lower_network`` +
    ``partition_layout`` run with ``cores`` = devices, so the cut
    heuristics (crossing bytes / flops balance / measured profile) and
    the delay-channel glue are shared verbatim with the grid backend.
    ``forward_transients`` stays off — transient forwarding is a
    megakernel *lowering*, while each device here runs the host dynamic
    executor over real ring state.
    """
    layout = lower_network(network)
    part = partition_layout(network, layout, cores=devices,
                            assign=(dict(device_assign)
                                    if device_assign is not None else None),
                            objective=cut_objective,
                            forward_transients=False,
                            profile=profile)
    return layout, part


def collective_bytes_per_sweep(layout: MegakernelLayout,
                               partition: GridPartition) -> int:
    """Bytes each sweep-barrier exchange moves across the mesh.

    Per crossing channel: its full Eq. 1 ring (producer -> consumer)
    plus the rd/wr cursor pair (one int each way); plus the 4-byte
    quiescence flag every round all-reduces.  The collective counterpart
    of the grid's ``shared_scratch_bytes`` polling surface — the two
    are compared side by side in EXPERIMENTS.md §Sharding.
    """
    total = _CURSOR_ITEMSIZE    # psum'd per-device progress flag
    for fi in partition.shared_fifos:
        total += (layout.fifo_specs[fi].capacity_bytes
                  + 2 * _CURSOR_ITEMSIZE)
    return total


def _crossing_edges(network: Network, layout: MegakernelLayout,
                    partition: GridPartition) -> List[Tuple[int, int, int]]:
    """``(fifo_index, producer_device, consumer_device)`` per SHARED
    channel, in layout order."""
    names = list(network.actors)
    out = []
    for fi in partition.shared_fifos:
        e = network.edge_of(layout.fifo_names[fi])
        src = partition.assignment[names.index(e.src_actor)]
        dst = partition.assignment[names.index(e.dst_actor)]
        out.append((fi, src, dst))
    return out


def compile_sharded(network: Network, layout: MegakernelLayout,
                    partition: GridPartition, max_sweeps: int = 1_000_000,
                    mode: RuntimeMode = RuntimeMode.PROPOSED,
                    multi_firing: bool = True,
                    guards: bool = False,
                    trace_capacity: Optional[int] = None) -> Callable:
    """The sharded dynamic executor: one sub-sweep per device under
    ``shard_map``, crossing channels exchanged at sweep barriers.

    Returns a runner with the single-device dynamic executor's record
    shape — ``runner(state) -> (state, counts, sweeps, stalled, health,
    trace)`` — where ``sweeps`` counts *barrier rounds* (one progress
    all-reduce each), ``health`` is the bitwise-OR / high-water merge
    across devices, and ``trace`` is the all-gathered per-device ring
    pair ``(rings (k, cap, 3+F), counts (k,))`` for
    :func:`decode_device_trace`.

    Observability caveats, by design: a traced event's occupancy sample
    is the recording device's *local view* (conservative between
    barriers), and a guarded run's ``high_water`` marks may legitimately
    exceed the single-device run's (the producer's occupancy view is an
    upper bound at write time) — both observe, neither schedules, so
    the state/counts bit-identity contract is untouched.
    """
    assert_mode_allows(network, mode)
    k = partition.n_cores
    if jax.device_count() < k:
        raise RuntimeError(
            f"compile_sharded: partition spans {k} devices but only "
            f"{jax.device_count()} are visible; on a CPU host set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={k} before "
            "jax initializes")
    # Explicit sub-mesh over the first k devices: jax.make_mesh insists
    # on covering every visible device, while a plan's device count is a
    # property of the network cut, not the host.
    mesh = Mesh(np.array(jax.devices()[:k]), (AXIS,))
    names = list(network.actors)
    n_fifos = len(network.fifos)
    crossing = _crossing_edges(network, layout, partition)
    # One traced sub-sweep per device, over that device's firing-table
    # slice in visit order — the exact per-actor body of the
    # single-device executor.
    bodies = [_make_visit_body(network, [names[i] for i in rows],
                               multi_firing)
              for rows in partition.core_rows]
    # Static merge owners: the device whose replica is authoritative for
    # each leaf at quiescence.  Actors: their partition device.  Private
    # channels: their owning device.  Crossing channels: the PRODUCER —
    # the barrier runs after every round (including the final no-fire
    # round), so its rd is synchronized at exit, its wr/ring are the
    # single writer's truth, and the consumer never writes ring bytes.
    fifo_owner = list(partition.fifo_cores)
    for fi, src, _dst in crossing:
        fifo_owner[fi] = src

    def exchange(state: NetworkState, dev: jax.Array) -> NetworkState:
        """The sweep-barrier collective: ship each crossing channel's
        ring + wr producer -> consumer and rd consumer -> producer, then
        restore every replica's occupancy from the channel invariant
        ``occ = delay + (wr - rd) * rate`` (exact on the endpoints;
        non-endpoint replicas keep their untouched init-state view)."""
        fifos = list(state.fifos)
        for fi, src, dst in crossing:
            spec = layout.fifo_specs[fi]
            fs = fifos[fi]
            fwd = [(src, dst)]
            bwd = [(dst, src)]
            buf = jax.lax.ppermute(fs.buf, AXIS, fwd)
            wr = jax.lax.ppermute(fs.wr, AXIS, fwd)
            rd = jax.lax.ppermute(fs.rd, AXIS, bwd)
            is_dst = dev == dst
            is_src = dev == src
            new_buf = jnp.where(is_dst, buf, fs.buf)
            new_wr = jnp.where(is_dst, wr, fs.wr)
            new_rd = jnp.where(is_src, rd, fs.rd)
            new_occ = (jnp.int32(spec.delay)
                       + (new_wr - new_rd) * jnp.int32(spec.rate))
            fifos[fi] = FifoState(buf=new_buf, rd=new_rd, wr=new_wr,
                                  occ=new_occ)
        return dataclasses.replace(state, fifos=tuple(fifos))

    def sharded_run(state: NetworkState):
        dev = jax.lax.axis_index(AXIS)
        counts0 = {nm: jnp.int32(0) for nm in names}
        hlth0 = init_health(n_fifos) if guards else None
        trc0 = init_trace(n_fifos, trace_capacity) if trace_capacity else None

        def branch(i):
            def run_branch(operand):
                st, cnt, h, t, sweeps = operand
                return bodies[i](st, cnt, h, t, sweeps)
            return run_branch

        branches = [branch(i) for i in range(k)]

        def sweep(carry):
            st, cnt, h, t, _, sweeps = carry
            # No collectives inside the switch: every device must issue
            # the identical exchange sequence, so the barrier sits
            # outside, once per round, unconditionally.
            st, cnt, h, t, fired = jax.lax.switch(
                dev, branches, (st, cnt, h, t, sweeps))
            st = exchange(st, dev)
            fired_any = jax.lax.psum(fired.astype(jnp.int32), AXIS) > 0
            return st, cnt, h, t, fired_any, sweeps + 1

        def cond(carry):
            _, _, _, _, fired_any, sweeps = carry
            return jnp.logical_and(fired_any, sweeps < max_sweeps)

        carry = (state, counts0, hlth0, trc0, jnp.bool_(True), jnp.int32(0))
        state, counts, hlth, trc, fired_any, sweeps = jax.lax.while_loop(
            cond, sweep, carry)
        stalled = jnp.logical_and(fired_any, sweeps >= max_sweeps)

        # ---- merge to one replicated result ---------------------------- #
        # Each leaf is taken whole from its static owner (all_gather +
        # constant index): exact for every dtype — no float re-derivation,
        # no one-hot arithmetic.
        def take(x, owner):
            return jax.lax.all_gather(x, AXIS)[owner]

        fifos = tuple(
            FifoState(buf=take(fs.buf, o), rd=take(fs.rd, o),
                      wr=take(fs.wr, o), occ=take(fs.occ, o))
            for fs, o in zip(state.fifos, fifo_owner))
        actors = tuple(
            jax.tree.map(functools.partial(take, owner=o), a)
            for a, o in zip(state.actors, partition.assignment))
        state = dataclasses.replace(state, fifos=fifos, actors=actors)
        # Fire counts: each actor is counted only on its owner (0
        # elsewhere), so an integer psum is the exact total.
        counts = {nm: jax.lax.psum(counts[nm], AXIS) for nm in names}
        if hlth is not None:
            # Fault words are bitmasks: OR across devices (a psum would
            # double-count a bit two endpoints both recorded).
            gathered = jax.lax.all_gather(hlth.fault, AXIS)
            fault = functools.reduce(jnp.bitwise_or,
                                     [gathered[d] for d in range(k)])
            hlth = HealthState(fault=fault,
                               high_water=jax.lax.pmax(hlth.high_water,
                                                       AXIS))
        if trc is not None:
            trc = (jax.lax.all_gather(trc.ring, AXIS),
                   jax.lax.all_gather(trc.count, AXIS))
        return state, counts, sweeps, stalled, hlth, trc

    sharded = jax.jit(jax.shard_map(sharded_run, mesh=mesh, in_specs=(P(),),
                                    out_specs=P(), check_vma=False))

    def run(state):
        if not isinstance(state, NetworkState):
            state = network.state_from_dict(state)
        return sharded(state)

    return run


def decode_device_trace(network: Network, trc: Optional[Tuple],
                        partition: GridPartition) -> Optional[Trace]:
    """Decode the all-gathered ``(rings (k, cap, 3+F), counts (k,))``
    pair of a sharded run into ONE :class:`repro.core.trace.Trace`:
    per-device rings are decoded independently, then interleaved by
    barrier round (stable by device), with ``actor_cores`` recording the
    mesh device of each actor — Perfetto tracks read ``actor [core d]``
    with d the device."""
    if trc is None:
        return None
    rings, counts = trc
    names = tuple(network.actors)
    devmap = {names[i]: d for d, rows in enumerate(partition.core_rows)
              for i in rows}
    per_dev = [
        decode_trace(network,
                     TraceState(ring=jnp.asarray(rings[d]),
                                count=jnp.asarray(counts[d])),
                     actor_cores=devmap)
        for d in range(partition.n_cores)
    ]
    return merge_device_traces(per_dev)
