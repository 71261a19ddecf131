"""The persistent Pallas megakernel: device-resident dynamic scheduling.

One ``pl.pallas_call`` executes the whole network to quiescence:

  * every **buffered** Eq. 1 ring is staged into a **scratch** allocation
    (``pltpu.VMEM`` shapes from :meth:`MegakernelLayout.scratch_shape`)
    at kernel entry and copied back to the HBM outputs at exit — between
    those two copies no channel traffic leaves the device's fast memory;
  * **forwarded** channels (``GridPartition.forwarded_fifos``: the
    core-private subset of the ``register_fifos`` transient analysis) get
    no scratch ring and no HBM input operand at all — their Eq. 1
    capacity lives as a **loop-carried token window** of the sweep loop,
    written and read with the same masked offset arithmetic as the ring
    path, initialized to the dead-slot zeros of ``init_state``;
  * FIFO cursors (rd / wr / occ per channel) and actor states are
    **loop-carried values** of the in-kernel sweep ``lax.while_loop`` —
    the register-resident analogue of ``FifoState``'s scalars.  The
    cursor block is **split per core** (``GridPartition.cursor_rows``):
    each core's private channels pack into that core's own block and only
    partition-crossing channels share the semaphore block, so the
    coherence surface a parallel grid mapping must fence is exactly the
    shared block;
  * the sweep loop itself is the paper's §3.3 device-resident scheduler:
    each sweep visits every actor in declaration order, peeks its control
    token straight out of channel storage, and predicates up to
    ``_max_fireable``-many firings on ring occupancy via ``lax.cond`` —
    the exact blocking semantics of the host-side token-driven executor,
    with no host round trip per dispatch decision.

**Closure hoisting.**  Actor functions close over arrays staged at graph
build time (DPD's reconfiguration schedule, the MoE layer weights).
``pallas_call`` requires every array a kernel touches to be an explicit
operand, so :func:`_hoist_consts` traces each actor's ``fire`` /
``control`` / ``ready`` once at compile time, lifts the captured arrays
out of the jaxpr, and the runner passes them as extra kernel inputs —
weights enter the megakernel the same way they would enter any other
accelerator kernel.

**Bit-identity contract.**  The channel helpers (``_chan_read_masked``,
``_chan_write_masked``, ``_chan_peek``) mirror ``FifoSpec.read_masked`` /
``write_masked`` / ``peek`` operation for operation — same offsets, same
masked-window rewrite (disabled writes rewrite the current bytes, no
``lax.cond`` identity arm), same predicated slot-0 delay copy-back — and
``_fire`` / ``_can_fire`` / ``_max_fireable`` mirror their
``repro.core.executor`` namesakes.  Final states, fire counts and sweep
counts are therefore bit-identical to ``compile_dynamic`` (pinned by
``tests/test_megakernel.py``; the ring helpers alone are pinned against
the queue oracle in ``tests/test_megakernel_ring.py``) — with ONE
carve-out, mirroring the static specializer's dead-slot rule: a
*forwarded* channel's loop-carried window starts from ``init_state``'s
zeros instead of the incoming HBM buffer, so its **stale** ring bytes
are no longer part of the contract (from a fresh ``init_state`` even
those coincide — the masked updates evolve identical bytes from an
identical zero start).  Live tokens, cursors, actor states, fire counts
and sweeps remain exact; like the static specializer, forwarded
channels must enter **drained** (occupancy 0 — checked per run when
cursors are concrete), else compile with ``specialize=False`` to keep
every ring in scratch.

**Backends.**  On a TPU backend the kernel is compiled by Mosaic, and a
body Mosaic cannot lower raises its lowering error; there is no interpret
fallback on the chip.  On any other backend it runs in Pallas interpret
mode (:func:`repro.kernels.resolve_interpret`), which is how the CPU
tests run it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.executor import (_MAX_FIRINGS_PER_VISIT, RuntimeMode,
                                 _is_concrete, assert_mode_allows)
from repro.core.fifo import FifoSpec, FifoState
from repro.core.health import (HealthState, init_health, read_guard_bits,
                               true_occupancy, write_guard_bits)
from repro.core.megakernel.lower import (CURSOR_FIELDS, FiringRow,
                                         GridPartition, MegakernelLayout,
                                         lower_network, partition_layout)
from repro.core.network import Network, NetworkState
from repro.core.trace import TraceState
from repro.kernels import resolve_interpret

# Cursor row layout inside each packed (rows, 3) cursor block.
_RD, _WR, _OCC = 0, 1, 2


# --------------------------------------------------------------------------- #
# Channel storage — scratch ring refs for buffered channels, loop-carried
# token windows for forwarded ones, and the per-core cursor-block split.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class _ChannelStore:
    """Trace-time view of the kernel's channel storage.

    ``rings`` holds the scratch refs of buffered channels (indexed via
    ``ring_pos``); forwarded channels live in the ``wins`` tuple threaded
    through the sweep carry (indexed via ``fwd_pos``).  ``cursor_slot``
    maps a flat channel index to its ``(block, row)`` in the split
    cursor-block tuple (``GridPartition.cursor_rows``: one private block
    per core, then the shared semaphore block).
    """

    specs: Tuple[FifoSpec, ...]
    rings: Tuple[Any, ...]
    ring_pos: Dict[int, int]
    fwd_pos: Dict[int, int]
    cursor_slot: Tuple[Tuple[int, int], ...]


def _cur(curs: Tuple[jax.Array, ...], slot: Tuple[int, int],
         field: int) -> jax.Array:
    block, row = slot
    return curs[block][row, field]


def _cur_advance(curs: Tuple[jax.Array, ...], slot: Tuple[int, int],
                 rd=None, wr=None, occ=None) -> Tuple[jax.Array, ...]:
    block, row = slot
    blk = curs[block]
    if rd is not None:
        blk = blk.at[row, _RD].add(rd)
    if wr is not None:
        blk = blk.at[row, _WR].add(wr)
    if occ is not None:
        blk = blk.at[row, _OCC].add(occ)
    return curs[:block] + (blk,) + curs[block + 1:]


# --------------------------------------------------------------------------- #
# Channel ops — FifoSpec's masked API, re-expressed on the channel store.
# Each mirrors its fifo.py namesake bit for bit; the phase-offset
# arithmetic is *shared* with FifoSpec (_read_offset / _write_offset) so a
# future phase-scheme change cannot diverge silently.  The forwarded path
# runs the same offsets and the same masked-window rewrite on the carried
# window value, so from identical initial bytes every byte evolves
# identically to a ring.
# --------------------------------------------------------------------------- #
def _window_slice(store: _ChannelStore, wins: Tuple[jax.Array, ...],
                  fi: int, off: jax.Array, size: int) -> jax.Array:
    p = store.fwd_pos.get(fi)
    if p is not None:
        return jax.lax.dynamic_slice_in_dim(wins[p], off, size, axis=0)
    return store.rings[store.ring_pos[fi]][pl.ds(off, size)]


def _chan_peek(store: _ChannelStore, wins, curs, fi: int) -> jax.Array:
    """``FifoSpec.peek``: next single token, cursor untouched."""
    spec = store.specs[fi]
    off = spec._read_offset(_cur(curs, store.cursor_slot[fi], _RD))
    return _window_slice(store, wins, fi, off, 1)[0]


def _chan_read(store: _ChannelStore, wins, curs,
               fi: int) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """``FifoSpec.read``: unconditional window consume (control ports)."""
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    off = spec._read_offset(_cur(curs, slot, _RD))
    window = _window_slice(store, wins, fi, off, spec.rate)
    curs = _cur_advance(curs, slot, rd=1, occ=-spec.rate)
    return window, curs


def _chan_read_masked(store: _ChannelStore, wins, curs, fi: int,
                      enabled: jax.Array
                      ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """``FifoSpec.read_masked``: static-shaped window, masked cursor
    advance; disabled reads return the current (stale) slots exactly as
    the functional API does, so gated consumers see identical bytes."""
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    off = spec._read_offset(_cur(curs, slot, _RD))
    window = _window_slice(store, wins, fi, off, spec.rate)
    e = enabled.astype(jnp.int32)
    curs = _cur_advance(curs, slot, rd=e, occ=-e * spec.rate)
    return window, curs


def _chan_write_masked(store: _ChannelStore, wins, curs, fi: int,
                       tokens: jax.Array, enabled: jax.Array
                       ) -> Tuple[Tuple[jax.Array, ...],
                                  Tuple[jax.Array, ...]]:
    """``FifoSpec.write_masked``: the window slot is rewritten
    unconditionally with either the new tokens or its current content
    (no cond identity arm), and delay channels fold the Fig. 2 copy-back
    into a predicated single-token rewrite of slot 0 (forwarded channels
    are delay-free by construction, so only the ring path carries it)."""
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    e = enabled.astype(jnp.int32)
    off = spec._write_offset(_cur(curs, slot, _WR))
    p = store.fwd_pos.get(fi)
    if p is not None:
        cur = jax.lax.dynamic_slice_in_dim(wins[p], off, spec.rate, axis=0)
        eff = jnp.where(enabled, jnp.asarray(tokens, spec.dtype), cur)
        w = jax.lax.dynamic_update_slice_in_dim(wins[p], eff, off, axis=0)
        wins = wins[:p] + (w,) + wins[p + 1:]
    else:
        ring = store.rings[store.ring_pos[fi]]
        cur = ring[pl.ds(off, spec.rate)]
        eff = jnp.where(enabled, jnp.asarray(tokens, spec.dtype), cur)
        ring[pl.ds(off, spec.rate)] = eff
        if spec.delay:
            do_copy = jnp.logical_and(
                enabled, (_cur(curs, slot, _WR) % spec.n_write_phases) == 2)
            slot0 = jnp.where(do_copy, ring[3 * spec.rate], ring[0])
            ring[pl.ds(0, 1)] = slot0[None]
    curs = _cur_advance(curs, slot, wr=e, occ=e * spec.rate)
    return wins, curs


# --------------------------------------------------------------------------- #
# Guarded channel ops — the health layer's in-kernel fault flags.  Each
# wrapper snapshots the pre-op cursors, runs the UNCHANGED unguarded op,
# and ORs the fault bits into the loop-carried HealthState: guards observe
# channel traffic, they never alter it, so a guarded clean run's ring
# bytes / cursors / states stay bit-identical to the unguarded kernel.
# The `_chan_*` ops above keep their signatures (pinned against the queue
# oracle by tests/test_megakernel_ring.py).
# --------------------------------------------------------------------------- #
def _chan_read_guarded(store: _ChannelStore, wins, curs, fi: int,
                       hlth: HealthState):
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    rd, wr, occ = (_cur(curs, slot, _RD), _cur(curs, slot, _WR),
                   _cur(curs, slot, _OCC))
    window, curs = _chan_read(store, wins, curs, fi)
    bits = read_guard_bits(spec, rd, wr, occ, jnp.bool_(True), window)
    return window, curs, hlth.record(fi, bits)


def _chan_read_masked_guarded(store: _ChannelStore, wins, curs, fi: int,
                              enabled: jax.Array, hlth: HealthState):
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    rd, wr, occ = (_cur(curs, slot, _RD), _cur(curs, slot, _WR),
                   _cur(curs, slot, _OCC))
    window, curs = _chan_read_masked(store, wins, curs, fi, enabled)
    bits = read_guard_bits(spec, rd, wr, occ, enabled, window)
    return window, curs, hlth.record(fi, bits)


def _chan_write_masked_guarded(store: _ChannelStore, wins, curs, fi: int,
                               tokens: jax.Array, enabled: jax.Array,
                               hlth: HealthState):
    spec = store.specs[fi]
    slot = store.cursor_slot[fi]
    rd, wr, occ = (_cur(curs, slot, _RD), _cur(curs, slot, _WR),
                   _cur(curs, slot, _OCC))
    wins, curs = _chan_write_masked(store, wins, curs, fi, tokens, enabled)
    bits = write_guard_bits(spec, rd, wr, occ, enabled, tokens)
    e = enabled.astype(jnp.int32)
    occ_after = true_occupancy(spec, rd, wr) + e * spec.rate
    return wins, curs, hlth.record(fi, bits).mark_high_water(fi, occ_after)


# --------------------------------------------------------------------------- #
# Closure hoisting: actor fns -> (jaxpr-eval callable, captured arrays).
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class _HoistedFn:
    """One actor function with its closure arrays lifted out.

    ``call(args, const_values)`` evaluates the traced jaxpr with the
    hoisted arrays substituted back in as inputs; ``const_ids`` index into
    the layout-wide deduplicated const table.  When ``const_ids`` is empty
    the original Python callable is used directly (preserving trace-time
    constant folding on concrete rates, exactly like the host executors).
    """

    call: Callable
    const_ids: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class _ActorFns:
    fire: _HoistedFn
    control: Optional[_HoistedFn]
    ready: Optional[_HoistedFn]


def _hoist_fn(fn: Callable, example_args: Tuple[Any, ...],
              register: Callable[[List[Any]], Tuple[int, ...]]) -> _HoistedFn:
    """Trace ``fn`` once against abstract example args; lift the jaxpr's
    captured concrete arrays into the shared const table."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    if not closed.consts:
        return _HoistedFn(call=lambda args, _consts: fn(*args),
                          const_ids=())
    in_tree = jax.tree.structure(example_args)
    out_tree = jax.tree.structure(out_shape)
    const_ids = register(list(closed.consts))
    jaxpr = closed.jaxpr

    def call(args: Tuple[Any, ...], const_values: List[jax.Array]) -> Any:
        flat, tree = jax.tree.flatten(args)
        if tree != in_tree:
            raise ValueError(
                f"megakernel hoisted call: argument structure {tree} does "
                f"not match the traced structure {in_tree}")
        outs = jax.core.eval_jaxpr(jaxpr, const_values, *flat)
        return jax.tree.unflatten(out_tree, outs)

    return _HoistedFn(call=call, const_ids=const_ids)


def _hoist_consts(network: Network, layout: MegakernelLayout
                  ) -> Tuple[Dict[str, _ActorFns], List[jax.Array]]:
    """Build per-actor hoisted fire/control/ready callables plus the
    deduplicated table of every array any actor closure captures."""
    example = jax.eval_shape(network.init_state)
    consts: List[jax.Array] = []
    seen: Dict[int, int] = {}
    # The dedup key is id(original); jnp.asarray may *copy* (numpy
    # consts), so the original must be kept alive for as long as `seen`
    # is consulted or a recycled id could alias a later actor's const to
    # the wrong operand.
    keepalive: List[Any] = []

    def register(arrs: List[Any]) -> Tuple[int, ...]:
        ids = []
        for arr in arrs:
            key = id(arr)
            if key not in seen:
                seen[key] = len(consts)
                consts.append(jnp.asarray(arr))
                keepalive.append(arr)
            ids.append(seen[key])
        return tuple(ids)

    fns: Dict[str, _ActorFns] = {}
    for row in layout.firing_table:
        a = network.actors[row.name]
        st_ex = example.actors[row.index]
        wins_ex = {
            pb.port: jax.ShapeDtypeStruct(
                (layout.fifo_specs[pb.fifo].rate,)
                + tuple(layout.fifo_specs[pb.fifo].token_shape),
                layout.fifo_specs[pb.fifo].dtype)
            for pb in row.inputs
        }
        control = None
        if row.control is not None:
            cspec = layout.fifo_specs[row.control]
            tok_ex = jax.ShapeDtypeStruct(tuple(cspec.token_shape),
                                          cspec.dtype)
            rate_keys = list(jax.eval_shape(a.control, tok_ex))
            missing = (set(a.in_ports) | set(a.out_ports)) - set(rate_keys)
            if missing:
                raise ValueError(
                    f"actor {row.name}: control() must set a rate for every "
                    f"regular port; missing {sorted(missing)}")
            control = _hoist_fn(a.control, (tok_ex,), register)
        else:
            rate_keys = list(a.in_ports) + list(a.out_ports)
        rates_ex = {k: jax.ShapeDtypeStruct((), jnp.int32)
                    for k in rate_keys}
        fire = _hoist_fn(a.fire, (st_ex, wins_ex, rates_ex), register)
        ready = (_hoist_fn(a.ready, (st_ex,), register)
                 if row.has_ready else None)
        fns[row.name] = _ActorFns(fire=fire, control=control, ready=ready)
    return fns, consts


# --------------------------------------------------------------------------- #
# In-kernel firing protocol — mirrors executor.fire_actor's masked path.
# --------------------------------------------------------------------------- #
def _rates_for(a, fns: _ActorFns, consts: List[jax.Array],
               ctrl_tok: Optional[jax.Array]) -> Dict[str, jax.Array]:
    """``ActorSpec.rates_for`` with the hoisted control function."""
    one = jnp.int32(1)
    if not a.is_dynamic:
        return {p: one for p in (*a.in_ports, *a.out_ports)}
    raw = fns.control.call(
        (ctrl_tok,), [consts[i] for i in fns.control.const_ids])
    return {k: jnp.asarray(v, jnp.int32) for k, v in raw.items()}


def _can_fire(network: Network, layout: MegakernelLayout, row: FiringRow,
              fns: _ActorFns, consts: List[jax.Array], store: _ChannelStore,
              wins: Tuple[jax.Array, ...], curs: Tuple[jax.Array, ...],
              actors: Tuple[Any, ...]) -> jax.Array:
    """Blocking predicate of paper §2.2 on channel-store occupancies —
    mirrors ``executor._can_fire`` (same and-tree order, control token
    peeked).  Occupancies of crossing channels come from the shared
    cursor block — the in-kernel semaphore poll."""
    a = network.actors[row.name]
    specs = layout.fifo_specs
    slot = store.cursor_slot
    ok = jnp.bool_(True)
    if row.has_ready:
        ok = jnp.logical_and(ok, fns.ready.call(
            (actors[row.index],), [consts[i] for i in fns.ready.const_ids]))
    if row.control is not None:
        ci = row.control
        ok = jnp.logical_and(ok, _cur(curs, slot[ci], _OCC) >= 1)  # can_peek
        rates = _rates_for(a, fns, consts, _chan_peek(store, wins, curs, ci))
    else:
        rates = _rates_for(a, fns, consts, None)
    for pb in row.inputs:
        spec = specs[pb.fifo]
        have = _cur(curs, slot[pb.fifo], _OCC) >= spec.rate
        ok = jnp.logical_and(ok, jnp.logical_or(rates[pb.port] == 0, have))
    for pb in row.outputs:
        spec = specs[pb.fifo]
        room = (_cur(curs, slot[pb.fifo], _OCC) + spec.rate
                <= spec.writable_occupancy_bound)
        ok = jnp.logical_and(ok, jnp.logical_or(rates[pb.port] == 0, room))
    return ok


def _max_fireable(layout: MegakernelLayout, row: FiringRow,
                  store: _ChannelStore,
                  curs: Tuple[jax.Array, ...]) -> jax.Array:
    """Occupancy-derived multi-firing bound — mirrors
    ``executor._max_fireable`` (PRUNE-style decidable bound)."""
    slot = store.cursor_slot
    if row.control is not None:
        return jnp.minimum(jnp.int32(_MAX_FIRINGS_PER_VISIT),
                           _cur(curs, slot[row.control], _OCC))
    specs = layout.fifo_specs
    k = jnp.int32(_MAX_FIRINGS_PER_VISIT)
    for pb in row.inputs:
        k = jnp.minimum(k, _cur(curs, slot[pb.fifo], _OCC)
                        // specs[pb.fifo].rate)
    for pb in row.outputs:
        spec = specs[pb.fifo]
        room = spec.writable_occupancy_bound - _cur(curs, slot[pb.fifo], _OCC)
        k = jnp.minimum(k, room // spec.rate)
    return k


def _fire(network: Network, layout: MegakernelLayout, row: FiringRow,
          fns: _ActorFns, consts: List[jax.Array], store: _ChannelStore,
          wins: Tuple[jax.Array, ...], curs: Tuple[jax.Array, ...],
          actors: Tuple[Any, ...], hlth: Optional[HealthState] = None
          ) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...],
                     Tuple[Any, ...], Optional[HealthState]]:
    """One firing against the channel store — mirrors
    ``executor.fire_actor``'s masked (phase=None) path step for step:
    control consume, rates, masked input reads, predicated body, masked
    output writes.  With ``hlth`` (guards on) every channel op routes
    through its ``_guarded`` wrapper, accumulating fault bits and
    high-water marks; ``hlth=None`` traces the exact pre-health ops."""
    a = network.actors[row.name]

    ctrl_tok = None
    if row.control is not None:
        if hlth is None:
            ctok, curs = _chan_read(store, wins, curs, row.control)
        else:
            ctok, curs, hlth = _chan_read_guarded(store, wins, curs,
                                                  row.control, hlth)
        ctrl_tok = ctok[0]
    rates = _rates_for(a, fns, consts, ctrl_tok)

    windows: Dict[str, jax.Array] = {}
    for pb in row.inputs:
        if hlth is None:
            windows[pb.port], curs = _chan_read_masked(
                store, wins, curs, pb.fifo, rates[pb.port] > 0)
        else:
            windows[pb.port], curs, hlth = _chan_read_masked_guarded(
                store, wins, curs, pb.fifo, rates[pb.port] > 0, hlth)

    enabled_list = [rates[p] for p in (*a.in_ports, *a.out_ports)]
    concrete_on = any(_is_concrete(e) and int(e) > 0 for e in enabled_list)
    if enabled_list:
        any_enabled = functools.reduce(
            jnp.logical_or, [e > 0 for e in enabled_list])
    else:
        any_enabled = jnp.bool_(True)

    out_specs = {pb.port: layout.fifo_specs[pb.fifo] for pb in row.outputs}

    def run_body(operand):
        st, wins = operand
        new_st, outs = fns.fire.call(
            (st, wins, rates), [consts[i] for i in fns.fire.const_ids])
        missing = set(a.out_ports) - set(outs)
        if missing:
            raise ValueError(
                f"actor {row.name}: fire() missing outputs {sorted(missing)}")
        outs = {
            p: jnp.asarray(outs[p], out_specs[p].dtype).reshape(
                (out_specs[p].rate,) + tuple(out_specs[p].token_shape))
            for p in a.out_ports
        }
        return new_st, outs

    def skip_body(operand):
        st, _ = operand
        zeros = {
            p: jnp.zeros((s.rate,) + tuple(s.token_shape), s.dtype)
            for p, s in out_specs.items()
        }
        return st, zeros

    if a.is_dynamic and not concrete_on:
        new_actor_state, outputs = jax.lax.cond(
            any_enabled, run_body, skip_body, (actors[row.index], windows))
    else:
        new_actor_state, outputs = run_body((actors[row.index], windows))

    for pb in row.outputs:
        if hlth is None:
            wins, curs = _chan_write_masked(
                store, wins, curs, pb.fifo, outputs[pb.port],
                rates[pb.port] > 0)
        else:
            wins, curs, hlth = _chan_write_masked_guarded(
                store, wins, curs, pb.fifo, outputs[pb.port],
                rates[pb.port] > 0, hlth)

    actors = actors[:row.index] + (new_actor_state,) + actors[row.index + 1:]
    return wins, curs, actors, hlth


# --------------------------------------------------------------------------- #
# Kernel body construction.
# --------------------------------------------------------------------------- #
def _build_kernel(network: Network, layout: MegakernelLayout,
                  fns: Dict[str, _ActorFns],
                  actor_treedef, scalar_leaf: List[bool],
                  scalar_const: List[bool],
                  multi_firing: bool, max_sweeps: int,
                  partition: GridPartition,
                  fwd_list: Tuple[int, ...],
                  buffered: Tuple[int, ...],
                  guards: bool = False,
                  trace_capacity: Optional[int] = None) -> Callable:
    n_fifos = len(layout.fifo_specs)
    n_actors = len(network.actors)
    n_leaves = len(scalar_leaf)
    n_consts = len(scalar_const)
    # Channel storage split: forwarded channels (loop-carried windows,
    # no HBM input / no scratch) vs buffered ones (staged scratch rings).
    # `fwd_list`/`buffered` come from compile_megakernel — the SAME
    # tuples that ordered the pallas_call's input operands and scratch
    # shapes, so ring_pos indexing cannot drift from the operand order.
    fwd_pos = {fi: p for p, fi in enumerate(fwd_list)}
    ring_pos = {fi: p for p, fi in enumerate(buffered)}
    # Per-core cursor blocks + the shared semaphore block; `cursor_order`
    # flattens the blocks, `inv_order` scatters them back into the packed
    # (n_fifos, 3) HBM layout at exit.
    cursor_rows = partition.cursor_rows
    cursor_slot = [None] * n_fifos
    for b, rows in enumerate(cursor_rows):
        for r, fi in enumerate(rows):
            cursor_slot[fi] = (b, r)
    cursor_slot = tuple(cursor_slot)

    def kernel(*refs):
        n_bufs = len(buffered)
        buf_in = refs[:n_bufs]
        cur_in = refs[n_bufs]
        leaf_in = refs[n_bufs + 1:n_bufs + 1 + n_leaves]
        const_in = refs[n_bufs + 1 + n_leaves:
                        n_bufs + 1 + n_leaves + n_consts]
        o = n_bufs + 1 + n_leaves + n_consts
        buf_out = refs[o:o + n_fifos]
        cur_out = refs[o + n_fifos]
        leaf_out = refs[o + n_fifos + 1:o + n_fifos + 1 + n_leaves]
        counts_ref = refs[o + n_fifos + 1 + n_leaves]
        sweeps_ref = refs[o + n_fifos + 2 + n_leaves]
        flags_ref = refs[o + n_fifos + 3 + n_leaves]
        extra = 4
        if guards:
            fault_ref = refs[o + n_fifos + extra + n_leaves]
            hw_ref = refs[o + n_fifos + extra + 1 + n_leaves]
            extra += 2
        if trace_capacity:
            # The device-side trace ring + its monotonic event counter —
            # extra output refs exactly like the fault refs above: absent
            # (no ref, no HLO) when tracing is off.
            trace_ref = refs[o + n_fifos + extra + n_leaves]
            tcount_ref = refs[o + n_fifos + extra + 1 + n_leaves]
            extra += 2
        rings = refs[o + n_fifos + extra + n_leaves:]
        assert len(rings) == n_bufs

        # 1. Stage the buffered Eq. 1 rings into device scratch; read the
        #    packed cursor block and split it into the per-core blocks +
        #    the shared semaphore block; actor states and hoisted closure
        #    arrays become loop-carried / trace-bound values.  Forwarded
        #    channels start from init_state's zeros (the dead-slot
        #    carve-out): their HBM buffers are not kernel inputs at all.
        for p in range(n_bufs):
            rings[p][...] = buf_in[p][...]
        store = _ChannelStore(specs=layout.fifo_specs, rings=tuple(rings),
                              ring_pos=ring_pos, fwd_pos=fwd_pos,
                              cursor_slot=cursor_slot)
        # Static per-row stacking (NOT a fancy-index gather: a constant
        # index array would become a captured jaxpr const, which
        # pallas_call rejects — the same constraint _hoist_consts works
        # around for actor closures).
        cursors_packed = cur_in[...]
        curs0 = tuple(
            jnp.stack([cursors_packed[fi] for fi in rows]) if rows
            else jnp.zeros((0, CURSOR_FIELDS), jnp.int32)
            for rows in cursor_rows)
        wins0 = tuple(
            jnp.zeros((layout.fifo_specs[fi].capacity_tokens,)
                      + tuple(layout.fifo_specs[fi].token_shape),
                      layout.fifo_specs[fi].dtype)
            for fi in fwd_list)
        leaves0 = [leaf_in[j][...].reshape(()) if scalar_leaf[j]
                   else leaf_in[j][...] for j in range(n_leaves)]
        actors0 = tuple(jax.tree.unflatten(actor_treedef, leaves0))
        consts = [const_in[j][...].reshape(()) if scalar_const[j]
                  else const_in[j][...] for j in range(n_consts)]
        if trace_capacity:
            # Output refs start undefined: zero the ring so undropped
            # slots decode deterministically even on short runs.
            trace_ref[...] = jnp.zeros((trace_capacity, 3 + n_fifos),
                                       jnp.int32)

        # 2. Device-resident sweep loop (mirrors executor._compile_dynamic:
        #    same visit order, same per-visit multi-firing bound, same
        #    quiescence condition, same sweep accounting).
        def attempt(row, wins, curs, actors, counts, hlth, tcnt, sweeps):
            ready = _can_fire(network, layout, row, fns[row.name], consts,
                              store, wins, curs, actors)

            def do(c):
                wins, curs, actors, counts, hlth = c
                wins, curs, actors, hlth = _fire(network, layout, row,
                                                 fns[row.name], consts,
                                                 store, wins, curs, actors,
                                                 hlth)
                return wins, curs, actors, counts.at[row.index].add(1), hlth

            wins, curs, actors, counts, hlth = jax.lax.cond(
                ready, do, lambda c: c, (wins, curs, actors, counts, hlth))
            if tcnt is not None:
                # One event per attempt with post-attempt occupancies —
                # written straight into the trace output ref (only the
                # scalar event counter rides the loop carry).  Static
                # per-row stacking, same constraint as the cursor blocks.
                occs = jnp.stack([_cur(curs, cursor_slot[i], _OCC)
                                  for i in range(n_fifos)])
                ev = jnp.concatenate([
                    jnp.stack([jnp.int32(row.index),
                               jnp.asarray(sweeps, jnp.int32),
                               ready.astype(jnp.int32)]),
                    occs])
                trace_ref[pl.ds(tcnt % trace_capacity, 1)] = ev[None]
                tcnt = tcnt + 1
            return wins, curs, actors, counts, hlth, tcnt, ready

        # The grid-parallel sweep (paper §3.3 actor-to-core mapping): each
        # core runs its own occupancy-bounded firing loop over its
        # partition slice of the firing table.  A core's private channels
        # keep their cursor rows in that core's own block; only crossing
        # channels sit in the shared block, so a cross-partition
        # `_can_fire` polls the remote ring's monotonic rd/wr counters
        # there — the in-kernel semaphore analogue of
        # `heterogeneous_split`'s boundary actors, now isolated to
        # exactly `GridPartition.semaphore_bytes()` of state.  The core
        # loop is traced in fixed partition order (the interpret-mode /
        # sequential-grid tie-break, which makes the schedule — and thus
        # every ring byte — deterministic by construction); a genuinely
        # parallel grid mapping only changes the interleaving, which Kahn
        # determinism keeps invisible in the final state.  Quiescence is
        # global: the sweep ends when ALL partitions report no progress.
        def sweep(carry):
            wins, curs, actors, counts, hlth, tcnt, _, sweeps = carry
            core_progress = []
            for rows_ix in partition.core_rows:
                core_fired = jnp.bool_(False)
                for ri in rows_ix:
                    row = layout.firing_table[ri]
                    if multi_firing:
                        k = _max_fireable(layout, row, store, curs)

                        def body(_, c, row=row):
                            wins, curs, actors, counts, hlth, tcnt, \
                                fired = c
                            wins, curs, actors, counts, hlth, tcnt, \
                                ready = attempt(row, wins, curs, actors,
                                                counts, hlth, tcnt, sweeps)
                            return (wins, curs, actors, counts, hlth,
                                    tcnt, jnp.logical_or(fired, ready))

                        wins, curs, actors, counts, hlth, tcnt, fired = \
                            jax.lax.fori_loop(
                                0, k, body,
                                (wins, curs, actors, counts, hlth, tcnt,
                                 jnp.bool_(False)))
                    else:
                        wins, curs, actors, counts, hlth, tcnt, fired = \
                            attempt(row, wins, curs, actors, counts, hlth,
                                    tcnt, sweeps)
                    core_fired = jnp.logical_or(core_fired, fired)
                core_progress.append(core_fired)
            fired_any = functools.reduce(jnp.logical_or, core_progress,
                                         jnp.bool_(False))
            return (wins, curs, actors, counts, hlth, tcnt, fired_any,
                    sweeps + 1)

        def cond(carry):
            _, _, _, _, _, _, fired_any, sweeps = carry
            return jnp.logical_and(fired_any, sweeps < max_sweeps)

        hlth0 = init_health(n_fifos) if guards else None
        tcnt0 = jnp.int32(0) if trace_capacity else None
        carry = (wins0, curs0, actors0,
                 jnp.zeros((n_actors,), jnp.int32), hlth0, tcnt0,
                 jnp.bool_(True), jnp.int32(0))
        wins, curs, actors, counts, hlth, tcnt, fired_any, sweeps = \
            jax.lax.while_loop(cond, sweep, carry)

        # 3. Copy the buffered rings back out of scratch and the carried
        #    windows of forwarded channels into their buffer outputs;
        #    repack the split cursor blocks; emit actor states, fire
        #    counts and the sweep count.
        for i in range(n_fifos):
            p = fwd_pos.get(i)
            if p is not None:
                buf_out[i][...] = wins[p]
            else:
                buf_out[i][...] = rings[ring_pos[i]][...]
        packed_rows = [None] * n_fifos
        for b, rows in enumerate(cursor_rows):
            for r, fi in enumerate(rows):
                packed_rows[fi] = curs[b][r]
        cur_out[...] = jnp.stack(packed_rows)
        leaves = jax.tree.leaves(actors)
        assert len(leaves) == n_leaves
        for j in range(n_leaves):
            leaf_out[j][...] = (leaves[j].reshape(1) if scalar_leaf[j]
                                else leaves[j])
        counts_ref[...] = counts
        sweeps_ref[0] = sweeps
        # Run-level STALL forensics feed: the loop left through the sweep
        # budget with work remaining (fired_any still set), not
        # quiescence.  Emitted unconditionally so even guards-off runs
        # can warn instead of silently returning partial state.
        stalled = jnp.logical_and(fired_any, sweeps >= max_sweeps)
        flags_ref[0] = stalled.astype(jnp.int32)
        if guards:
            fault_ref[...] = hlth.fault
            hw_ref[...] = hlth.high_water
        if trace_capacity:
            tcount_ref[0] = tcnt

    return kernel


# --------------------------------------------------------------------------- #
# Public entrypoint.
# --------------------------------------------------------------------------- #
class _MegaResult(tuple):
    """``(final_state, fire_counts, n_sweeps)`` — the megakernel runner's
    historical 3-tuple — extended with the health layer's host-visible
    record as attributes so existing ``s, c, sw = runner(state)`` unpacks
    keep working unchanged.

    ``stalled``  bool jax scalar: sweep loop exited via the ``max_sweeps``
                 budget with work remaining (always computed).
    ``health``   :class:`repro.core.health.HealthState` fault / high-water
                 vectors when compiled with ``guards=True``, else None.
    ``trace``    :class:`repro.core.trace.TraceState` device trace ring
                 when compiled with ``trace_capacity=N``, else None.
    """

    def __new__(cls, state, counts, sweeps, stalled, health, trace=None):
        self = tuple.__new__(cls, (state, counts, sweeps))
        self.stalled = stalled
        self.health = health
        self.trace = trace
        return self


def compile_megakernel(network: Network, max_sweeps: int = 1_000_000,
                       mode: RuntimeMode = RuntimeMode.PROPOSED,
                       multi_firing: bool = True,
                       layout: Optional[MegakernelLayout] = None,
                       cores: int = 1,
                       assign: Optional[Dict[str, int]] = None,
                       partition: Optional[GridPartition] = None,
                       cut_objective: str = "crossing",
                       forward_transients: bool = True,
                       guards: bool = False,
                       trace_capacity: Optional[int] = None) -> Callable:
    """Compile the network into one persistent Pallas kernel.

    Returns ``runner(state) -> (final_state, fire_counts, n_sweeps)`` with
    the exact signature and bit-exact results of the token-driven dynamic
    executor (``executor._compile_dynamic(..., return_sweeps=True)``) —
    modulo the forwarded-channel dead-slot carve-out (module docstring).
    The result is a :class:`_MegaResult`: the same 3-tuple, plus
    ``.stalled`` (sweep-budget exit) and ``.health`` (the in-kernel fault
    flags and high-water marks when ``guards=True``, else None).
    ``guards=True`` arms the per-channel overflow / underflow / cursor /
    non-finite guards inside the kernel's sweep loop; guards observe the
    channel ops without changing them, so clean guarded runs stay
    bit-identical, and ``guards=False`` traces the exact pre-health
    kernel (the health slot is the empty pytree ``None``).

    ``trace_capacity=N`` threads a fixed-capacity device-side trace ring
    through the sweep loop — one ``[actor, sweep, fired, occ...]`` int32
    row per firing attempt, written to an extra output ref with only the
    scalar event counter loop-carried.  Same off-path contract as the
    fault refs: ``trace_capacity=None`` adds no refs and no carry slots,
    so the untraced kernel lowers to the identical HLO, and traced runs
    stay bit-identical in states / cursors / fire counts / sweeps on
    every path (single-core, grid, forwarded windows).  The decoded
    :class:`repro.core.trace.TraceState` rides the result as ``.trace``.

    ``layout`` lets a caller that already lowered the
    network (``Program``) pass its :class:`MegakernelLayout` instead of
    lowering twice.

    ``cores`` > 1 partitions the firing table across grid partitions
    (:func:`partition_layout`; ``assign`` pins actors to cores, default
    is the contiguous ``cut_objective`` cut): each core sweeps only its
    slice and quiescence becomes global (no partition fired).  Final
    states, live ring bytes, cursors and fire counts stay bit-identical
    to the single-core kernel for every core count (Kahn determinism plus
    the fixed partition-order tie-break); the sweep count is the number
    of global rounds.  ``partition`` lets ``Program`` pass a prebuilt
    :class:`GridPartition` instead of partitioning twice (in which case
    ``cut_objective`` / ``forward_transients`` are already baked in).

    ``forward_transients=False`` disables the transient-forwarding pass:
    every channel keeps a scratch ring and the kernel is bit-identical
    to the dynamic executor with no carve-out at all (the pre-forwarding
    behaviour; also the escape hatch for resuming states whose transient
    channels are not drained).
    """
    assert_mode_allows(network, mode)
    if layout is None:
        layout = lower_network(network)
    if partition is None:
        partition = partition_layout(network, layout, cores, assign,
                                     objective=cut_objective,
                                     forward_transients=forward_transients)
    fns, const_arrays = _hoist_consts(network, layout)
    n_fifos = len(layout.fifo_specs)
    n_actors = len(network.actors)
    actor_names = tuple(network.actors)
    fwd_list = tuple(partition.forwarded_fifos)
    fwd_set = frozenset(fwd_list)
    buffered = tuple(i for i in range(n_fifos) if i not in fwd_set)
    scalar_const = [c.ndim == 0 for c in const_arrays]
    kernel_consts = [c.reshape(1) if s else c
                     for c, s in zip(const_arrays, scalar_const)]

    def run(state):
        if not isinstance(state, NetworkState):
            state = network.state_from_dict(state)
        # Forwarded channels enter as loop-carried windows, not HBM
        # operands: only the buffered rings are kernel inputs.
        bufs = [state.fifos[i].buf for i in buffered]
        cursors = jnp.stack(
            [jnp.stack([jnp.asarray(f.rd, jnp.int32),
                        jnp.asarray(f.wr, jnp.int32),
                        jnp.asarray(f.occ, jnp.int32)])
             for f in state.fifos])
        leaves, treedef = jax.tree.flatten(tuple(state.actors))
        leaves = [jnp.asarray(leaf) for leaf in leaves]
        scalar_leaf = [leaf.ndim == 0 for leaf in leaves]
        kernel_leaves = [leaf.reshape(1) if s else leaf
                         for leaf, s in zip(leaves, scalar_leaf)]

        kernel = _build_kernel(network, layout, fns, treedef, scalar_leaf,
                               scalar_const, multi_firing, max_sweeps,
                               partition, fwd_list, buffered, guards,
                               trace_capacity)
        out_shape = (
            [jax.ShapeDtypeStruct(f.buf.shape, f.buf.dtype)
             for f in state.fifos]
            + [jax.ShapeDtypeStruct((n_fifos, 3), jnp.int32)]
            + [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in kernel_leaves]
            + [jax.ShapeDtypeStruct((n_actors,), jnp.int32),
               jax.ShapeDtypeStruct((1,), jnp.int32),
               jax.ShapeDtypeStruct((1,), jnp.int32)]   # stall flag
        )
        if guards:
            out_shape += [jax.ShapeDtypeStruct((n_fifos,), jnp.int32),
                          jax.ShapeDtypeStruct((n_fifos,), jnp.int32)]
        if trace_capacity:
            out_shape += [jax.ShapeDtypeStruct(
                              (trace_capacity, 3 + n_fifos), jnp.int32),
                          jax.ShapeDtypeStruct((1,), jnp.int32)]
        scratch_shapes = [
            pltpu.VMEM(layout.scratch_shape(i), layout.fifo_specs[i].dtype)
            for i in buffered
        ]
        outs = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=resolve_interpret(None),
        )(*bufs, cursors, *kernel_leaves, *kernel_consts)

        bufs_o = outs[:n_fifos]
        cur_o = outs[n_fifos]
        leaves_o = outs[n_fifos + 1:n_fifos + 1 + len(kernel_leaves)]
        base = n_fifos + 1 + len(kernel_leaves)
        counts_vec = outs[base]
        sweeps = outs[base + 1][0]
        stalled = outs[base + 2][0] != 0
        nxt = base + 3
        health = None
        if guards:
            health = HealthState(fault=outs[nxt], high_water=outs[nxt + 1])
            nxt += 2
        trace = (TraceState(ring=outs[nxt], count=outs[nxt + 1][0])
                 if trace_capacity else None)
        leaves_o = [l.reshape(()) if s else l
                    for l, s in zip(leaves_o, scalar_leaf)]
        actors = tuple(jax.tree.unflatten(treedef, leaves_o))
        fifos = tuple(
            FifoState(buf=bufs_o[i], rd=cur_o[i, _RD], wr=cur_o[i, _WR],
                      occ=cur_o[i, _OCC])
            for i in range(n_fifos))
        final = NetworkState(fifos=fifos, actors=actors,
                             fifo_names=state.fifo_names,
                             actor_names=state.actor_names)
        counts = {nm: counts_vec[i] for i, nm in enumerate(actor_names)}
        return final, counts, sweeps, stalled, health, trace

    jitted = jax.jit(run)

    def runner(state):
        if fwd_list:
            st = (state if isinstance(state, NetworkState)
                  else network.state_from_dict(state))
            # The static specializer's drained-entry rule, per run: a
            # forwarded channel's window starts from dead-slot zeros, so
            # live tokens entering on it would be dropped.  Checked only
            # when cursors are concrete (callers jitting the runner keep
            # the contract by construction of the states they thread).
            for fi in fwd_list:
                occ = st.fifos[fi].occ
                if _is_concrete(occ) and int(occ):
                    raise ValueError(
                        f"megakernel transient forwarding: fifo "
                        f"{layout.fifo_names[fi]!r} enters with occupancy "
                        f"{int(occ)}; forwarded channels must be drained "
                        "(start from Network.init_state, or compile with "
                        "ExecutionPlan(specialize=False) to keep every "
                        "ring in scratch)")
        return _MegaResult(*jitted(state))

    # Exposed for Program.stats: the hoisted closure arrays are kernel
    # operands living in HBM alongside the state pytree, and the grid
    # partition drives the per-core scratch/occupancy telemetry.
    runner.hoisted_const_bytes = int(sum(
        c.size * c.dtype.itemsize for c in const_arrays))
    runner.grid_partition = partition
    return runner
