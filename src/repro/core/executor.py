"""Network executors — paper §3.3 adapted to XLA.

The paper runs every actor on its own OS thread and lets the OS schedule
firings by data availability (blocking FIFOs).  Inside one XLA program
there are no threads, so we provide three execution strategies whose
*observable* FIFO semantics are identical.  The public entrypoint is
``Network.compile(ExecutionPlan(mode=...)) -> Program``
(repro.core.program); the strategy names below survive as deprecated
shims at the bottom of this module:

  1. ``compile_static``   — the whole network compiles to one jitted
     ``lax.scan``; one scan step = one *iteration* = one (predicated)
     firing of every actor in a topological order.  This is the analogue of
     the paper's accelerator-mapped subnetwork: maximum fusion, contiguous
     Eq. 1 buffer windows, dynamic actors predicated with ``lax.cond`` so
     rate-0 firings genuinely skip compute (the source of the paper's 5x).
     With ``specialize=True`` (default) transient channels
     (``Network.register_fifos``) are register-allocated — windows flow
     producer->consumer as traced values, no ring-buffer traffic — and the
     remaining buffered channels get their phase cycle unrolled (LCM of
     ``n_write_phases``, <= 6 — see ``phase_unroll_period``) so
     cursor-driven ``dynamic_slice`` arithmetic on statically-scheduled
     ports folds to compile-time slice offsets (EXPERIMENTS.md §Executor
     perf: DPD 1.95x).

  2. ``compile_dynamic``  — a token-driven scheduler compiled as
     ``lax.while_loop``: every sweep attempts each actor, firing it iff its
     blocking predicates hold (control token peeked to evaluate rates
     first).  This handles networks whose occupancies are data dependent —
     the general dynamic-dataflow case.  With ``multi_firing=True``
     (default) an actor is fired up to its occupancy-derived bound —
     ``min(occ // r, room // r)`` for static actors, control-channel
     occupancy for dynamic ones — per sweep via ``lax.fori_loop`` instead
     of once, reaching quiescence in strictly fewer sweeps (PRUNE,
     arXiv:1802.06625, motivates the decidable bound; CAF's OpenCL actors,
     arXiv:1709.07781, motivate batching firings per dispatch).

  3. ``run_interpreted``  — an eager Python loop (one jitted fire per
     actor), standing in for the paper's GPP-threaded execution and used as
     the measurement baseline (DAL-multicore analogue) in the benchmarks.

All executors thread a flat :class:`repro.core.network.NetworkState`
pytree (built once per network) and accept ``donate=True`` to let XLA
update FIFO buffers in place across calls.

``RuntimeMode.STATIC_DAL`` reproduces the *reference* framework's
restriction: dynamic-rate actors are rejected on the accelerated path
(DAL's OpenCL extension is limited to SDF — paper §2.3), forcing the
all-branches-active execution that the proposed framework beats.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.fifo import FifoSpec, FifoState
from repro.core.health import HealthState, init_health
from repro.core.network import Network, NetworkState
from repro.core.trace import init_trace
from repro.core.schedule import phase_unroll_period

# Legacy dict states are accepted everywhere and converted on entry.
State = Union[NetworkState, Dict[str, Any]]

# Worst-case firings of one actor per multi-firing visit.  Eq. 1 caps any
# channel at 2 (double buffer) or 3 (delay triple buffer) windows, so no
# connected actor can ever have more than 3 pending firings; 8 leaves slack
# for port-free corner cases without risking runaway loops.
_MAX_FIRINGS_PER_VISIT = 8


class RuntimeMode(enum.Enum):
    PROPOSED = "proposed"        # this paper: dynamic rates allowed everywhere
    STATIC_DAL = "static_dal"    # reference framework: SDF only on the accelerator


def assert_mode_allows(network: Network, mode: RuntimeMode,
                       accelerated: Optional[List[str]] = None) -> None:
    """DAL's OpenCL path rejects dynamic actors (paper §2.3 / §4.3)."""
    if mode is not RuntimeMode.STATIC_DAL:
        return
    accel = set(accelerated if accelerated is not None else network.actors)
    bad = [n for n in accel if network.actors[n].is_dynamic]
    if bad:
        raise ValueError(
            f"STATIC_DAL mode: dynamic-rate actors {bad} cannot be mapped to "
            "the accelerator (SDF-only reference framework); rewrite them "
            "statically or run them interpreted"
        )


def _is_concrete(x: Any) -> bool:
    """True when ``x`` is a compile-time constant (not a traced value)."""
    return not isinstance(x, jax.core.Tracer)


#: Arrays of at least this many bytes that an actor closes over (an LM's
#: weights in the serving graph's decode actor) become arguments of the
#: compiled runner; jit would otherwise embed them in the program as
#: constants.
_HOIST_CONST_BYTES = 1 << 20


def _jit_runner(run: Callable, donate: bool) -> Callable:
    """``jax.jit(run)`` for ``run(state)``, with large closed-over arrays
    passed to the compiled program as (never donated) arguments.

    The first call decides: a runner whose trace closes over no large
    array is the plain jitted function from then on."""
    jitted = jax.jit(run, donate_argnums=(0,) if donate else ())
    hoisted: Dict[Any, Callable] = {}
    plain = False

    def call(state):
        nonlocal plain
        if plain:
            return jitted(state)
        traced = jitted.trace(state)       # reused by the call below
        closed = traced.jaxpr
        fn = hoisted.get(closed)
        if fn is None:
            big = [getattr(c, "nbytes", 0) >= _HOIST_CONST_BYTES
                   for c in closed.consts]
            if not hoisted and not any(big):
                plain = True
                return jitted(state)
            fn = hoisted[closed] = _hoist(
                closed, big, jax.tree.structure(traced.out_info), donate)
        return fn(state)

    return call


def _hoist(closed, big: List[bool], out_tree, donate: bool) -> Callable:
    consts = list(closed.consts)
    args = [c for c, b in zip(consts, big) if b]

    def flat_run(args, leaves):
        it = iter(args)
        cs = [next(it) if b else c for c, b in zip(consts, big)]
        return jax.core.eval_jaxpr(closed.jaxpr, cs, *leaves)

    jitted = jax.jit(flat_run, donate_argnums=(1,) if donate else ())

    def call(state):
        out = jitted(args, jax.tree.leaves(state))
        return jax.tree.unflatten(out_tree, out)

    return call


# --------------------------------------------------------------------------- #
# Single predicated firing (shared by all executors).
# --------------------------------------------------------------------------- #
def _register_read(spec: FifoSpec, st: FifoState, window: jax.Array,
                   enabled: jax.Array) -> Tuple[jax.Array, FifoState]:
    """Consume from a register-allocated channel: the forwarded ``window``
    replaces the buffer read; cursor arithmetic matches ``read_masked``."""
    e = (enabled > 0).astype(jnp.int32)
    return window, FifoState(buf=st.buf, rd=st.rd + e, wr=st.wr,
                             occ=st.occ - e * spec.rate)


def _register_write(spec: FifoSpec, st: FifoState,
                    enabled: jax.Array) -> FifoState:
    """Produce to a register-allocated channel: the buffer is untouched
    (the window is forwarded via the regs dict); cursor arithmetic matches
    ``write_masked``."""
    e = (enabled > 0).astype(jnp.int32)
    return FifoState(buf=st.buf, rd=st.rd, wr=st.wr + e,
                     occ=st.occ + e * spec.rate)


def fire_actor(network: Network, name: str, state: State,
               phase: Optional[int] = None,
               regs: Optional[Dict[int, jax.Array]] = None,
               period: Optional[int] = None,
               health: Optional["HealthState"] = None):
    """Fire actor ``name`` once, updating FIFO and actor state.

    Implements the firing protocol of paper §2.2:
      1. dynamic actors first consume one control token;
      2. the control token pins every regular port to rate 0 or r;
      3. tokens are consumed from enabled inputs, the body computes,
         tokens are produced to enabled outputs.

    Rate-0 ports freeze their FIFO cursors; a firing whose every regular
    port is disabled skips the body entirely via ``lax.cond``.
    Callers guarantee blocking preconditions (the static scheduler proves
    them at build time; the dynamic scheduler checks them per sweep).

    ``phase`` (a Python int) enables trace-time cursor specialization for
    the static schedule, on two levels:

      * channels in ``network.register_fifos`` (transient: delay-free with
        provably-matched enables) are register-allocated — the produced
        window is forwarded to the consumer through ``regs`` (a per-
        iteration dict keyed by fifo index) as a traced value, and the ring
        buffer is never touched (only the cursor/occupancy scalars advance,
        exactly as the masked path would);
      * buffered channels whose port enable is a compile-time constant use
        static slice offsets ``(phase % n_write_phases) * r`` instead of
        cursor-driven ``dynamic_slice``.

    Valid only when the state descends from ``Network.init_state`` through
    whole phase cycles (the ``compile_static`` contract).  ``period`` is
    the unroll period ``phase`` cycles through: a buffered channel is
    offset-specialized only when its own phase cycle divides ``period``
    (``period=None`` asserts the caller's phase covers every channel).
    Genuinely data-dependent ports of buffered channels keep the masked
    dynamic-cursor path.  Observable results (actor states, cursors,
    occupancies, live tokens) are bit-identical to ``phase=None``; only
    the dead slots of register-allocated buffers differ (their content is
    unspecified by the MoC).

    ``health`` (a :class:`repro.core.health.HealthState`) arms the channel
    guards: every read/write additionally evaluates its fault-bit word
    (overflow / underflow / cursor consistency / non-finite tokens) from
    the pre-op cursors and ORs it into the per-channel fault vector.  The
    return value becomes ``(state, health)``.  Guards ride the dynamic
    masked path only — the phase-specialized static schedule proves its
    blocking preconditions at build time (``check_schedule_feasible``), so
    combining ``health`` with ``phase``/``regs`` is rejected.
    """
    if health is not None and (phase is not None or regs is not None):
        raise ValueError(
            "fire_actor: health guards apply to the dynamic (masked-cursor) "
            "path; the phase-specialized static schedule proves blocking "
            "bounds at build time — run with ExecutionPlan(mode='dynamic', "
            "guards=True) instead")
    if not isinstance(state, NetworkState):
        state = network.state_from_dict(state)
    a = network.actors[name]
    fifos = list(state.fifos)
    reg_mode = phase is not None and regs is not None

    def is_reg(spec: FifoSpec) -> bool:
        return reg_mode and spec.name in network.register_fifos

    def phase_covers(spec: FifoSpec) -> bool:
        return phase is not None and (period is None
                                      or period % spec.n_write_phases == 0)

    def forwarded(spec: FifoSpec, fi: int) -> jax.Array:
        if fi not in regs:
            raise ValueError(
                f"fifo {spec.name}: consumer {name} fired before its "
                "producer in the specialized schedule — pass a topological "
                "order (or specialize=False) to compile_static")
        return regs[fi]

    # 1. Control token (always rate 1, consumed unconditionally).
    ctrl_tok = None
    ctl = network.control_specs[name]
    if ctl is not None:
        cspec, ci = ctl
        if is_reg(cspec):
            ctok, fifos[ci] = _register_read(cspec, fifos[ci],
                                             forwarded(cspec, ci),
                                             jnp.int32(1))
        elif phase_covers(cspec):
            ctok, fifos[ci] = cspec.read_static(fifos[ci], phase)
        elif health is not None:
            ctok, fifos[ci], bits = cspec.read_guarded(fifos[ci])
            health = health.record(ci, bits)
        else:
            ctok, fifos[ci] = cspec.read(fifos[ci])
        ctrl_tok = ctok[0]  # rate-1 window -> single token

    # 2. Per-port 0/1 enables for this firing.
    rates = a.rates_for(ctrl_tok)

    # 3. Consume enabled inputs (static windows, masked cursor advance).
    windows: Dict[str, jax.Array] = {}
    for p, spec, fi in network.in_port_specs[name]:
        en = rates[p]
        if is_reg(spec):
            windows[p], fifos[fi] = _register_read(spec, fifos[fi],
                                                   forwarded(spec, fi), en)
        elif phase_covers(spec) and _is_concrete(en):
            if int(en) > 0:
                windows[p], fifos[fi] = spec.read_static(fifos[fi], phase)
            else:
                # Constant-disabled port: its cursor never moved off 0, so
                # the (unspecified-by-the-MoC) window is the slot-0 slice.
                windows[p] = jax.lax.slice_in_dim(fifos[fi].buf, 0, spec.rate,
                                                  axis=0)
        elif health is not None:
            windows[p], fifos[fi], bits = spec.read_masked_guarded(
                fifos[fi], en > 0)
            health = health.record(fi, bits)
        else:
            windows[p], fifos[fi] = spec.read_masked(fifos[fi], en > 0)

    # 4. Body, predicated on any port being enabled.
    enabled_list = [rates[p] for p in (*a.in_ports, *a.out_ports)]
    concrete_on = any(_is_concrete(e) and int(e) > 0 for e in enabled_list)
    if enabled_list:
        any_enabled = functools.reduce(jnp.logical_or, [e > 0 for e in enabled_list])
    else:
        any_enabled = jnp.bool_(True)  # pure source/sink with no regular ports

    out_specs = {p: spec for p, spec, _ in network.out_port_specs[name]}

    def run_body(operand):
        st, wins = operand
        new_st, outs = a.fire(st, wins, rates)
        missing = set(a.out_ports) - set(outs)
        if missing:
            raise ValueError(f"actor {name}: fire() missing outputs {sorted(missing)}")
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

    aidx = network.actor_index[name]
    if a.is_dynamic and not concrete_on:
        new_actor_state, outputs = jax.lax.cond(
            any_enabled, run_body, skip_body, (state.actors[aidx], windows))
    else:
        # Static actor, or a dynamic one with a constant-enabled port: the
        # body runs on every firing, so the cond would always take the true
        # branch — eliding it produces identical values without forcing XLA
        # to materialize both arms' buffer copies.
        new_actor_state, outputs = run_body((state.actors[aidx], windows))

    # 5. Produce to enabled outputs.
    for p, spec, fi in network.out_port_specs[name]:
        en = rates[p]
        if is_reg(spec):
            regs[fi] = outputs[p]
            fifos[fi] = _register_write(spec, fifos[fi], en)
        elif phase_covers(spec) and _is_concrete(en):
            if int(en) > 0:
                fifos[fi] = spec.write_static(fifos[fi], outputs[p], phase)
            # Constant-disabled port: cursor frozen, buffer untouched.
        elif health is not None:
            fifos[fi], bits, occ_after = spec.write_masked_guarded(
                fifos[fi], outputs[p], en > 0)
            health = health.record(fi, bits).mark_high_water(fi, occ_after)
        else:
            fifos[fi] = spec.write_masked(fifos[fi], outputs[p], en > 0)

    actors = list(state.actors)
    actors[aidx] = new_actor_state
    new_state = dataclasses.replace(state, fifos=tuple(fifos),
                                    actors=tuple(actors))
    if health is not None:
        return new_state, health
    return new_state


# --------------------------------------------------------------------------- #
# 1. Static single-appearance schedule  ->  jitted lax.scan.
# --------------------------------------------------------------------------- #
def make_iteration_step(network: Network,
                        order: Optional[List[str]] = None,
                        phase: Optional[int] = None) -> Callable[[State], NetworkState]:
    """One network iteration: every actor fires once, topologically ordered.

    Build-time checks prove that under Eq. 1 capacities the schedule never
    violates blocking semantics (see ``Network.check_schedule_feasible``).
    With a trace-time ``phase`` the iteration runs cursor-specialized:
    transient channels forward their windows through a fresh per-iteration
    register dict, buffered static ports use compile-time slice offsets.
    """
    order = list(order) if order is not None else network.topological_order()
    network.check_schedule_feasible()

    def step(state: State) -> NetworkState:
        regs: Dict[int, jax.Array] = {}
        for nm in order:
            state = fire_actor(network, nm, state, phase=phase, regs=regs)
        return state

    return step


def _phase_aligned_fifos(network: Network,
                         period: int) -> List[Tuple[str, bool, bool]]:
    """(fifo, read_side_static, write_side_static) deducible at build time,
    for buffered (non-register-allocated) channels whose phase cycle the
    unroll ``period`` covers — the only ones offset-specialization touches.

    A side is statically scheduled when its port consumes/produces
    unconditionally under ``compile_static``: every port of a static actor,
    and the control port of a dynamic actor.  (Constant-enable ports of
    dynamic actors also specialize, but only trace-time concreteness can
    prove that — they advance in lockstep with the build-time-static set,
    so checking this set suffices for the phase-alignment guard.)
    """
    out = []
    for e in network.edges:
        if e.fifo in network.register_fifos:
            continue
        if period % network.fifos[e.fifo].n_write_phases:
            continue
        src = network.actors[e.src_actor]
        dst = network.actors[e.dst_actor]
        read_static = (e.dst_port == dst.control_port) or not dst.is_dynamic
        write_static = not src.is_dynamic
        out.append((e.fifo, read_static, write_static))
    return out


def _compile_static(network: Network, n_iterations: int,
                    mode: RuntimeMode = RuntimeMode.PROPOSED,
                    order: Optional[List[str]] = None,
                    donate: bool = False,
                    specialize: bool = True,
                    unroll_bound: int = 6) -> Callable[[State], NetworkState]:
    """Compile ``n_iterations`` of the network into a single XLA program.

    ``specialize=True`` applies trace-time cursor specialization:

      * transient channels (``network.register_fifos``: delay-free, enables
        provably matched) are register-allocated — windows flow
        producer->consumer as traced values and their ring buffers are
        never read or written;
      * remaining (buffered) channels get the phase cycle unrolled inside
        the scan body (period = LCM of their ``n_write_phases``, <= 6) so
        statically-scheduled ports use compile-time slice offsets instead
        of cursor-driven ``dynamic_slice``.

    The input state must be phase-aligned and transient-drained: fresh from
    ``Network.init_state``, or the result of a prior run whose iteration
    count was a multiple of the period (checked eagerly when cursors are
    concrete).  Final actor states, cursors, occupancies and live tokens
    are bit-identical to ``specialize=False``; dead slots of
    register-allocated buffers keep their initial zeros.

    ``donate=True`` donates the input state so XLA can reuse its buffers
    in place — the caller's state object is consumed by the call.  Beware
    that a state from ``Network.init_state`` may *share* arrays with the
    graph definition (e.g. a signal staged at build time is aliased, not
    copied): donating it consumes those arrays for every future
    ``init_state`` too.  ``jax.tree.map(jnp.copy, state)`` first when the
    network outlives the call (see benchmarks/bench_executors.py).
    """
    assert_mode_allows(network, mode)
    order = list(order) if order is not None else network.topological_order()
    network.check_schedule_feasible()

    period = (phase_unroll_period(
        [spec.n_write_phases for name, spec in network.fifos.items()
         if name not in network.register_fifos],
        bound=unroll_bound) if specialize else 1)
    n_super, rem = divmod(n_iterations, period)

    def step(state: NetworkState, p: Optional[int]) -> NetworkState:
        regs: Dict[int, jax.Array] = {}
        for nm in order:
            state = fire_actor(network, nm, state, phase=p, regs=regs,
                               period=period)
        return state

    def run(state: State) -> NetworkState:
        if not isinstance(state, NetworkState):
            state = network.state_from_dict(state)

        def body(s, _):
            if specialize:
                for p in range(period):
                    s = step(s, p)
            else:
                s = step(s, None)
            return s, None

        if n_super:
            state, _ = jax.lax.scan(body, state, None, length=n_super)
        for p in range(rem):
            state = step(state, p if specialize else None)
        return state

    jitted = _jit_runner(run, donate)
    if not specialize:
        return jitted

    aligned = _phase_aligned_fifos(network, period)

    def checked(state: State) -> NetworkState:
        st = state if isinstance(state, NetworkState) else network.state_from_dict(state)
        for fname, read_static, write_static in aligned:
            fs = st.fifos[network.fifo_index[fname]]
            spec = network.fifos[fname]
            for cursor, is_static in ((fs.rd, read_static), (fs.wr, write_static)):
                if is_static and _is_concrete(cursor) and int(cursor) % spec.n_write_phases:
                    raise ValueError(
                        f"compile_static(specialize=True): fifo {fname} cursor "
                        f"{int(cursor)} is not phase-aligned (cycle "
                        f"{spec.n_write_phases}); start from Network.init_state "
                        "or run a multiple of the unroll period, or pass "
                        "specialize=False")
        for fname in network.register_fifos:
            occ = st.fifos[network.fifo_index[fname]].occ
            if _is_concrete(occ) and int(occ):
                raise ValueError(
                    f"compile_static(specialize=True): transient fifo {fname} "
                    f"enters with occupancy {int(occ)}; register-allocated "
                    "channels must be drained (start from Network.init_state "
                    "or pass specialize=False)")
        return jitted(state)

    return checked


# --------------------------------------------------------------------------- #
# 2. Token-driven dynamic scheduler  ->  jitted lax.while_loop.
# --------------------------------------------------------------------------- #
def _can_fire(network: Network, name: str, state: NetworkState) -> jax.Array:
    """Blocking predicate of paper §2.2, evaluated without side effects.

    For dynamic actors the control token is *peeked* (not consumed) so the
    control function can be evaluated first — our shared-memory analogue of
    the paper's blocking control-port read.  All port->spec resolution uses
    the tables precomputed at network build time.
    """
    a = network.actors[name]
    fifos = state.fifos
    ok = jnp.bool_(True)
    if a.ready is not None:
        ok = jnp.logical_and(ok, a.ready(state.actors[network.actor_index[name]]))
    ctl = network.control_specs[name]
    if ctl is not None:
        cspec, ci = ctl
        ok = jnp.logical_and(ok, cspec.can_peek(fifos[ci]))
        # Rates given the (peeked) control token; garbage if !can_peek, but
        # then `ok` is already False and the and-tree short-circuits in value.
        rates = a.rates_for(cspec.peek(fifos[ci]))
    else:
        rates = a.rates_for(None)
    for p, spec, fi in network.in_port_specs[name]:
        have = spec.can_read(fifos[fi])
        ok = jnp.logical_and(ok, jnp.logical_or(rates[p] == 0, have))
    for p, spec, fi in network.out_port_specs[name]:
        room = spec.can_write(fifos[fi])
        ok = jnp.logical_and(ok, jnp.logical_or(rates[p] == 0, room))
    return ok


def _max_fireable(network: Network, name: str, state: NetworkState) -> jax.Array:
    """Upper bound on this actor's fireable count, from occupancies alone.

    The PRUNE-style decidable bound (arXiv:1802.06625):

      * dynamic actors consume exactly one control token per firing, so the
        control channel's occupancy is a hard bound that holds whatever the
        (data-dependent) regular-port rates turn out to be — crucially it
        does not under-count rate-0 firings, which need no data tokens;
      * static actors fire at full rate r on every port, so
        ``min(occ // r over inputs, room // r over outputs)`` is exact.

    The bound never misses a fireable actor (``_can_fire`` implies bound
    >= 1: peeking needs control occ >= 1; static reads/writes need a full
    window of tokens/room), and every firing inside the bound is still
    guarded by a per-firing ``_can_fire`` — so the multi-firing sweep
    performs exactly the firings the one-per-sweep baseline would,
    compressed into fewer sweeps.
    """
    ctl = network.control_specs[name]
    if ctl is not None:
        _, ci = ctl
        return jnp.minimum(jnp.int32(_MAX_FIRINGS_PER_VISIT),
                           state.fifos[ci].occ)
    k = jnp.int32(_MAX_FIRINGS_PER_VISIT)
    for _, spec, fi in network.in_port_specs[name]:
        k = jnp.minimum(k, state.fifos[fi].occ // spec.rate)
    for _, spec, fi in network.out_port_specs[name]:
        room = spec.writable_occupancy_bound - state.fifos[fi].occ
        k = jnp.minimum(k, room // spec.rate)
    return k


def _make_visit_body(network: Network, names: List[str],
                     multi_firing: bool) -> Callable:
    """One in-order visit of ``names``: the token-driven sweep body.

    Shared by the single-device dynamic executor (``names`` = every
    actor) and the per-device sub-sweeps of the sharded executor
    (:mod:`repro.core.shard`, ``names`` = one device's partition of the
    firing table) — both backends fire the identical per-actor logic, so
    sharded quiescence states stay bit-identical to the single-device
    run by Kahn determinism.

    Returns ``visit_all(state, counts, hlth, trc, sweeps) -> (state,
    counts, hlth, trc, fired_any)``: each named actor is attempted (up
    to its occupancy bound under ``multi_firing``), guarded per firing
    by ``_can_fire``, with the optional health/trace slots following
    the empty-pytree-when-off contract of ``_compile_dynamic``.
    """
    n_fifos = len(network.fifos)

    def fire_once(nm: str, state, counts, hlth, trc, sweeps):
        ready = _can_fire(network, nm, state)

        def do_fire(operand):
            st, c, h = operand
            if h is None:
                st = fire_actor(network, nm, st)
            else:
                st, h = fire_actor(network, nm, st, health=h)
            c = dict(c)
            c[nm] = c[nm] + 1
            return st, c, h

        state, counts, hlth = jax.lax.cond(ready, do_fire, lambda o: o,
                                           (state, counts, hlth))
        if trc is not None:
            # One event per attempt — fired or skipped — with post-attempt
            # occupancies, recorded unconditionally so tracing never
            # perturbs the schedule's control flow.
            occs = jnp.stack([state.fifos[i].occ for i in range(n_fifos)])
            trc = trc.record(network.actor_index[nm], sweeps, ready, occs)
        return state, counts, hlth, trc, ready

    def visit_all(state, counts, hlth, trc, sweeps):
        fired_any = jnp.bool_(False)
        for nm in names:
            if multi_firing:
                k = _max_fireable(network, nm, state)

                def body(_, c, nm=nm):
                    st, cnt, h, t, fired = c
                    st, cnt, h, t, ready = fire_once(nm, st, cnt, h, t,
                                                     sweeps)
                    return st, cnt, h, t, jnp.logical_or(fired, ready)

                state, counts, hlth, trc, fired = jax.lax.fori_loop(
                    0, k, body, (state, counts, hlth, trc,
                                 jnp.bool_(False)))
            else:
                state, counts, hlth, trc, fired = fire_once(
                    nm, state, counts, hlth, trc, sweeps)
            fired_any = jnp.logical_or(fired_any, fired)
        return state, counts, hlth, trc, fired_any

    return visit_all


def _compile_dynamic(network: Network, max_sweeps: int = 1_000_000,
                     mode: RuntimeMode = RuntimeMode.PROPOSED,
                     multi_firing: bool = True,
                     donate: bool = False,
                     return_sweeps: bool = False,
                     guards: bool = False,
                     trace_capacity: Optional[int] = None
                     ) -> Callable[..., Tuple]:
    """Token-driven executor: sweeps until quiescence (no actor can fire).

    Returns ``(final_state, fire_counts)`` where ``fire_counts[actor]`` is
    the number of firings — used by the benchmarks for throughput
    accounting (frames / samples per second).  With ``return_sweeps=True``
    the executor returns the full health-aware record ``(final_state,
    fire_counts, n_sweeps, stalled, health)``: ``stalled`` is True when
    the loop exited via the ``max_sweeps`` bound with work remaining
    (previously indistinguishable from quiescence), and ``health`` is the
    :class:`repro.core.health.HealthState` fault/high-water record when
    ``guards=True``, else None.

    ``guards=True`` arms the per-channel fault guards (overflow /
    underflow / cursor consistency / non-finite tokens) on every firing's
    reads and writes.  The health vectors thread the sweep carry as extra
    loop state; with ``guards=False`` that slot is the empty pytree
    ``None``, so the guards-off loop lowers to the identical HLO as before
    the health layer existed, and a guarded clean run's states / cursors /
    fire counts / sweeps are bit-identical to an unguarded one (guards
    observe channel operations, they never change them).

    ``trace_capacity=N`` arms firing-level event tracing: every firing
    *attempt* appends ``[actor, sweep, fired, occ...]`` to a ring-
    buffered :class:`repro.core.trace.TraceState` riding the sweep carry
    next to ``health`` — and following the same contract: the off slot
    is the empty pytree ``None``, so an untraced loop lowers to the
    identical HLO and a traced run's states / cursors / fire counts /
    sweeps stay bit-identical (the trace observes, it never schedules).
    With ``return_sweeps=True`` the record grows to ``(final_state,
    fire_counts, n_sweeps, stalled, health, trace)``.

    ``multi_firing=True`` fires each visited actor up to its
    occupancy-derived bound (``_max_fireable``) via ``lax.fori_loop``
    before moving to the next actor, instead of once per sweep: the same
    set of firings happens in strictly fewer sweeps, collapsing the
    O(sweeps x actors) predicate/cond overhead of the baseline.  Dataflow
    (Kahn) determinism makes the final state bit-identical either way.
    """
    assert_mode_allows(network, mode)
    names = list(network.actors)
    visit_all = _make_visit_body(network, names, multi_firing)

    def sweep(carry):
        state, counts, hlth, trc, _, sweeps = carry
        state, counts, hlth, trc, fired_any = visit_all(
            state, counts, hlth, trc, sweeps)
        return state, counts, hlth, trc, fired_any, sweeps + 1

    def cond(carry):
        _, _, _, _, fired_any, sweeps = carry
        return jnp.logical_and(fired_any, sweeps < max_sweeps)

    def run(state: State):
        if not isinstance(state, NetworkState):
            state = network.state_from_dict(state)
        counts = {nm: jnp.int32(0) for nm in names}
        hlth = init_health(len(network.fifos)) if guards else None
        trc = (init_trace(len(network.fifos), trace_capacity)
               if trace_capacity else None)
        carry = (state, counts, hlth, trc, jnp.bool_(True), jnp.int32(0))
        state, counts, hlth, trc, fired_any, sweeps = jax.lax.while_loop(
            cond, sweep, carry)
        if return_sweeps:
            # fired_any still True at exit means the loop left through the
            # sweep budget, not quiescence — the stall the health layer
            # surfaces instead of returning partial state silently.
            stalled = jnp.logical_and(fired_any, sweeps >= max_sweeps)
            return state, counts, sweeps, stalled, hlth, trc
        return state, counts

    return _jit_runner(run, donate)


# --------------------------------------------------------------------------- #
# 3. Interpreted executor (GPP-thread / DAL-multicore analogue).
# --------------------------------------------------------------------------- #
def _run_interpreted(network: Network, state: State, n_iterations: int,
                     order: Optional[List[str]] = None,
                     donate: bool = False) -> NetworkState:
    """Eagerly fire the static schedule actor-by-actor (no cross-actor fusion).

    Each actor's firing is independently jitted — the analogue of the
    paper's per-thread GPP execution where no cross-actor optimization can
    happen.  Used as the multicore baseline in the Table 3/4 benchmarks.

    ``donate=True`` donates each intermediate state to the next firing so
    XLA updates FIFO buffers in place; the caller's input state is copied
    once up front so it survives the run.
    """
    order = list(order) if order is not None else network.topological_order()
    network.check_schedule_feasible()
    if not isinstance(state, NetworkState):
        state = network.state_from_dict(state)
    if donate:
        state = jax.tree.map(jnp.copy, state)
    fns = {nm: jax.jit(functools.partial(fire_actor, network, nm),
                       donate_argnums=(0,) if donate else ())
           for nm in order}
    for _ in range(n_iterations):
        for nm in order:
            state = fns[nm](state)
    return state


def collect_sink(network: Network, state: State, actor: str) -> Any:
    """Run an actor's ``finish`` hook on its final state (paper §3.1)."""
    a = network.actors[actor]
    if not isinstance(state, NetworkState):
        state = network.state_from_dict(state)
    st = state.actors[network.actor_index[actor]]
    return a.finish(st) if a.finish is not None else st


# --------------------------------------------------------------------------- #
# Legacy entrypoints — thin deprecated shims over Network.compile / Program.
#
# Deprecation policy (EXPERIMENTS.md §API): the shims delegate to the exact
# same runners Program uses, so results stay bit-identical for at least two
# further PRs; new code should construct an ExecutionPlan instead, where
# every executor policy (mode, specialization, multi-firing, donation,
# heterogeneous placement) is a plan field.
# --------------------------------------------------------------------------- #
# Warned entrypoints, module-level so each shim warns once per process:
# per-call warnings flooded benchmark loops that call a shim-built runner
# factory repeatedly (thousands of identical lines per bench section).
_DEPRECATION_WARNED: set = set()


def reset_deprecation_warnings() -> None:
    """Re-arm the once-per-process shim warnings (testing hook)."""
    _DEPRECATION_WARNED.clear()


def _warn_deprecated(old: str, new: str) -> None:
    msg = (f"{old} is deprecated; use {new} (see ExecutionPlan and "
           "ExecutionPlan.validate in repro.core.program for the plan "
           "fields and the cross-field rules they must satisfy)")
    if os.environ.get("REPRO_STRICT_DEPRECATION") == "1":
        # CI's retirement gate: legacy entrypoints become hard errors so
        # no new call site can land while the shims still exist.
        raise DeprecationWarning(msg)
    if old in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(old)
    warnings.warn(msg, DeprecationWarning, stacklevel=3)


def compile_static(network: Network, n_iterations: int,
                   mode: RuntimeMode = RuntimeMode.PROPOSED,
                   order: Optional[List[str]] = None,
                   donate: bool = False,
                   specialize: bool = True,
                   unroll_bound: int = 6) -> Callable[[State], NetworkState]:
    """Deprecated: ``network.compile(mode="static", n_iterations=...)``."""
    _warn_deprecated("compile_static(net, n, ...)",
                     'net.compile(mode="static", n_iterations=n, ...).run')
    prog = network.compile(
        mode="static", n_iterations=n_iterations, runtime_mode=mode,
        order=tuple(order) if order is not None else None, donate=donate,
        specialize=specialize, unroll_bound=unroll_bound)
    return lambda state=None: prog.run(state).state


def compile_dynamic(network: Network, max_sweeps: int = 1_000_000,
                    mode: RuntimeMode = RuntimeMode.PROPOSED,
                    multi_firing: bool = True,
                    donate: bool = False,
                    return_sweeps: bool = False) -> Callable[..., Tuple]:
    """Deprecated: ``network.compile(mode="dynamic", ...)``."""
    _warn_deprecated("compile_dynamic(net, ...)",
                     'net.compile(mode="dynamic", ...).run')
    prog = network.compile(
        mode="dynamic", runtime_mode=mode, multi_firing=multi_firing,
        donate=donate, max_sweeps=max_sweeps)

    def run(state=None):
        r = prog.run(state)
        if return_sweeps:
            return r.state, r.fire_counts, r.sweeps
        return r.state, r.fire_counts

    return run


def run_interpreted(network: Network, state: State, n_iterations: int,
                    order: Optional[List[str]] = None,
                    donate: bool = False) -> NetworkState:
    """Deprecated: ``network.compile(mode="interpreted", ...).run(state)``."""
    _warn_deprecated("run_interpreted(net, state, n, ...)",
                     'net.compile(mode="interpreted", n_iterations=n, ...)'
                     ".run(state)")
    prog = network.compile(
        mode="interpreted", n_iterations=n_iterations,
        order=tuple(order) if order is not None else None, donate=donate)
    return prog.run(state).state
