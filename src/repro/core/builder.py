"""Declarative network construction — the paper's §3.1/§3.4 host API.

The paper's host program is a handful of uniform calls: declare actors,
declare channels, launch.  Hand-assembling parallel ``actors`` / ``fifos``
/ ``edges`` lists (the pre-builder style) scatters one logical connection
across three places and pushes every MoC rule violation to the monolithic
``Network.__init__`` validator, whose errors point at lists, not at the
line that made the mistake.  :class:`NetworkBuilder` is the declarative
replacement::

    b = NetworkBuilder()
    b.actor(source)
    b.actor(amp)
    b.actor(sink)
    b.connect("source.out", "amp.in", rate=2, token_shape=(4,))
    b.connect("ctl.out", "amp.c")            # control: inferred from port
    net = b.build()                          # -> plain repro.core.Network

One ``connect`` call replaces a ``FifoSpec`` + an ``Edge``; channel names
are auto-derived (override with ``name=``), ``is_control`` is inferred
from the destination port, and ``matched_rates`` — the transient-channel
declaration that unlocks register allocation in the specialized static
executor — is *derived* from the two endpoint actors' control functions
when the match is provable (see :func:`derive_matched_rates`).  Violations
of the MoC's structural rules (unknown actor/port, double connection,
control-rate, …) are reported at the offending ``connect`` call with the
exact fix, not at build time.

``build()`` emits today's :class:`repro.core.network.Network` unchanged —
builder-constructed and hand-assembled networks are indistinguishable
(same actor/fifo ordering rules: registration / connection order), so all
executors, verifiers and the :class:`repro.core.program.Program` runtime
apply as-is.
"""
from __future__ import annotations

import dataclasses
import difflib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.core.actor import ActorSpec
from repro.core.fifo import FifoSpec
from repro.core.network import Edge, Network


def _suggest(name: str, known: Sequence[str]) -> str:
    close = difflib.get_close_matches(name, list(known), n=2)
    hint = f"; did you mean {' or '.join(map(repr, close))}?" if close else ""
    return f"known: {sorted(known)}{hint}"


@dataclasses.dataclass(frozen=True)
class _Connection:
    """One declared channel, pre-Network: spec + endpoint binding."""

    spec: FifoSpec
    edge: Edge
    matched_override: Optional[bool]   # None = derive at build()
    initial_token: Optional[Any]


# --------------------------------------------------------------------------- #
# matched_rates derivation: prove the two ports are always enabled together.
# --------------------------------------------------------------------------- #
def _canonical_enable_str(closed) -> str:
    """Canonical string of the sub-jaxpr computing one enable output.

    Control functions compute the *whole* per-port dict, so the raw jaxpr
    of one port carries dead equations for every other port (and their
    count varies per actor).  Backward-slice from the output, rename vars
    in first-use order, and inline captured const values — two ports
    canonicalize equally iff they run the same live computation on the
    token (the basis of the matched-rates proof).
    """
    import numpy as np
    jaxpr = closed.jaxpr
    needed = {id(v) for v in jaxpr.outvars
              if not isinstance(v, jax_core.Literal)}
    kept = []
    for eqn in reversed(jaxpr.eqns):
        if any(id(v) in needed for v in eqn.outvars):
            kept.append(eqn)
            needed |= {id(v) for v in eqn.invars
                       if not isinstance(v, jax_core.Literal)}
    kept.reverse()
    consts = {id(v): c for v, c in zip(jaxpr.constvars, closed.consts)}
    names: Dict[int, str] = {}

    def nm(v) -> str:
        if isinstance(v, jax_core.Literal):
            return f"lit({v.val!r}:{getattr(v.aval, 'dtype', None)})"
        if id(v) not in names:
            if id(v) in consts:
                c = np.asarray(consts[id(v)])
                names[id(v)] = f"const({c.dtype}:{c.tolist()!r})"
            else:
                names[id(v)] = f"v{len(names)}"
        return names[id(v)]

    for v in jaxpr.invars:
        nm(v)
    lines = []
    for eqn in kept:
        params = sorted((k, repr(p)) for k, p in eqn.params.items())
        lines.append(f"{[nm(v) for v in eqn.outvars]} = "
                     f"{eqn.primitive.name}{params} "
                     f"{[nm(v) for v in eqn.invars]}")
    lines.append("out " + repr([nm(v) for v in jaxpr.outvars]))
    return "\n".join(lines)


def _enable_expr(actor: ActorSpec, port: str,
                 ctl_token_spec: Optional[FifoSpec],
                 ctl_feed: Optional[Tuple[str, str]]):
    """Classify a port's enable as ``("const", v)`` or ``("expr", s, feed)``.

    * static actor -> every regular port is unconditionally enabled:
      ``("const", 1)``;
    * dynamic actor -> trace ``control(token)[port]`` to a jaxpr.  If the
      output provably does not depend on the token (no dataflow path from
      the input var), evaluate it once: ``("const", v)``.  Otherwise the
      canonical jaxpr string plus the identity of the channel feeding the
      control port — ``("expr", jaxpr_str, (feeder_actor, feeder_port))`` —
      is the symbolic enable.

    Returns ``None`` when the enable cannot be classified (control channel
    not yet known, tracing failure) — callers treat that as unprovable.
    """
    if not actor.is_dynamic:
        return ("const", 1)
    if ctl_token_spec is None or ctl_feed is None:
        return None
    try:
        tok0 = jnp.zeros((1,) + tuple(ctl_token_spec.token_shape),
                         ctl_token_spec.dtype)[0]

        def enable(tok):
            return jnp.asarray(actor.control(tok)[port], jnp.int32)

        closed = jax.make_jaxpr(enable)(tok0)
    except Exception:
        return None
    jaxpr = closed.jaxpr
    # Dataflow reachability: does any outvar depend on the token invar?
    reached = {id(v) for v in jaxpr.invars}
    for eqn in jaxpr.eqns:
        if any(not isinstance(v, jax_core.Literal) and id(v) in reached
               for v in eqn.invars):
            reached |= {id(v) for v in eqn.outvars}
    depends = any(not isinstance(v, jax_core.Literal) and id(v) in reached
                  for v in jaxpr.outvars)
    if not depends:
        try:
            return ("const", int(enable(tok0)))
        except Exception:
            return None
    return ("expr", _canonical_enable_str(closed), ctl_feed)


def _ports_provably_equal(actor: ActorSpec, p1: str, p2: str,
                          in_specs: Dict[str, FifoSpec]) -> bool:
    """True when ``actor`` provably emits the same value on ``p1``/``p2``.

    Proof by tracing one firing of ``fire`` with example-shaped inputs and
    checking that the two output ports flatten to the *same jaxpr
    variable* — the single-assignment form can only reuse a var for both
    outputs when they are literally the same traced value (e.g. DPD's
    configuration actor broadcasting one token to all control ports).
    Conservative: any trace failure, and any pair that merely computes
    equal-but-distinct values, is "not provable".
    """
    if p1 == p2:
        return True
    try:
        state0 = actor.init_state()
        ones = {p: jnp.int32(1)
                for p in (*actor.in_ports, *actor.out_ports)}
        ins = {}
        for p in actor.in_ports:
            spec = in_specs.get(p)
            if spec is None:
                return False
            ins[p] = jnp.zeros((spec.rate,) + tuple(spec.token_shape),
                               spec.dtype)

        def f(st, windows):
            _, outs = actor.fire(st, windows, ones)
            return outs[p1], outs[p2]

        closed = jax.make_jaxpr(f)(state0, ins)
        o1, o2 = closed.jaxpr.outvars
        return (not isinstance(o1, jax_core.Literal)
                and not isinstance(o2, jax_core.Literal)
                and o1 is o2)
    except Exception:
        return False


def derive_matched_rates(src: ActorSpec, src_port: str,
                         dst: ActorSpec, dst_port: str,
                         src_env, dst_env,
                         feeder_equal) -> bool:
    """Decide whether a delay-free data channel's ports are provably
    enabled together (the ``FifoSpec.matched_rates`` invariant).

    ``src_env`` / ``dst_env`` are :func:`_enable_expr` classifications for
    the producing and consuming port; ``feeder_equal(actor, pa, pb)``
    proves two output ports of a shared control-feeder actor carry the
    same value.  The provable cases:

      * both enables constant and equal (covers a dynamic port whose
        control function pins it unconditionally on, e.g. DPD fork's
        ``in`` port against a static source);
      * both enables are the *same expression* of control tokens that
        *provably carry the same value* — identical jaxprs, control
        channels fed by the same actor on ports shown equal by tracing
        that actor's ``fire`` (DPD's configuration fan-out).

    Channels between two static actors are deliberately **not** marked:
    both enables are constant, but registerizing static-static bulk
    channels fuses producer stencils into every consumer tap (the XLA CPU
    mega-fusion pathology, EXPERIMENTS.md §Executor perf) — the buffered
    static-offset path is the measured optimum there, and
    ``Network.register_fifos`` already handles the profitable
    static-producer *control* channels separately.
    """
    if not (src.is_dynamic or dst.is_dynamic):
        return False
    if src_env is None or dst_env is None:
        return False
    if src_env[0] == "const" and dst_env[0] == "const":
        return src_env[1] == dst_env[1]
    if src_env[0] == "expr" and dst_env[0] == "expr":
        _, s_expr, (s_feed_actor, s_feed_port) = src_env
        _, d_expr, (d_feed_actor, d_feed_port) = dst_env
        if s_expr != d_expr or s_feed_actor != d_feed_actor:
            return False
        return feeder_equal(s_feed_actor, s_feed_port, d_feed_port)
    return False  # const vs token-dependent: enables can diverge


# --------------------------------------------------------------------------- #
# PRUNE-style buffer-bound analysis (arXiv:1802.06625): decide per channel,
# from declared or derived enable-fraction bounds, whether the Eq. 1
# capacity provably suffices — overflow/starvation becomes a *build* error
# for decidable graphs and stays a runtime guard flag only for the rest.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ChannelBounds:
    """One channel's enable-fraction bounds and the verdict they prove.

    ``src_bounds`` / ``dst_bounds`` are ``(lo, hi)`` fractions of firings
    in which the producing / consuming port is enabled (1.0 = every
    firing, the static case).  Verdicts:

      * ``"balanced"``   — production provably equals consumption
        (matched-rates derivation, or equal constant bounds): the Eq. 1
        capacity is exact, the channel cannot overflow or starve.
      * ``"unbounded"``  — the producer's floor exceeds the consumer's
        ceiling: backlog grows every iteration, and under blocking
        semantics the producer eventually blocks for good (the bounded-
        buffer image of PRUNE's unbounded-growth verdict).
      * ``"starved"``    — the consumer's floor exceeds the producer's
        ceiling: the consumer is guaranteed to stall waiting on tokens
        that provably never arrive often enough.
      * ``"undecided"``  — token-dependent enables with no declared
        bounds: not provable either way at build time; the runtime
        guards (``ExecutionPlan(guards=True)``) own this channel.
    """

    fifo: str
    src: str
    dst: str
    src_bounds: Tuple[float, float]
    dst_bounds: Tuple[float, float]
    verdict: str

    def describe(self) -> str:
        return (f"channel {self.fifo!r} ({self.src} -> {self.dst}): "
                f"{self.verdict} [producer enabled "
                f"{self.src_bounds[0]:g}..{self.src_bounds[1]:g} of "
                f"firings, consumer {self.dst_bounds[0]:g}.."
                f"{self.dst_bounds[1]:g}]")


@dataclasses.dataclass(frozen=True)
class BoundsReport:
    """Per-channel verdicts of :meth:`NetworkBuilder.check_bounds`."""

    channels: Tuple[ChannelBounds, ...]

    def violations(self) -> Tuple[ChannelBounds, ...]:
        return tuple(c for c in self.channels
                     if c.verdict in ("unbounded", "starved"))

    def undecided(self) -> Tuple[ChannelBounds, ...]:
        return tuple(c for c in self.channels if c.verdict == "undecided")

    def describe(self) -> str:
        return "\n".join(c.describe() for c in self.channels)


# --------------------------------------------------------------------------- #
# The builder.
# --------------------------------------------------------------------------- #
class NetworkBuilder:
    """Incremental, validating construction surface for actor networks."""

    def __init__(self) -> None:
        self._actors: Dict[str, ActorSpec] = {}
        self._connections: List[_Connection] = []
        self._fifo_names: set = set()
        self._used_out: Dict[Tuple[str, str], str] = {}
        self._used_in: Dict[Tuple[str, str], str] = {}
        self._rate_bounds: Dict[Tuple[str, str], Tuple[float, float]] = {}
        #: Last :meth:`check_bounds` result (also set by
        #: ``build(check_bounds=True)``).
        self.bounds_report: Optional[BoundsReport] = None

    # -- actors --------------------------------------------------------- #
    def actor(self, spec: ActorSpec) -> ActorSpec:
        """Register an actor.  Registration order is the network's actor
        order (and thus the state layout).  Returns ``spec`` for chaining."""
        if not isinstance(spec, ActorSpec):
            raise TypeError(
                f"NetworkBuilder.actor() takes an ActorSpec, got "
                f"{type(spec).__name__}; build one with static_actor(...) "
                "or dynamic_actor(...)")
        if spec.name in self._actors:
            raise ValueError(
                f"actor {spec.name!r} already registered; actor names must "
                "be unique within a network")
        self._actors[spec.name] = spec
        return spec

    def actors(self, *specs: ActorSpec) -> "NetworkBuilder":
        for s in specs:
            self.actor(s)
        return self

    # -- endpoint parsing ------------------------------------------------ #
    def _parse(self, endpoint: str, kind: str) -> Tuple[str, str]:
        if not isinstance(endpoint, str) or endpoint.count(".") != 1:
            raise ValueError(
                f"{kind} endpoint {endpoint!r} must be an 'actor.port' "
                "string (exactly one dot)")
        actor, port = endpoint.split(".")
        if actor not in self._actors:
            raise ValueError(
                f"{kind} endpoint {endpoint!r}: unknown actor {actor!r} — "
                f"register it with b.actor(...) first; "
                f"{_suggest(actor, self._actors)}")
        return actor, port

    # -- channels -------------------------------------------------------- #
    def connect(self, src: str, dst: str, *,
                rate: int = 1,
                token_shape: Optional[Tuple[int, ...]] = None,
                dtype: Any = None,
                capacity: Optional[int] = None,
                delay: int = 0,
                control: Optional[bool] = None,
                name: Optional[str] = None,
                matched_rates: Optional[bool] = None,
                initial_token: Optional[Any] = None,
                domain: Optional[Tuple[float, float]] = None,
                row_id_col: Optional[int] = None) -> str:
        """Declare one channel ``src("actor.port") -> dst("actor.port")``.

        * ``name`` defaults to ``"src.port->dst.port"``;
        * ``domain=(lo, hi)`` declares the valid value range of every
          token element: guarded runs flag out-of-range enabled windows
          with the ``DOMAIN`` fault bit (:mod:`repro.core.health`) and
          ``Program.stream`` validates staged feeds against it host-side;
        * ``row_id_col`` names the record-id column of record-row tokens
          (>= 2-D token shapes) so fault and feed-validation reports can
          name the offending record, not just the channel;
        * ``control`` (whether this is a rate-1 control channel) is
          inferred from the destination port being the consuming actor's
          control port — pass it only to assert your expectation;
        * control channels default to ``token_shape=(1,)``/``int32`` (the
          scalar-token convention of every paper graph);
        * ``capacity`` is **derived** from the Eq. 1 law (``2r`` / ``3r+1``)
          — pass it to assert the expected value, mismatches raise;
        * ``matched_rates=None`` defers to :func:`derive_matched_rates` at
          ``build()`` time; ``True``/``False`` overrides the derivation;
        * ``delay=1`` with ``rate > 1`` additionally glues the two
          endpoint actors to one core under grid-partitioned megakernel
          plans (``ExecutionPlan(cores=k)``): the Fig. 2 copy-back
          cannot cross a partition boundary unless the initial tokens
          cover a whole read window
          (``Network.validate_partition`` / ``delay_partition_constraints``).

        Returns the channel name.
        """
        src_actor, src_port = self._parse(src, "source")
        dst_actor, dst_port = self._parse(dst, "destination")
        sa, da = self._actors[src_actor], self._actors[dst_actor]

        if src_port not in sa.out_ports:
            raise ValueError(
                f"connect({src!r}, {dst!r}): actor {src_actor!r} has no "
                f"output port {src_port!r}; {_suggest(src_port, sa.out_ports)}")
        if dst_port not in da.all_in_ports():
            raise ValueError(
                f"connect({src!r}, {dst!r}): actor {dst_actor!r} has no "
                f"input port {dst_port!r}; "
                f"{_suggest(dst_port, da.all_in_ports())}")

        if (src_actor, src_port) in self._used_out:
            raise ValueError(
                f"connect({src!r}, {dst!r}): output port {src!r} is already "
                f"connected by channel "
                f"{self._used_out[(src_actor, src_port)]!r}; the MoC allows "
                "exactly one reader per channel — add a fork actor to fan "
                "out")
        if (dst_actor, dst_port) in self._used_in:
            raise ValueError(
                f"connect({src!r}, {dst!r}): input port {dst!r} is already "
                f"connected by channel "
                f"{self._used_in[(dst_actor, dst_port)]!r}; the MoC allows "
                "exactly one writer per channel — add a merge actor to fan "
                "in")

        is_control = dst_port == da.control_port
        if control is not None and bool(control) != is_control:
            if control:
                raise ValueError(
                    f"connect({src!r}, {dst!r}): control=True but "
                    f"{dst_port!r} is not the control port of "
                    f"{dst_actor!r} (control_port={da.control_port!r})")
            raise ValueError(
                f"connect({src!r}, {dst!r}): control=False but "
                f"{dst_port!r} IS the control port of {dst_actor!r}; "
                "control channels are inferred from the destination port")
        if is_control:
            if rate != 1:
                raise ValueError(
                    f"connect({src!r}, {dst!r}): control channels must have "
                    f"token rate 1 (paper §2.2), got rate={rate}")
            if delay:
                raise ValueError(
                    f"connect({src!r}, {dst!r}): control channels cannot "
                    "carry delay tokens")
            token_shape = (1,) if token_shape is None else token_shape
            dtype = jnp.int32 if dtype is None else dtype
        else:
            if token_shape is None:
                raise ValueError(
                    f"connect({src!r}, {dst!r}): data channels need an "
                    "explicit token_shape=")
            dtype = jnp.float32 if dtype is None else dtype

        if name is None:
            name = f"{src}->{dst}"
        if name in self._fifo_names:
            raise ValueError(
                f"connect({src!r}, {dst!r}): channel name {name!r} already "
                "used; pass a unique name=")

        spec = FifoSpec(name, rate, tuple(token_shape), dtype, delay=delay,
                        is_control=is_control,
                        matched_rates=bool(matched_rates),
                        domain=domain, row_id_col=row_id_col)
        if capacity is not None and capacity != spec.capacity_tokens:
            raise ValueError(
                f"connect({src!r}, {dst!r}): capacity={capacity} contradicts "
                f"the Eq. 1 law — rate {rate} with delay {delay} allocates "
                f"{spec.capacity_tokens} tokens "
                f"({'3r+1' if delay else '2r'}); capacities are derived, not "
                "chosen (drop capacity= or fix rate/delay)")
        if initial_token is not None and not delay:
            raise ValueError(
                f"connect({src!r}, {dst!r}): initial_token needs delay=1 "
                "(initial tokens live on delay channels, paper §2.2)")

        edge = Edge(name, src_actor, src_port, dst_actor, dst_port)
        self._connections.append(_Connection(spec, edge, matched_rates,
                                             initial_token))
        self._fifo_names.add(name)
        self._used_out[(src_actor, src_port)] = name
        self._used_in[(dst_actor, dst_port)] = name
        return name

    # -- dangling-port accounting ---------------------------------------- #
    def dangling_ports(self) -> List[str]:
        """Every declared-but-unconnected port, as ``actor.port`` strings."""
        out = []
        for a in self._actors.values():
            for p in a.all_in_ports():
                if (a.name, p) not in self._used_in:
                    out.append(f"{a.name}.{p}")
            for p in a.out_ports:
                if (a.name, p) not in self._used_out:
                    out.append(f"{a.name}.{p}")
        return out

    # -- matched-rates derivation ---------------------------------------- #
    def _control_feed(self, actor: ActorSpec):
        """(feeder (actor, port), control FifoSpec) for a dynamic actor."""
        for c in self._connections:
            e = c.edge
            if e.dst_actor == actor.name and e.dst_port == actor.control_port:
                return (e.src_actor, e.src_port), c.spec
        return None, None

    def _derive_matched(self) -> Dict[str, bool]:
        in_specs: Dict[str, Dict[str, FifoSpec]] = {n: {} for n in self._actors}
        for c in self._connections:
            in_specs[c.edge.dst_actor][c.edge.dst_port] = c.spec

        env_cache: Dict[Tuple[str, str], Any] = {}

        def env(actor_name: str, port: str):
            key = (actor_name, port)
            if key not in env_cache:
                a = self._actors[actor_name]
                feed, cspec = self._control_feed(a)
                env_cache[key] = _enable_expr(a, port, cspec, feed)
            return env_cache[key]

        feeder_cache: Dict[Tuple[str, str, str], bool] = {}

        def feeder_equal(actor_name: str, pa: str, pb: str) -> bool:
            key = (actor_name, *sorted((pa, pb)))
            if key not in feeder_cache:
                feeder_cache[key] = _ports_provably_equal(
                    self._actors[actor_name], pa, pb,
                    in_specs[actor_name])
            return feeder_cache[key]

        out: Dict[str, bool] = {}
        for c in self._connections:
            if c.matched_override is not None:
                out[c.spec.name] = c.matched_override
                continue
            if c.spec.is_control or c.spec.delay:
                out[c.spec.name] = False
                continue
            e = c.edge
            out[c.spec.name] = derive_matched_rates(
                self._actors[e.src_actor], e.src_port,
                self._actors[e.dst_actor], e.dst_port,
                env(e.src_actor, e.src_port), env(e.dst_actor, e.dst_port),
                feeder_equal)
        return out

    # -- PRUNE-style bound proofs ----------------------------------------- #
    def rate_bounds(self, endpoint: str, lo: float,
                    hi: float) -> "NetworkBuilder":
        """Declare worst/best-case enable bounds for a dynamic port.

        ``lo`` / ``hi`` bound the *fraction of firings* in which
        ``endpoint`` ("actor.port") is enabled by its control token —
        the declared-rate input PRUNE's analysis needs where the enable
        is data-dependent and not derivable from the control jaxpr.
        ``rate_bounds("fork.active", 0.0, 1.0)`` is the (vacuous)
        default; ``(1.0, 1.0)`` pins the port always-on; ``(0.5, 0.5)``
        declares exact half-rate decimation.  Returns ``self``.
        """
        actor, port = self._parse(endpoint, "rate_bounds")
        a = self._actors[actor]
        if port not in a.all_in_ports() and port not in a.out_ports:
            raise ValueError(
                f"rate_bounds({endpoint!r}): actor {actor!r} has no port "
                f"{port!r}; "
                f"{_suggest(port, (*a.all_in_ports(), *a.out_ports))}")
        if not (0.0 <= lo <= hi <= 1.0):
            raise ValueError(
                f"rate_bounds({endpoint!r}): bounds must satisfy "
                f"0 <= lo <= hi <= 1 (fractions of firings), got "
                f"lo={lo}, hi={hi}")
        self._rate_bounds[(actor, port)] = (float(lo), float(hi))
        return self

    def _port_bounds(self, actor_name: str, port: str,
                     env) -> Tuple[float, float]:
        """Enable-fraction bounds of one port, most-precise source first:
        declared ``rate_bounds`` > control port (consumes every firing) >
        provably-constant enable > static actor > dynamic unknown."""
        a = self._actors[actor_name]
        declared = self._rate_bounds.get((actor_name, port))
        if declared is not None:
            return declared
        if port == a.control_port:
            return (1.0, 1.0)
        if not a.is_dynamic:
            return (1.0, 1.0)
        e = env(actor_name, port)
        if e is not None and e[0] == "const":
            v = 1.0 if e[1] > 0 else 0.0
            return (v, v)
        return (0.0, 1.0)

    def check_bounds(self) -> BoundsReport:
        """Run the PRUNE-style per-channel bound analysis (no build).

        Combines the matched-rates derivation (provably co-enabled ports
        -> ``"balanced"``), constant-enable proofs from the control
        jaxprs, and any declared :meth:`rate_bounds` into a per-channel
        verdict; see :class:`ChannelBounds` for the taxonomy.  The report
        is also stored as ``self.bounds_report``.
        """
        matched = self._derive_matched()
        env_cache: Dict[Tuple[str, str], Any] = {}

        def env(actor_name: str, port: str):
            key = (actor_name, port)
            if key not in env_cache:
                a = self._actors[actor_name]
                feed, cspec = self._control_feed(a)
                env_cache[key] = _enable_expr(a, port, cspec, feed)
            return env_cache[key]

        channels = []
        for c in self._connections:
            e = c.edge
            src_b = self._port_bounds(e.src_actor, e.src_port, env)
            dst_b = self._port_bounds(e.dst_actor, e.dst_port, env)
            if matched.get(c.spec.name):
                verdict = "balanced"
            elif src_b[0] > dst_b[1]:
                verdict = "unbounded"
            elif dst_b[0] > src_b[1]:
                verdict = "starved"
            elif src_b == dst_b and src_b[0] == src_b[1]:
                verdict = "balanced"
            else:
                verdict = "undecided"
            channels.append(ChannelBounds(
                fifo=c.spec.name,
                src=f"{e.src_actor}.{e.src_port}",
                dst=f"{e.dst_actor}.{e.dst_port}",
                src_bounds=src_b, dst_bounds=dst_b, verdict=verdict))
        report = BoundsReport(channels=tuple(channels))
        self.bounds_report = report
        return report

    # -- emission --------------------------------------------------------- #
    def build(self, derive_matched: bool = True,
              check_bounds: bool = False) -> Network:
        """Validate and emit the :class:`Network`.

        Dangling ports are reported here with the exact ``connect`` calls
        still missing; everything else was validated incrementally.  With
        ``derive_matched=True`` (default) channels left with
        ``matched_rates=None`` get the provable-transiency derivation.
        ``check_bounds=True`` additionally runs the PRUNE-style buffer
        bound analysis (:meth:`check_bounds`) and rejects graphs with a
        provably unbounded or starved channel — overflow becomes a build
        error where decidable, a runtime guard flag only for the rest.
        """
        dangling = self.dangling_ports()
        if dangling:
            raise ValueError(
                "network has dangling ports (every port connects to exactly "
                f"one channel, paper §3.2): {sorted(dangling)} — add a "
                "b.connect(...) for each")
        if check_bounds:
            bad = self.check_bounds().violations()
            if bad:
                raise ValueError(
                    "NetworkBuilder.build(check_bounds=True): the declared/"
                    "derived rate bounds prove these channels violate their "
                    "Eq. 1 buffers:\n  "
                    + "\n  ".join(c.describe() for c in bad)
                    + "\n(fix the graph, adjust rate_bounds(...), or build "
                    "with check_bounds=False and rely on runtime guards)")
        matched = (self._derive_matched() if derive_matched
                   else {c.spec.name: bool(c.matched_override)
                         for c in self._connections})
        fifos = [dataclasses.replace(c.spec, matched_rates=matched[c.spec.name])
                 if matched[c.spec.name] != c.spec.matched_rates else c.spec
                 for c in self._connections]
        initial = {c.spec.name: c.initial_token for c in self._connections
                   if c.initial_token is not None}
        return Network(list(self._actors.values()), fifos,
                       [c.edge for c in self._connections],
                       initial_tokens=initial or None)
