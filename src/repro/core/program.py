"""Program: the single compile/run/stream entrypoint — paper §3.4.

The paper's runtime exposes one operation: *launch the network*.  Our
reproduction had grown three parallel entrypoints (``compile_static``,
``compile_dynamic``, ``run_interpreted``) plus a separate
``heterogeneous_split`` + ``stage_feed`` code path for host/accelerator
placement.  :class:`Program` folds them behind one object::

    plan = ExecutionPlan(mode="static", n_iterations=8)
    prog = net.compile(plan)           # Network.compile -> Program
    result = prog.run()                # RunResult(state, counts, sweeps)

Every execution policy is a field of :class:`ExecutionPlan` — the mode
(static scan / token-driven dynamic / interpreted / persistent-Pallas
megakernel, see :mod:`repro.core.megakernel`), trace-time
specialization, multi-firing sweeps, buffer donation, and *heterogeneous
placement*: ``accelerated=[...]`` splits the network at construction so
boundary channels become feed/fetch actors, and :meth:`Program.stream`
drives chunked host-feed/fetch through the compiled accelerator step (the
paper's host<->device transfer loop).  Future policies (sharding across a
mesh axis, async dispatch, alternate backends — ROADMAP) land as new plan
fields, not new entrypoints.

The legacy trio lives on in ``repro.core.executor`` as thin deprecated
shims delegating here; results are bit-identical (pinned by
``tests/test_program_api.py``).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import warnings
from typing import Any, Dict, List, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointIntegrityError,
                              load_stream_checkpoint, save_stream_checkpoint)
from repro.core.executor import (RuntimeMode, _compile_dynamic,
                                 _compile_static, _run_interpreted,
                                 collect_sink)
from repro.core.fifo import FifoState
from repro.core.health import (Diagnostics, NetworkFaultError, decode_health)
from repro.core.mapping import heterogeneous_split
from repro.core.network import (Network, NetworkState, iteration_token_flops)
from repro.core.schedule import phase_unroll_period
from repro.core.trace import (SPAN_COLLECT, SPAN_DECODE, SPAN_LAUNCH,
                              SPAN_PREPARE, SPAN_STAGE, SPAN_STREAM,
                              SPAN_WAIT, TRACE_CAPACITY_DEFAULT, Trace,
                              decode_trace, merge_traces)


class Mode(str, enum.Enum):
    """Execution backends of :class:`ExecutionPlan`.

    A ``str`` enum so plans written with bare strings (``mode="static"``)
    and with the enum (``mode=Mode.MEGAKERNEL``) are interchangeable.
    """

    STATIC = "static"
    DYNAMIC = "dynamic"
    INTERPRETED = "interpreted"
    # Device-resident scheduling: the whole network as ONE persistent
    # Pallas kernel — Eq. 1 rings in scratch memory, the token-driven
    # sweep loop inside the kernel (repro.core.megakernel).
    MEGAKERNEL = "megakernel"


#: Convenience alias so call sites can write ``ExecutionPlan(mode=MEGAKERNEL)``.
MEGAKERNEL = Mode.MEGAKERNEL

_MODES = tuple(m.value for m in Mode)

# donate="auto" default threshold: donation is only profitable when the
# state the call consumes is dominated by register-allocatable traffic;
# once the *buffered* (ring-resident) channel bytes grow past this, the
# in-place aliasing constraint costs more than the elided copies
# (measured on MD: 707 -> 415 tok/s donated, EXPERIMENTS.md §Executor
# perf — negative result; DPD, whose bulk channels registerize, gains
# 1.2x).  1 MiB was measured on this container's CPU backend; real-TPU
# HBM economics differ, so ``ExecutionPlan(donate_threshold_bytes=...)``
# overrides it per plan (the resolved value is reported by
# ``Program.stats().resolved_donate_threshold``).
_DONATE_AUTO_BUFFERED_BYTES_MAX = 1 << 20

#: Partition-cut objectives of the megakernel grid backend (mirrors
#: ``repro.core.megakernel.lower.CUT_OBJECTIVES``, duplicated here so a
#: plan can validate without importing the Pallas-backed package).
_CUT_OBJECTIVES = ("crossing", "flops", "profile")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Declarative execution policy — every executor knob in one record.

    Fields:
      mode:          ``"static"`` (whole network -> one jitted scan),
                     ``"dynamic"`` (token-driven ``while_loop`` scheduler,
                     runs to quiescence), ``"interpreted"`` (eager
                     per-actor firing, the GPP-thread analogue), or
                     ``"megakernel"`` / :data:`Mode.MEGAKERNEL` (one
                     persistent Pallas kernel: Eq. 1 rings in scratch,
                     the token-driven sweep loop device-resident; runs to
                     quiescence like dynamic mode and is bit-identical to
                     it).
      n_iterations:  iteration count for static/interpreted schedules (and
                     the chunk length of :meth:`Program.stream`); dynamic
                     and megakernel modes run to quiescence and ignore it
                     unless ``accelerated`` needs it for feed slab sizing.
      specialize:    static mode: trace-time cursor specialization +
                     transient-channel register allocation.  Megakernel
                     mode: in-kernel transient forwarding — core-private
                     ``register_fifos`` channels lower to loop-carried
                     token windows instead of scratch rings (dead-slot
                     carve-out: their stale ring bytes leave the
                     bit-identity contract, and they must enter drained;
                     ``specialize=False`` keeps every ring in scratch).
      multi_firing:  dynamic/megakernel modes: fire each actor up to its
                     occupancy bound per sweep.
      donate:        donate the input state so XLA reuses its buffers.
                     Default ``"auto"``: donation is applied only to
                     ``run()`` calls where the program owns the state
                     (``state=None`` — a private copy is donated, so
                     caller-held arrays are never invalidated), and only
                     when the buffered (non-register-allocated) channel
                     bytes are small enough that copy elision wins — the
                     measured heuristic behind the MD donate regression
                     (EXPERIMENTS.md §Executor perf).  ``donate=True``
                     keeps the legacy semantics: every call donates,
                     including states the caller passed in (which are
                     consumed).  Megakernel mode resolves donation to
                     False regardless — buffers are staged through
                     kernel scratch, there is nothing to donate.
      donate_threshold_bytes:
                     buffered-bytes ceiling of the ``donate="auto"``
                     heuristic; ``None`` uses the 1 MiB default measured
                     on this container's CPU backend (re-measure on real
                     HBM — ROADMAP).  The resolved value is reported as
                     ``Program.stats().resolved_donate_threshold``.
      runtime_mode:  ``RuntimeMode.PROPOSED`` (this paper) or
                     ``STATIC_DAL`` (reference framework: SDF-only
                     accelerator, dynamic actors rejected).
      order:         optional static firing order (defaults topological).
      max_sweeps:    dynamic/megakernel sweep bound.
      unroll_bound:  static mode phase-unroll period cap.
      cores:         megakernel mode: number of grid partitions for the
                     multi-core sweep (paper §3.3 actor-to-core
                     mapping).  Each core runs its own occupancy-bounded
                     firing loop over its slice of the firing table;
                     partition-crossing channels are guarded by shared
                     cursor semaphores and quiescence is global.  Final
                     states / ring bytes / cursors / fire counts are
                     bit-identical for every core count.
      assign:        optional explicit actor -> core map (must cover
                     every actor; validated by
                     ``Network.validate_partition``).  Default is a
                     contiguous cut of the visit order with
                     delay-channel endpoints glued, per
                     ``cut_objective``.
      cut_objective: megakernel mode: the default partition cut's
                     criterion.  ``"crossing"`` (default) minimizes
                     partition-crossing ring bytes (the shared-scratch /
                     semaphore surface) among contiguous cuts whose
                     ``cost_flops`` bottleneck stays within the balance
                     slack; ``"flops"`` is the legacy pure load-balance
                     cut; ``"profile"`` runs the crossing cut over
                     *measured* weights from a traced run (requires
                     ``profile=``).  Ignored under an explicit
                     ``assign``.
      accelerated:   optional actor subset mapped to the accelerator: the
                     network is split (``heterogeneous_split``) and the
                     plan executes the accelerator subnetwork, with
                     boundary channels exposed as feed/fetch actors and
                     :meth:`Program.stream` as the host transfer loop.
      guards:        dynamic/megakernel modes: arm the runtime health
                     layer's per-channel fault guards (overflow /
                     underflow / cursor consistency / non-finite tokens —
                     :mod:`repro.core.health`).  Faulting runs raise
                     :class:`repro.core.health.NetworkFaultError` naming
                     the offending channel and actors, and every
                     ``RunResult.diagnostics`` carries the decoded fault
                     and high-water record.  Off by default: guards-off
                     kernels are bit-identical to the pre-health runtime,
                     and clean guarded runs stay bit-identical too (the
                     guards observe channel ops, they never change them).
      trace:         dynamic/megakernel modes: record one firing-level
                     event per attempt (actor, sweep, fired-or-skipped,
                     per-channel occupancy) into a fixed-capacity ring —
                     loop-carried on the host dynamic executor, a
                     device-side output ref inside the megakernel's sweep
                     loop.  Decoded onto ``RunResult.trace`` as a
                     :class:`repro.core.trace.Trace` (Perfetto export,
                     derived :class:`repro.core.trace.Profile`).  Same
                     off-path contract as ``guards``: ``trace=False``
                     lowers to bit-identical HLO and traced runs never
                     perturb states / cursors / fire counts / sweeps.
      trace_capacity:
                     event capacity of the trace ring (requires
                     ``trace=True``); ``None`` uses
                     :data:`repro.core.trace.TRACE_CAPACITY_DEFAULT`.
                     Overflowing runs keep the newest events and report
                     the drop count on ``Trace.dropped``.
      profile:       megakernel mode: the measured weights the
                     ``cut_objective="profile"`` partition cut uses — a
                     :class:`repro.core.trace.Profile`, its
                     ``as_cut_weights()`` dict (``{"actors": {...},
                     "channels": {...}}``), or the frozen tuple form a
                     previous plan normalized it to.  Required iff
                     ``cut_objective="profile"``.
      devices:       dynamic mode: shard the network across ``devices``
                     devices of a 1-D mesh (:mod:`repro.core.shard`) —
                     the JAX-native analogue of the paper's GPP+GPU
                     command queues.  The firing table is partitioned by
                     the same crossing-bytes cut as the megakernel grid
                     (``cores`` = devices); each device sweeps only its
                     own actors, partition-crossing channels exchange
                     ring tokens + cursor rows via collective permutes
                     at sweep barriers (instead of same-address-space
                     cursor polling), and quiescence is an all-reduce of
                     per-device progress flags.  Final states / ring
                     bytes / cursors / fire counts are bit-identical to
                     the single-device dynamic executor for every device
                     count (Kahn determinism); sweep counts are not part
                     of that contract (barrier rounds replace sweeps).
                     ``devices=1`` is exactly the plain dynamic path.
      device_assign: optional explicit actor -> device map for
                     ``devices > 1`` (must cover every actor; validated
                     by ``Network.validate_partition`` with the same
                     delay-channel crossing rule as the grid).  Default
                     is the ``cut_objective`` contiguous cut.
    """

    mode: Union[str, Mode] = "static"
    n_iterations: Optional[int] = None
    specialize: bool = True
    multi_firing: bool = True
    donate: Union[bool, str] = "auto"
    donate_threshold_bytes: Optional[int] = None
    runtime_mode: RuntimeMode = RuntimeMode.PROPOSED
    order: Optional[Tuple[str, ...]] = None
    max_sweeps: int = 1_000_000
    unroll_bound: int = 6
    cores: int = 1
    assign: Optional[Mapping[str, int]] = None
    cut_objective: str = "crossing"
    accelerated: Optional[Tuple[str, ...]] = None
    guards: bool = False
    trace: bool = False
    trace_capacity: Optional[int] = None
    profile: Optional[Any] = None
    devices: int = 1
    device_assign: Optional[Mapping[str, int]] = None

    def __post_init__(self) -> None:
        """Field-local normalization and value checks only.

        Everything that relates two plan fields (or a plan field to the
        network) lives in :meth:`validate`, which ``Network.compile`` /
        :class:`Program` call before building anything — so a plan
        record can always be *constructed* field by field (e.g. by an
        autotuner enumerating the space) and is judged as a whole at
        compile time.
        """
        if isinstance(self.mode, Mode):
            object.__setattr__(self, "mode", self.mode.value)
        if self.mode not in _MODES:
            raise ValueError(
                f"ExecutionPlan.mode must be one of {_MODES}, got "
                f"{self.mode!r}")
        if not isinstance(self.cores, int) or self.cores < 1:
            raise ValueError(
                f"ExecutionPlan.cores must be an int >= 1, got "
                f"{self.cores!r}")
        if not isinstance(self.devices, int) \
                or isinstance(self.devices, bool) or self.devices < 1:
            raise ValueError(
                f"ExecutionPlan.devices must be an int >= 1, got "
                f"{self.devices!r}")
        if self.assign is not None:
            # Freeze to a sorted pair tuple so the frozen plan stays
            # immutable (callers may pass any mapping).
            object.__setattr__(
                self, "assign",
                tuple(sorted((str(k), int(v))
                             for k, v in dict(self.assign).items())))
        if self.device_assign is not None:
            object.__setattr__(
                self, "device_assign",
                tuple(sorted((str(k), int(v))
                             for k, v in dict(self.device_assign).items())))
        if self.cut_objective not in _CUT_OBJECTIVES:
            raise ValueError(
                f"ExecutionPlan.cut_objective must be one of "
                f"{_CUT_OBJECTIVES}, got {self.cut_objective!r}")
        if self.trace_capacity is not None and (
                not isinstance(self.trace_capacity, int)
                or isinstance(self.trace_capacity, bool)
                or self.trace_capacity < 1):
            raise ValueError(
                f"ExecutionPlan.trace_capacity must be None or an int "
                f">= 1, got {self.trace_capacity!r}")
        if self.profile is not None:
            # Accept a Profile, its as_cut_weights() mapping, or the
            # frozen tuple form a prior plan normalized to (so
            # dataclasses.replace round-trips); freeze to sorted pair
            # tuples like `assign`.
            prof = self.profile
            if hasattr(prof, "as_cut_weights"):
                prof = prof.as_cut_weights()
            if isinstance(prof, tuple):
                prof = {k: dict(v) for k, v in prof}
            if (not isinstance(prof, Mapping) or "actors" not in prof
                    or set(prof) - {"actors", "channels"}):
                raise ValueError(
                    "ExecutionPlan.profile must be a "
                    "repro.core.trace.Profile or a mapping with 'actors' "
                    f"(and optional 'channels') weights, got {prof!r}")
            object.__setattr__(self, "profile", (
                ("actors", tuple(sorted(
                    (str(k), int(v))
                    for k, v in dict(prof["actors"]).items()))),
                ("channels", tuple(sorted(
                    (str(k), int(v))
                    for k, v in dict(prof.get("channels", {})).items()))),
            ))
        if not (isinstance(self.donate, bool) or self.donate == "auto"):
            raise ValueError(
                f"ExecutionPlan.donate must be True, False or 'auto', got "
                f"{self.donate!r}")
        if self.donate_threshold_bytes is not None and (
                not isinstance(self.donate_threshold_bytes, int)
                or isinstance(self.donate_threshold_bytes, bool)
                or self.donate_threshold_bytes < 0):
            raise ValueError(
                f"ExecutionPlan.donate_threshold_bytes must be None or an "
                f"int >= 0, got {self.donate_threshold_bytes!r}")
        if self.order is not None:
            object.__setattr__(self, "order", tuple(self.order))
        if self.accelerated is not None:
            object.__setattr__(self, "accelerated", tuple(self.accelerated))
        if self.n_iterations is not None and self.n_iterations < 0:
            raise ValueError(
                f"ExecutionPlan: n_iterations must be >= 0, got "
                f"{self.n_iterations}")

    def validate(self, network: "Network", *,
                 stream_persistent: Optional[bool] = None,
                 stream_on_fault: Optional[str] = None,
                 stream_checkpoint_dir: Optional[str] = None
                 ) -> "ExecutionPlan":
        """Judge the plan as a whole against ``network`` — THE cross-field
        rule book, called by ``Network.compile`` (via ``Program``) before
        anything is built and by ``Program.stream`` before a stream runs.

        Each rule raises a single-sentence ``ValueError`` naming the
        offending field pair.  ``__post_init__`` only checks fields in
        isolation, so a plan object can always be constructed; it becomes
        a *valid* plan only relative to a network.  The stream-only rules
        engage when ``stream_persistent`` / ``stream_on_fault`` are
        passed (``Program.stream`` forwards its arguments); plain
        compiles leave them None.  Returns ``self`` so call sites can
        chain ``plan.validate(net)``.
        """
        if (self.cores != 1 or self.assign is not None
                or self.cut_objective != "crossing") \
                and self.mode != "megakernel":
            raise ValueError(
                f"ExecutionPlan(mode={self.mode!r}): cores=/assign=/"
                "cut_objective= are grid-partition knobs of the megakernel "
                "backend; the host executors have no core axis (use "
                "mode=Mode.MEGAKERNEL, or accelerated=[...] for "
                "host/accelerator placement)")
        if self.guards and self.mode not in ("dynamic", "megakernel"):
            raise ValueError(
                f"ExecutionPlan(mode={self.mode!r}): guards=True is a "
                "sweep-loop health knob of the dynamic and megakernel "
                "backends; the static specializer register-allocates its "
                "channels away and the interpreter fires eagerly, so "
                "neither has the per-channel cursor state the guards "
                "watch")
        if self.trace and self.mode not in ("dynamic", "megakernel"):
            raise ValueError(
                f"ExecutionPlan(mode={self.mode!r}): trace=True is a "
                "sweep-loop observability knob of the dynamic and "
                "megakernel backends; the static/interpreted schedules "
                "have no firing attempts to record (every actor fires by "
                "construction)")
        if self.trace_capacity is not None and not self.trace:
            raise ValueError(
                "ExecutionPlan.trace_capacity requires trace=True")
        if self.cut_objective == "profile" and self.profile is None:
            raise ValueError(
                "ExecutionPlan(cut_objective='profile') needs measured "
                "weights: run once with ExecutionPlan(trace=True), then "
                "pass profile=RunResult.trace.profile() (or its "
                ".as_cut_weights() dict)")
        if self.profile is not None and self.cut_objective != "profile":
            raise ValueError(
                f"ExecutionPlan.profile is only consumed by "
                f"cut_objective='profile', but the plan says "
                f"{self.cut_objective!r}")
        if self.devices > 1 and self.cores != 1:
            raise ValueError(
                f"ExecutionPlan(devices={self.devices}, cores="
                f"{self.cores}): devices= (the mesh axis) and cores= (the "
                "megakernel grid axis) are exclusive — pick one partition "
                "axis per plan")
        if self.device_assign is not None and self.devices == 1:
            raise ValueError(
                "ExecutionPlan(device_assign=..., devices=1): "
                "device_assign places actors on mesh devices, so it "
                "requires devices > 1 (use assign= for the megakernel "
                "grid's core map)")
        if self.devices > 1 and self.mode != "dynamic":
            raise ValueError(
                f"ExecutionPlan(mode={self.mode!r}, devices="
                f"{self.devices}): multi-device sharding runs the "
                "token-driven dynamic executor per device; use "
                "mode='dynamic' (one megakernel per device is a ROADMAP "
                "item, not a plan knob yet)")
        if self.devices > 1 and self.accelerated is not None:
            raise ValueError(
                f"ExecutionPlan(devices={self.devices}, "
                "accelerated=[...]): sharding and heterogeneous "
                "host/accelerator placement are exclusive — the mesh IS "
                "the accelerator set under devices=, so drop "
                "accelerated= (or stream with devices=1)")
        needs_iters = (self.mode in ("static", "interpreted")
                       or self.accelerated is not None)
        if needs_iters and self.n_iterations is None:
            raise ValueError(
                f"ExecutionPlan(mode={self.mode!r}"
                + (", accelerated=[...]" if self.accelerated is not None else "")
                + "): pass n_iterations= — static/interpreted schedules "
                "compile a fixed iteration count, and heterogeneous plans "
                "size their boundary feed/fetch slabs with it (dynamic "
                "mode alone runs to quiescence without one)")
        if self.accelerated is not None:
            unknown = set(self.accelerated) - set(network.actors)
            if unknown:
                raise ValueError(
                    f"ExecutionPlan.accelerated names unknown actors "
                    f"{sorted(unknown)}; known: {sorted(network.actors)}")
        if self.assign is not None and self.accelerated is None:
            # Explicit core maps must cover the executed network; under
            # accelerated= the executed network is the split subnetwork,
            # whose partition_layout re-validates against the right
            # actor set.
            network.validate_partition(dict(self.assign), self.cores)
        if self.device_assign is not None:
            network.validate_partition(dict(self.device_assign),
                                       self.devices, unit="device")
        if (stream_persistent is not None or stream_on_fault is not None
                or stream_checkpoint_dir is not None):
            if self.accelerated is None:
                raise ValueError(
                    "Program.stream: this plan has no heterogeneous "
                    "placement; pass ExecutionPlan(accelerated=[...], "
                    "n_iterations=chunk) so boundary channels become "
                    "host feed/fetch actors")
            # persistent x on_fault="resume"/"skip" is legal since PR 10:
            # a faulting persistent entry falls back to the chunked loop,
            # whose per-chunk checkpoints make the policy meaningful.
            # What persistent mode still cannot do is DURABLE cadence —
            # one entry has no chunk boundaries to snapshot at.
            if stream_persistent and stream_checkpoint_dir is not None:
                raise ValueError(
                    "Program.stream: persistent=True runs the whole "
                    "stream as one kernel entry with no chunk boundaries "
                    "to snapshot at, so checkpoint_dir= has no cadence; "
                    "use the chunked loop (persistent=False) for durable "
                    "checkpoints")
        return self


@dataclasses.dataclass(frozen=True)
class RunResult:
    """One execution's outcome.

    ``state`` is the final :class:`NetworkState` (bit-identical to the
    legacy entrypoints' output for the same plan).  ``fire_counts`` /
    ``sweeps`` are populated by dynamic mode only.  ``diagnostics`` is
    the decoded :class:`repro.core.health.Diagnostics` of dynamic /
    megakernel runs — with guards off it still carries the ``stalled``
    flag (the sweep loop left through its budget, not quiescence); with
    ``ExecutionPlan(guards=True)`` it adds per-channel fault words and
    high-water occupancy marks.  ``trace`` is the decoded
    :class:`repro.core.trace.Trace` of a ``plan.trace=True`` run
    (firing-level events plus occupancy samples; None otherwise).
    """

    state: NetworkState
    fire_counts: Optional[Dict[str, jax.Array]] = None
    sweeps: Optional[jax.Array] = None
    diagnostics: Optional[Diagnostics] = None
    trace: Optional[Trace] = None


@dataclasses.dataclass(frozen=True)
class ProgramStats:
    """Static + last-run telemetry for a compiled program.

    ``actor_flops`` is the per-firing FLOP annotation (``cost_flops``);
    ``actor_window_bytes`` the bytes moved through that actor's ports per
    firing (Eq. 1 windows); ``actor_intensity`` their ratio — the
    operational-intensity coordinate of a roofline plot.

    Megakernel programs additionally report the device-residency split:
    ``scratch_bytes`` (buffered Eq. 1 rings + cursor block held in kernel
    scratch for the whole run — forwarded channels contribute nothing),
    ``transient_scratch_bytes`` (ring bytes of the transient channels,
    the forwarding upper bound), ``forwarded_fifos`` / ``reclaimed_
    scratch_bytes`` (the channels actually lowered to loop-carried
    windows under this plan's partition, and the ring bytes that
    reclaimed) and ``hbm_state_bytes`` (the kernel's HBM operands — ring
    copies, actor states, hoisted closure arrays — measured from the
    last run's state).  ``resolved_donate`` is the per-graph outcome of
    ``donate="auto"`` and ``resolved_donate_threshold`` the buffered-
    bytes ceiling it used (``plan.donate_threshold_bytes`` or the
    measured 1 MiB default).

    The ``last_stream_*`` fields describe the last :meth:`Program.stream`
    call: chunk count, whether persistent-feed mode ran, and the staging
    traffic — ``last_stream_staged_bytes_per_chunk`` is what crosses the
    host boundary at each chunk (chunked mode re-stages the megakernel's
    ring/cursor scratch every entry on top of the feed/fetch slabs;
    persistent mode stages rings once and pays only the slab share), and
    ``last_stream_total_staged_bytes`` the whole stream's staging bill.

    Grid-partitioned megakernel programs (``plan.cores``) add the
    per-partition telemetry: ``grid_cores``, ``partition_actors`` (actor
    names per core, visit order), ``core_scratch_bytes`` (each core's
    private ring block, forwarding excluded), ``shared_scratch_bytes``
    (partition-crossing rings plus their semaphore cursor rows),
    ``shared_fifos`` (the crossing channels), ``core_cursor_rows`` (the
    per-core private cursor-block split — the shared semaphore block
    holds the remaining ``len(shared_fifos)`` rows), ``cut_objective``
    (the partition cut's criterion) and ``partition_fire_counts``
    (firings per core in the last run — the occupancy telemetry of each
    core's bounded firing loop).

    Sharded programs (``plan.devices > 1`` — :mod:`repro.core.shard`)
    report ``devices`` (always present; 1 when unsharded),
    ``device_partition_actors`` (actor names per mesh device, visit
    order), ``collective_bytes_per_sweep`` (bytes every sweep-barrier
    exchange moves: each crossing channel's ring + rd/wr cursor pair,
    plus the quiescence flag — the collective analogue of the grid's
    ``shared_scratch_bytes`` polling surface) and
    ``quiescence_allreduces`` (barrier rounds of the last run, one
    progress all-reduce each).
    """

    mode: str
    n_actors: int
    n_fifos: int
    buffer_bytes: int
    register_fifos: Tuple[str, ...]
    iteration_flops: int
    actor_flops: Dict[str, int]
    actor_window_bytes: Dict[str, int]
    actor_intensity: Dict[str, float]
    last_sweeps: Optional[int] = None
    last_fire_counts: Optional[Dict[str, int]] = None
    resolved_donate: Optional[bool] = None
    resolved_donate_threshold: Optional[int] = None
    scratch_bytes: Optional[int] = None
    transient_scratch_bytes: Optional[int] = None
    forwarded_fifos: Optional[Tuple[str, ...]] = None
    reclaimed_scratch_bytes: Optional[int] = None
    hbm_state_bytes: Optional[int] = None
    grid_cores: Optional[int] = None
    partition_actors: Optional[Tuple[Tuple[str, ...], ...]] = None
    core_scratch_bytes: Optional[Tuple[int, ...]] = None
    shared_scratch_bytes: Optional[int] = None
    shared_fifos: Optional[Tuple[str, ...]] = None
    core_cursor_rows: Optional[Tuple[int, ...]] = None
    cut_objective: Optional[str] = None
    partition_fire_counts: Optional[Tuple[int, ...]] = None
    last_stream_chunks: Optional[int] = None
    last_stream_persistent: Optional[bool] = None
    last_stream_staged_bytes_per_chunk: Optional[int] = None
    last_stream_total_staged_bytes: Optional[int] = None
    devices: int = 1
    device_partition_actors: Optional[Tuple[Tuple[str, ...], ...]] = None
    collective_bytes_per_sweep: Optional[int] = None
    quiescence_allreduces: Optional[int] = None

    #: Version of the :meth:`to_json` schema.  Bump ONLY when a field is
    #: renamed/removed or its meaning changes; adding optional fields is
    #: backward-compatible and keeps the version.  v2 (multi-device
    #: sharding): added ``devices`` (now always present, 1 when
    #: unsharded — the semantic change behind the bump) plus the
    #: sharding telemetry ``device_partition_actors`` /
    #: ``collective_bytes_per_sweep`` / ``quiescence_allreduces``;
    #: every v1 field survives unchanged, so v1 consumers keep parsing.
    SCHEMA_VERSION = 2

    def to_json(self) -> Dict[str, Any]:
        """The stats as a ``json.dump``-able dict (committed schema).

        Every dataclass field appears under its own name with tuples
        lowered to lists; ``schema_version`` pins the layout so external
        dashboards can parse dumps across repo versions.
        """
        def lower(v):
            if isinstance(v, tuple):
                return [lower(x) for x in v]
            if isinstance(v, dict):
                return {k: lower(x) for k, x in v.items()}
            return v

        doc: Dict[str, Any] = {"schema_version": self.SCHEMA_VERSION}
        for f in dataclasses.fields(self):
            doc[f.name] = lower(getattr(self, f.name))
        return doc


# ---------------------------------------------------------------------- #
# Durable-checkpoint payload helpers (PR 10): Trace and NetworkState go
# through plain containers so repro.checkpoint's skeleton serializer can
# round-trip them across a process boundary.
# ---------------------------------------------------------------------- #
def _trace_to_payload(t: Trace) -> Dict[str, Any]:
    return {"actor_names": list(t.actor_names),
            "fifo_names": list(t.fifo_names),
            "events": np.asarray(t.events, np.int32),
            "capacity": int(t.capacity),
            "dropped": int(t.dropped),
            "actor_flops": [int(x) for x in t.actor_flops],
            "fifo_token_bytes": [int(x) for x in t.fifo_token_bytes],
            "actor_cores": (None if t.actor_cores is None
                            else [int(x) for x in t.actor_cores])}


def _trace_from_payload(d: Mapping[str, Any]) -> Trace:
    # Older snapshots also carry a "wall_time_s" entry; it is ignored.
    return Trace(actor_names=tuple(d["actor_names"]),
                 fifo_names=tuple(d["fifo_names"]),
                 events=np.asarray(d["events"], np.int32),
                 capacity=int(d["capacity"]),
                 dropped=int(d["dropped"]),
                 actor_flops=tuple(int(x) for x in d["actor_flops"]),
                 fifo_token_bytes=tuple(int(x)
                                        for x in d["fifo_token_bytes"]),
                 actor_cores=(None if d["actor_cores"] is None
                              else tuple(int(x) for x in d["actor_cores"])))


class Program:
    """A network compiled under a plan; run with :meth:`run` or
    :meth:`stream`.  Built via :meth:`repro.core.network.Network.compile`.
    """

    def __init__(self, network: Network, plan: ExecutionPlan):
        self.plan = plan
        self.source_network = network
        self._last: Optional[RunResult] = None
        self._last_is_stream_chunk = False
        #: Per-chunk fault/recovery log of the last :meth:`stream` call
        #: (entries only for chunks that needed the on_fault policy).
        self.last_stream_report: List[Dict[str, Any]] = []
        #: Telemetry of the last :meth:`stream` call (chunks / persistent /
        #: staged bytes), surfaced through :meth:`stats`.
        self._last_stream: Optional[Dict[str, Any]] = None
        #: Merged :class:`repro.core.trace.Trace` across the last
        #: :meth:`stream` call's chunks (None unless ``plan.trace``).
        self.last_stream_trace: Optional[Trace] = None
        #: Accumulated per-actor fire counts across the last
        #: :meth:`stream` / :meth:`resume_stream` call's chunks (None for
        #: modes without counts); a resumed stream's totals equal the
        #: uninterrupted run's — the counts ride the durable checkpoint.
        self.last_stream_fire_counts: Optional[Dict[str, int]] = None
        #: Accumulated sweeps across the last stream call's chunks.
        self.last_stream_sweeps: Optional[int] = None
        #: Full-length programs built lazily by persistent-feed streams,
        #: keyed by total window count (reused across stream() calls).
        self._persistent_progs: Dict[int, "Program"] = {}
        #: Bounded-sweep twin programs built lazily by
        #: :meth:`run_checkpointed`, keyed by segment sweep budget.
        self._segment_progs: Dict[int, "Program"] = {}
        self._feed_by_fifo: Dict[str, str] = {}
        self._fetch_by_fifo: Dict[str, str] = {}
        # THE cross-field rule book: every plan x network combination is
        # judged here (and only here) before anything is built.
        plan.validate(network)
        if plan.accelerated is not None:
            sub, feeds, fetches = heterogeneous_split(
                network, list(plan.accelerated), plan.n_iterations)
            self.network = sub
            self._feed_by_fifo = {f[len("__feed_"):]: f for f in feeds}
            self._fetch_by_fifo = {f[len("__fetch_"):]: f for f in fetches}
        else:
            self.network = network
        self.donate = self._resolve_donate(plan, self.network)
        self._layout = None
        self._partition = None
        self._shard_layout = None
        self._shard_partition = None
        if plan.mode == "megakernel":
            from repro.core.megakernel import lower_network, partition_layout
            self._layout = lower_network(self.network)
            self._partition = partition_layout(
                self.network, self._layout, plan.cores,
                dict(plan.assign) if plan.assign is not None else None,
                objective=plan.cut_objective,
                forward_transients=plan.specialize,
                profile=({k: dict(v) for k, v in plan.profile}
                         if plan.profile is not None else None))
        if plan.devices > 1:
            if jax.device_count() < plan.devices:
                raise RuntimeError(
                    f"ExecutionPlan(devices={plan.devices}): only "
                    f"{jax.device_count()} JAX device(s) visible; on a CPU "
                    "host force a bigger mesh with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={plan.devices} "
                    "(set before jax initializes)")
            from repro.core.shard import build_device_partition
            self._shard_layout, self._shard_partition = \
                build_device_partition(
                    self.network, plan.devices,
                    device_assign=(dict(plan.device_assign)
                                   if plan.device_assign is not None
                                   else None),
                    cut_objective=plan.cut_objective)
        # donate="auto" must never consume a state the *caller* passed in
        # (donated inputs are invalidated; callers legitimately reuse
        # states across runs), so auto donation applies only to run(None),
        # where the program donates its own private copy.  Two runners are
        # built for that case; jit tracing is lazy, so an unused variant
        # costs nothing.
        if plan.mode == "megakernel" or plan.devices > 1:
            # Donation is meaningless here (megakernel buffers are staged
            # through kernel scratch; sharded state is replicated across
            # the mesh): one runner serves both donate paths and no
            # private copy is ever made (_resolve_donate -> False).
            runner = self._make_runner(False)
            self._runners = {False: runner, True: runner}
        elif isinstance(plan.donate, bool):
            self._runners = {plan.donate: self._make_runner(plan.donate)}
        else:
            self._runners = {False: self._make_runner(False)}
            if self.donate:
                self._runners[True] = self._make_runner(True)

    def _make_runner(self, donate: bool):
        plan = self.plan
        order = list(plan.order) if plan.order is not None else None
        trace_cap = ((plan.trace_capacity or TRACE_CAPACITY_DEFAULT)
                     if plan.trace else None)
        if plan.mode == "static":
            return _compile_static(
                self.network, plan.n_iterations, mode=plan.runtime_mode,
                order=order, donate=donate, specialize=plan.specialize,
                unroll_bound=plan.unroll_bound)
        if plan.mode == "dynamic":
            if plan.devices > 1:
                from repro.core.shard import compile_sharded
                return compile_sharded(
                    self.network, self._shard_layout, self._shard_partition,
                    plan.max_sweeps, mode=plan.runtime_mode,
                    multi_firing=plan.multi_firing, guards=plan.guards,
                    trace_capacity=trace_cap)
            return _compile_dynamic(
                self.network, plan.max_sweeps, mode=plan.runtime_mode,
                multi_firing=plan.multi_firing, donate=donate,
                return_sweeps=True, guards=plan.guards,
                trace_capacity=trace_cap)
        if plan.mode == "megakernel":
            from repro.core.megakernel import compile_megakernel
            return compile_megakernel(
                self.network, max_sweeps=plan.max_sweeps,
                mode=plan.runtime_mode, multi_firing=plan.multi_firing,
                layout=self._layout,
                partition=self._partition, guards=plan.guards,
                trace_capacity=trace_cap)
        return functools.partial(
            _run_interpreted, self.network,
            n_iterations=plan.n_iterations, order=order, donate=donate)

    @staticmethod
    def _resolve_donate(plan: ExecutionPlan, network: Network) -> bool:
        """Resolve ``donate="auto"`` per graph.

        Donation helps only while the ring-buffered state stays small:
        once the buffered (non-register-allocated) channel bytes dominate,
        the aliasing constraint regresses throughput (MD: 707 -> 415
        tok/s; DPD, whose bulk channels registerize, gains 1.2x —
        EXPERIMENTS.md §Executor perf).  The megakernel stages buffers
        through kernel scratch itself, so donation buys nothing there.
        """
        if plan.mode == "megakernel":
            return False    # even explicit donate=True: nothing to donate
        if plan.devices > 1:
            # The sharded runner keeps the state replicated across the
            # mesh and merges a fresh copy out — nothing to alias.
            return False
        if isinstance(plan.donate, bool):
            return plan.donate
        # register_fifos leave their ring buffers untouched ONLY under the
        # specialized static executor; every other mode keeps those rings
        # live, so their bytes count as buffered there.
        registerized = (network.register_fifos
                        if plan.mode == "static" and plan.specialize
                        else frozenset())
        buffered = sum(
            spec.capacity_bytes for name, spec in network.fifos.items()
            if name not in registerized)
        return buffered <= Program._donate_threshold(plan)

    @staticmethod
    def _donate_threshold(plan: ExecutionPlan) -> int:
        """The buffered-bytes ceiling of the ``donate="auto"`` heuristic."""
        if plan.donate_threshold_bytes is not None:
            return plan.donate_threshold_bytes
        return _DONATE_AUTO_BUFFERED_BYTES_MAX

    # ------------------------------------------------------------------ #
    def init_state(self) -> NetworkState:
        """Fresh state of the executed network (the accelerator subnetwork
        under a heterogeneous plan)."""
        return self.network.init_state()

    def run(self, state: Optional[Any] = None,
            private: bool = False) -> RunResult:
        """Execute once from ``state`` (fresh :meth:`init_state` if None).

        Legacy ``{"fifos": ..., "actors": ...}`` dict states are accepted.
        With an explicit ``plan.donate=True`` a passed-in state's buffers
        are consumed; under the default ``"auto"`` only runs that create
        their own state donate (a private copy), so caller-held arrays
        stay valid.  ``private=True`` declares a passed-in state the
        caller's own, no array of it held anywhere else: it is donated
        as that private copy would be, without the copy.
        """
        st = self.init_state() if state is None else state
        if state is None or private:
            donate_now = self.donate
            if donate_now and state is None:
                # init_state() may alias arrays staged in the graph closure
                # (e.g. a source's signal slab); donating those would
                # poison every later init_state() of the network.  When
                # run() creates the state itself, donate a private copy.
                st = jax.tree.map(jnp.copy, st)
        else:
            # A caller-passed state is consumed only under an *explicit*
            # donate=True plan; the "auto" heuristic never invalidates
            # arrays the caller may still hold.
            donate_now = self.plan.donate is True
        runner = self._runners[donate_now]
        if self.plan.mode in ("dynamic", "megakernel"):
            with jax.profiler.TraceAnnotation(SPAN_LAUNCH):
                if self.plan.mode == "dynamic":
                    final, counts, sweeps, stalled, health, trc = runner(st)
                else:
                    res = runner(st)     # _MegaResult: 3-tuple + attributes
                    final, counts, sweeps = res
                    stalled, health = res.stalled, res.health
                    trc = res.trace
            # One scalar host sync; a stalled exit then pays the eager
            # per-actor forensics, the path where latency is moot.
            with jax.profiler.TraceAnnotation(SPAN_WAIT):
                stalled_b = bool(stalled)
            with jax.profiler.TraceAnnotation(SPAN_DECODE):
                trace = None if trc is None else self._decode_ring(trc)
                diag = decode_health(self.network, health, stalled_b,
                                     final if stalled_b else None)
            result = RunResult(final, fire_counts=counts, sweeps=sweeps,
                               diagnostics=diag, trace=trace)
            self._last = result
            self._last_is_stream_chunk = False
            if not diag.ok:
                if self.plan.guards:
                    err = NetworkFaultError(diag)
                    err.result = result
                    raise err
                if stalled_b:
                    # Guards off: surface the exhaustion (satellite fix for
                    # the silent max_sweeps return) without changing the
                    # no-raise contract of unguarded plans.
                    warnings.warn(
                        f"Program.run: sweep budget "
                        f"(max_sweeps={self.plan.max_sweeps}) exhausted "
                        f"with work remaining — partial state returned; "
                        f"{diag.summary()}", RuntimeWarning, stacklevel=2)
            return result
        with jax.profiler.TraceAnnotation(SPAN_LAUNCH):
            final = runner(st)           # static/interpreted: bare state
        result = RunResult(final)
        self._last = result
        self._last_is_stream_chunk = False
        return result

    def _decode_ring(self, trc) -> Trace:
        """The run's device trace ring as a host :class:`Trace`."""
        if self.plan.devices > 1:
            # Sharded runs return one per-device trace ring each
            # (all-gathered); decode and merge, with actor_cores recording
            # the mesh device instead of a grid core.
            from repro.core.shard import decode_device_trace
            return decode_device_trace(self.network, trc,
                                       self._shard_partition)
        cores = None
        part = self._partition
        if part is not None and part.n_cores > 1:
            names = tuple(self.network.actors)
            cores = {names[i]: c for c, rows in enumerate(part.core_rows)
                     for i in rows}
        return decode_trace(self.network, trc, actor_cores=cores)

    def collect(self, actor: str, state: Optional[NetworkState] = None) -> Any:
        """Run ``actor``'s ``finish`` hook on its state (paper §3.1);
        defaults to the last :meth:`run`'s final state."""
        if state is None:
            if self._last is None:
                raise ValueError("Program.collect: no run yet; pass a state "
                                 "or call run() first")
            if self._last_is_stream_chunk:
                raise ValueError(
                    "Program.collect: the last execution was stream(), whose "
                    "implicit final state covers only the LAST chunk; use "
                    "the dict stream() returned for the full output, or "
                    "pass a state explicitly")
            state = self._last.state
        return collect_sink(self.network, state, actor)

    # ------------------------------------------------------------------ #
    # Chunked host-feed / fetch loop (heterogeneous plans).                #
    # ------------------------------------------------------------------ #
    def _set_actor(self, state: NetworkState, actor: str, value: Any) -> NetworkState:
        return state.replace_actor(self.network.actor_index[actor], value)

    def _normalize_feed(self, fifo: str, feed_actor: str, spec: Any,
                        raw: Any, where: str = ""):
        """Validate + window-normalize one feed array; returns
        ``(raw_dtype, (n, r, *token_shape) array)``.  ``where`` labels
        the chunk in errors when the feed arrived as a per-chunk list."""
        raw = jnp.asarray(raw)
        # Real-to-real casts (int windows into a float channel, float
        # probes into a uint8 frame channel) are long-standing host
        # conveniences; complex data into a real channel silently
        # drops the imaginary half, which is always a wrong feed wired
        # to the right name — reject that one here with the actor
        # named instead of staging garbage.
        if (jnp.issubdtype(raw.dtype, jnp.complexfloating)
                and not jnp.issubdtype(jnp.dtype(spec.dtype),
                                       jnp.complexfloating)):
            raise ValueError(
                f"Program.stream: feed {fifo!r}{where} (staged into actor "
                f"{feed_actor!r}) carries dtype {raw.dtype}, but the "
                f"channel expects {jnp.dtype(spec.dtype)}; cast the "
                "stream explicitly if the conversion is intended")
        arr = raw.astype(spec.dtype)
        window = (spec.rate,) + tuple(spec.token_shape)
        if arr.shape[1:] != window:
            if arr.ndim >= 1 and arr.shape[0] % spec.rate == 0 \
                    and arr.shape[1:] == tuple(spec.token_shape):
                arr = arr.reshape((-1,) + window)
            else:
                raise ValueError(
                    f"Program.stream: feed {fifo!r}{where} (staged into "
                    f"actor {feed_actor!r}) has shape {arr.shape}; expected "
                    f"(n, {spec.rate}, *{tuple(spec.token_shape)}) "
                    "windows or the flattened token stream")
        return raw.dtype, arr

    def stream(self, feeds: Mapping[str, Any], on_fault: str = "raise",
               max_retries: int = 2,
               persistent: bool = False,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 1) -> Dict[str, jax.Array]:
        """Stream host data through the accelerated subnetwork in chunks.

        ``feeds`` maps each *inbound boundary channel* name to its full
        token stream — ``(total_windows, r, *token_shape)``, the
        flattened ``(total_windows * r, *token_shape)``, or a
        list/tuple of per-chunk arrays (one element per
        ``plan.n_iterations``-window chunk, each in either layout; every
        element must keep the dtype and shape of chunk 0 — a mismatch is
        rejected naming the chunk index and channel, it is never staged).
        The stream is cut into chunks of ``plan.n_iterations`` windows;
        each chunk is staged into the feed actors, executed under the
        plan, and the fetch actors' slabs collected.  Actor and
        internal-FIFO state (e.g. filter histories, delay tokens)
        carries across chunks — streaming N chunks equals one long run
        over the concatenation.

        ``persistent=True`` is the persistent-feed mode: instead of
        re-entering the compiled chunk-length program once per chunk —
        which re-stages every buffered ring HBM -> kernel scratch on
        each megakernel entry — the stream compiles ONE full-length
        program (same network, ``n_iterations=total``), stages the feed
        slabs once, and runs to completion in a single entry; rings stay
        resident across what used to be chunk boundaries.  Outputs are
        bit-identical to the chunked loop (the concatenation invariant
        above).  With ``on_fault="resume"``/``"skip"`` a faulting
        persistent entry *falls back to the chunked loop* — the only
        place per-chunk checkpoints exist to restore — logged in
        ``last_stream_report`` as ``action="fallback-chunked"``; the
        healthy path keeps the single-entry staging savings, reported by
        :meth:`stats` (``last_stream_staged_bytes_per_chunk`` /
        ``last_stream_total_staged_bytes``).  Durable snapshots are the
        one thing persistent mode cannot do (no chunk boundaries), so
        ``checkpoint_dir=`` requires ``persistent=False``.

        ``checkpoint_dir=`` makes the chunked loop *durable*: every
        ``checkpoint_every`` chunks (and at the final chunk) the full
        progress — NetworkState rings + cursors, fetched output slabs,
        accumulated fire counts/sweeps, per-chunk trace rings — is
        written as a CRC'd, atomically-committed, versioned snapshot
        (:mod:`repro.checkpoint`).  After a process kill,
        :meth:`resume_stream` on a freshly compiled program continues
        from the newest intact snapshot bit-identically to the
        uninterrupted run.

        The loop checkpoints the :class:`NetworkState` before each chunk;
        ``on_fault`` decides what a :class:`NetworkFaultError` from a
        guarded run (``ExecutionPlan(guards=True)``) does:

          * ``"raise"`` (default): re-raise, augmented with the chunk
            index — the stream dies but the error names chunk, channel
            and actors.
          * ``"resume"``: re-stage the chunk from the checkpoint and
            retry up to ``max_retries`` times, then raise.  Retries are
            meaningful for *nondeterministic* faults (flaky hardware, a
            poisoned transient the caller repairs out of band) — a
            deterministic fault fails identically each attempt.
          * ``"skip"``: restore the checkpoint, emit all-zero windows for
            the chunk's fetch slabs, and continue with the next chunk —
            the degraded-service mode of a serving loop.

        Chunks needing the policy are logged in ``last_stream_report``
        (dicts of chunk / attempts / action / fault).  Unguarded plans
        never raise ``NetworkFaultError``, so the policy only engages
        under ``guards=True``.

        Returns ``{outbound_channel: (total_windows, r, *token_shape)}``.
        """
        with jax.profiler.TraceAnnotation(SPAN_STREAM):
            with jax.profiler.TraceAnnotation(SPAN_PREPARE):
                arrays, total, chunk, n_chunks, slab_bytes, ring_bytes = \
                    self._prepare_stream(feeds, on_fault, max_retries,
                                         persistent, checkpoint_dir,
                                         checkpoint_every)
            report: List[Dict[str, Any]] = []
            self.last_stream_report = report
            if persistent:
                try:
                    return self._stream_persistent(arrays, total, chunk,
                                                   n_chunks, slab_bytes,
                                                   ring_bytes)
                except NetworkFaultError as err:
                    if on_fault == "raise":
                        raise
                    # The checkpointed chunk loop is the only surface with
                    # something to restore; re-run the stream there (its
                    # outputs are bit-identical by the concatenation
                    # invariant, so the fallback changes recovery, not
                    # data).
                    report.append({"chunk": None, "attempts": 1,
                                   "action": "fallback-chunked",
                                   "fault": str(err)})
            return self._stream_chunked(arrays, total, chunk, n_chunks,
                                        on_fault, max_retries, slab_bytes,
                                        ring_bytes, report, checkpoint_dir,
                                        checkpoint_every)

    def _prepare_stream(self, feeds: Mapping[str, Any], on_fault: str,
                        max_retries: int, persistent: bool,
                        checkpoint_dir: Optional[str],
                        checkpoint_every: int):
        """Shared stream validation + feed normalization (stream and
        resume_stream enter the chunk loop through the same checks)."""
        if on_fault not in ("raise", "resume", "skip"):
            raise ValueError(
                f"Program.stream: on_fault must be 'raise', 'resume' or "
                f"'skip', got {on_fault!r}")
        # Stream-context cross-field rules (heterogeneous placement,
        # persistent x checkpoint_dir) live in the one plan rule book.
        self.plan.validate(self.source_network, stream_persistent=persistent,
                           stream_on_fault=on_fault,
                           stream_checkpoint_dir=checkpoint_dir)
        if not isinstance(max_retries, int) or isinstance(max_retries, bool) \
                or max_retries < 0:
            raise ValueError(
                f"Program.stream: max_retries must be an int >= 0, got "
                f"{max_retries!r}")
        if not isinstance(checkpoint_every, int) \
                or isinstance(checkpoint_every, bool) or checkpoint_every < 1:
            raise ValueError(
                f"Program.stream: checkpoint_every must be an int >= 1, "
                f"got {checkpoint_every!r}")
        chunk = self.plan.n_iterations
        if self.plan.mode == "static" and self.plan.specialize:
            # The specialized static executor requires phase-aligned input
            # cursors; chunk 2+ resumes from chunk 1's final state, so the
            # chunk size must cover whole phase-unroll periods.  Check here,
            # before any chunk runs, instead of failing mid-stream with a
            # resumption error that blames the state rather than the plan.
            period = phase_unroll_period(
                [spec.n_write_phases
                 for name, spec in self.network.fifos.items()
                 if name not in self.network.register_fifos],
                bound=self.plan.unroll_bound)
            if chunk % period:
                raise ValueError(
                    f"Program.stream: n_iterations={chunk} is not a "
                    f"multiple of the phase-unroll period {period} of the "
                    "accelerated subnetwork, so chunks after the first "
                    "would resume from non-phase-aligned cursors; use a "
                    "multiple (delay channels cycle 3, double buffers 2) "
                    "or plan specialize=False")
        unknown = set(feeds) - set(self._feed_by_fifo)
        if unknown:
            raise ValueError(
                f"Program.stream: unknown feed channels {sorted(unknown)}; "
                f"inbound boundary channels: {sorted(self._feed_by_fifo)}")
        missing = set(self._feed_by_fifo) - set(feeds)
        if missing:
            raise ValueError(
                f"Program.stream: missing feeds for inbound boundary "
                f"channels {sorted(missing)}")
        arrays: Dict[str, jax.Array] = {}
        total = None
        for fifo, arr in feeds.items():
            spec = self.source_network.fifos[fifo]
            feed_actor = self._feed_by_fifo[fifo]
            if isinstance(arr, (list, tuple)):
                # Per-chunk feed: one element per chunk.  Each element is
                # normalized on its own, then pinned to chunk 0's dtype
                # and window layout — chunk 2+ of a drifting stream must
                # fail HERE naming the chunk, not stage a silently cast /
                # misaligned slab (the cross-chunk validation gap).
                if len(arr) == 0:
                    raise ValueError(
                        f"Program.stream: feed {fifo!r} is an empty "
                        "per-chunk list; pass one array per chunk")
                dt0 = a0 = None
                parts = []
                for i, piece in enumerate(arr):
                    dt, a = self._normalize_feed(fifo, feed_actor, spec,
                                                 piece, where=f" chunk {i}")
                    if i == 0:
                        dt0, a0 = dt, a
                        if a.shape[0] != chunk:
                            raise ValueError(
                                f"Program.stream: per-chunk feed {fifo!r} "
                                f"chunk 0 covers {a.shape[0]} windows, but "
                                f"chunks are n_iterations={chunk} windows "
                                "each; pass whole chunks (or one "
                                "concatenated array)")
                    else:
                        if dt != dt0:
                            raise ValueError(
                                f"Program.stream: feed {fifo!r} chunk {i} "
                                f"carries dtype {dt}, but chunk 0 staged "
                                f"{dt0}; per-chunk feeds must keep one "
                                "dtype across the stream (cast explicitly "
                                "if the drift is intended)")
                        if a.shape != a0.shape:
                            raise ValueError(
                                f"Program.stream: feed {fifo!r} chunk {i} "
                                f"has window shape {tuple(a.shape)}, but "
                                f"chunk 0 staged {tuple(a0.shape)}; "
                                "per-chunk feeds must keep a consistent "
                                "window count and token shape across "
                                "chunks")
                    parts.append(a)
                arr = jnp.concatenate(parts, axis=0)
            else:
                _, arr = self._normalize_feed(fifo, feed_actor, spec, arr)
            if total is None:
                total = arr.shape[0]
            elif arr.shape[0] != total:
                raise ValueError(
                    f"Program.stream: feed {fifo!r} carries {arr.shape[0]} "
                    f"windows but other feeds carry {total}; all feeds "
                    "must cover the same number of iterations")
            arrays[fifo] = arr
        if total is None:
            raise ValueError("Program.stream: no feeds given")
        if total % chunk:
            raise ValueError(
                f"Program.stream: {total} windows do not divide into "
                f"chunks of n_iterations={chunk}; pad the stream or pick "
                "a dividing chunk size")
        n_chunks = total // chunk
        # Staging-traffic accounting (stats().last_stream_*): the boundary
        # feed/fetch slab share every chunk pays in either mode, plus the
        # megakernel's ring + cursor scratch footprint — which the chunked
        # loop re-stages HBM -> scratch on every kernel entry and the
        # persistent run stages exactly once.
        slab_bytes = 0
        for f in list(arrays) + list(self._fetch_by_fifo):
            spec = self.source_network.fifos[f]
            slab_bytes += chunk * spec.rate * spec.token_size_bytes
        if self._layout is not None:
            from repro.core.megakernel import entry_staging_bytes
            ring_bytes = entry_staging_bytes(self._layout, self._partition)
        else:
            ring_bytes = 0
        self._check_feed_domains(arrays, chunk)
        return arrays, total, chunk, n_chunks, slab_bytes, ring_bytes

    def _check_feed_domains(self, arrays: Mapping[str, jax.Array],
                            chunk: int) -> None:
        """Reject out-of-domain feed windows before any chunk runs.

        A staged value outside a channel's declared ``domain`` would trip
        the DOMAIN write guard mid-stream, blaming the run instead of the
        input.  The error names the chunk index the bad window lands in
        and — when the channel declares ``row_id_col`` — the request id
        carried by the offending row, so serving callers can quarantine
        the request without replaying the stream.
        """
        for fifo, arr in arrays.items():
            spec = self.source_network.fifos[fifo]
            if spec.domain is None:
                continue
            lo, hi = spec.domain
            a = np.asarray(arr)
            bad = (a < lo) | (a > hi) | ~np.isfinite(a.astype(np.float64))
            if not bad.any():
                continue
            idx = tuple(int(x) for x in np.argwhere(bad)[0])
            w = idx[0]
            detail = ""
            if spec.row_id_col is not None and len(idx) >= 2:
                rid = int(a[idx[:-1] + (int(spec.row_id_col),)])
                detail = f", request id {rid}"
            raise ValueError(
                f"Program.stream: feed {fifo!r} window {w} (chunk "
                f"{w // chunk}) carries value {a[idx]!r} outside the "
                f"channel domain [{lo}, {hi}]{detail}; drop or repair the "
                "request before streaming")

    def _stream_persistent(self, arrays: Mapping[str, jax.Array], total: int,
                           chunk: int, n_chunks: int, slab_bytes: int,
                           ring_bytes: int) -> Dict[str, jax.Array]:
        # One full-length program over the SAME source network: by the
        # concatenation invariant its single run is bit-identical to
        # the chunked loop, and the feed slabs (sized total instead of
        # chunk) are staged exactly once.
        with jax.profiler.TraceAnnotation(SPAN_STAGE):
            prog = self._persistent_progs.get(total)
            if prog is None:
                prog = Program(
                    self.source_network,
                    dataclasses.replace(self.plan, n_iterations=total))
                self._persistent_progs[total] = prog
            base = prog.init_state()
            for fifo, arr in arrays.items():
                base = prog._set_actor(base, prog._feed_by_fifo[fifo],
                                       (arr, jnp.int32(0)))
        result = prog.run(base)
        with jax.profiler.TraceAnnotation(SPAN_COLLECT):
            # collect() stays guarded: the implicit state belongs to the
            # full-length twin program, not this chunk-length one.
            self._last = result
            self._last_is_stream_chunk = True
            self.last_stream_trace = result.trace
            self.last_stream_fire_counts = (
                {k: int(v) for k, v in result.fire_counts.items()}
                if result.fire_counts is not None else None)
            self.last_stream_sweeps = (int(result.sweeps)
                                       if result.sweeps is not None
                                       else None)
            self._last_stream = {
                "chunks": n_chunks, "persistent": True,
                "staged_bytes_per_chunk": slab_bytes,
                "total_staged_bytes": ring_bytes + n_chunks * slab_bytes,
            }
            return {f: result.state.actor(prog._fetch_by_fifo[f])[0]
                    for f in self._fetch_by_fifo}

    def _stream_chunked(self, arrays: Mapping[str, jax.Array], total: int,
                        chunk: int, n_chunks: int, on_fault: str,
                        max_retries: int, slab_bytes: int, ring_bytes: int,
                        report: List[Dict[str, Any]],
                        checkpoint_dir: Optional[str],
                        checkpoint_every: int,
                        start_chunk: int = 0,
                        state: Optional[NetworkState] = None,
                        outs: Optional[Dict[str, list]] = None,
                        traces: Optional[List[Trace]] = None,
                        counts: Optional[Dict[str, int]] = None,
                        sweeps: int = 0) -> Dict[str, jax.Array]:
        """The chunked stream loop, resumable at any chunk boundary.

        ``stream`` enters it at chunk 0 with fresh accumulators;
        ``resume_stream`` enters it at the first chunk after the newest
        intact snapshot, with every accumulator restored — the loop body
        cannot tell the difference, which is the bit-identity argument.
        """
        if outs is None:
            outs = {f: [] for f in self._fetch_by_fifo}
        chunk_traces: List[Trace] = [] if traces is None else traces
        acc_counts = counts
        acc_sweeps = int(sweeps)
        self.last_stream_trace = None
        retrying = on_fault in ("resume", "skip")
        for c in range(start_chunk, n_chunks):
            # The per-chunk checkpoint: the last good NetworkState, before
            # this chunk's feeds are staged (a fresh stream's first stage
            # makes it).  Restoring it re-runs (or skips) the chunk with
            # actor/FIFO history intact.
            checkpoint = state
            attempts = 0
            while True:
                with jax.profiler.TraceAnnotation(SPAN_STAGE):
                    if checkpoint is None:
                        checkpoint = self.init_state()
                    base = checkpoint
                    if retrying and self.plan.donate is True:
                        # An explicit-donate run consumes its input
                        # buffers — which the staged state shares with the
                        # checkpoint — so every retryable attempt donates
                        # a private copy.
                        base = jax.tree.map(jnp.copy, checkpoint)
                    for fifo, arr in arrays.items():
                        base = self._set_actor(
                            base, self._feed_by_fifo[fifo],
                            (arr[c * chunk:(c + 1) * chunk], jnp.int32(0)))
                    for fifo, fetch in self._fetch_by_fifo.items():
                        slab, _ = base.actor(fetch)
                        base = self._set_actor(base, fetch,
                                               (jnp.zeros_like(slab),
                                                jnp.int32(0)))
                attempts += 1
                try:
                    chunk_res = self.run(base)
                    break
                except NetworkFaultError as err:
                    # Guard collect(): the implicit last state holds at
                    # most this chunk's fetch slabs, not the whole stream.
                    self._last_is_stream_chunk = True
                    if on_fault == "resume" and attempts <= max_retries:
                        continue
                    if on_fault == "skip":
                        report.append({"chunk": c, "attempts": attempts,
                                       "action": "skip", "fault": str(err)})
                        chunk_res = None
                        break
                    report.append({"chunk": c, "attempts": attempts,
                                   "action": "raise", "fault": str(err)})
                    err.args = (f"Program.stream: chunk {c} of {n_chunks} "
                                f"failed after {attempts} attempt(s): "
                                f"{err.args[0]}",)
                    raise
            with jax.profiler.TraceAnnotation(SPAN_COLLECT):
                if chunk_res is None:
                    # Skipped: the checkpoint stands, the chunk's windows
                    # are zeros.
                    state = checkpoint
                    for fifo, fetch in self._fetch_by_fifo.items():
                        outs[fifo].append(
                            jnp.zeros_like(state.actor(fetch)[0]))
                else:
                    state = chunk_res.state
                    if chunk_res.fire_counts is not None:
                        if acc_counts is None:
                            acc_counts = {}
                        for k, v in chunk_res.fire_counts.items():
                            acc_counts[k] = acc_counts.get(k, 0) + int(v)
                    if chunk_res.sweeps is not None:
                        acc_sweeps += int(chunk_res.sweeps)
                    if chunk_res.trace is not None:
                        chunk_traces.append(chunk_res.trace)
                    self._last_is_stream_chunk = True
                    if attempts > 1:
                        report.append({"chunk": c, "attempts": attempts,
                                       "action": "recovered", "fault": None})
                    for fifo, fetch in self._fetch_by_fifo.items():
                        outs[fifo].append(state.actor(fetch)[0])
                if checkpoint_dir is not None and (
                        (c + 1) % checkpoint_every == 0 or c + 1 == n_chunks):
                    # Snapshot AFTER the chunk commits: the payload is the
                    # full progress (state, fetched windows, fire counts,
                    # sweeps, trace ring) and the manifest's step is the
                    # count of chunks durably done.  A kill between
                    # snapshots loses at most checkpoint_every chunks of
                    # work, never data integrity (the writer commits by
                    # atomic rename).
                    self._save_stream_snapshot(
                        checkpoint_dir, c + 1, n_chunks, chunk, total, state,
                        outs, acc_counts, acc_sweeps, chunk_traces)
        with jax.profiler.TraceAnnotation(SPAN_COLLECT):
            self.last_stream_fire_counts = (
                dict(acc_counts) if acc_counts is not None else None)
            self.last_stream_sweeps = acc_sweeps
            self._last_stream = {
                "chunks": n_chunks, "persistent": False,
                "staged_bytes_per_chunk": ring_bytes + slab_bytes,
                "total_staged_bytes": n_chunks * (ring_bytes + slab_bytes),
            }
            # One Trace across the whole stream: later chunks' sweep
            # numbers are offset past the earlier chunks', so per-actor
            # firing counts and occupancy series read as a single run.
            self.last_stream_trace = merge_traces(chunk_traces)
            return {f: jnp.concatenate(ws, axis=0) for f, ws in outs.items()}

    # ------------------------------------------------------------------ #
    # Durable snapshots: payload <-> plain-container serialization.
    # ------------------------------------------------------------------ #
    def _save_stream_snapshot(self, directory: str, done_chunks: int,
                              n_chunks: int, chunk: int, total: int,
                              state: NetworkState, outs: Dict[str, list],
                              counts: Optional[Dict[str, int]], sweeps: int,
                              traces: List[Trace]) -> None:
        payload = {
            "state": self._state_payload(state),
            "outs": {f: [np.asarray(w) for w in ws]
                     for f, ws in outs.items()},
            "fire_counts": dict(counts) if counts is not None else None,
            "sweeps": int(sweeps),
            "traces": [_trace_to_payload(t) for t in traces],
        }
        meta = {
            "kind": "stream", "chunk": int(done_chunks),
            "n_chunks": int(n_chunks), "chunk_windows": int(chunk),
            "total_windows": int(total), "mode": self.plan.mode,
            "devices": int(self.plan.devices),
            "feed_fifos": sorted(self._feed_by_fifo),
            "fetch_fifos": sorted(self._fetch_by_fifo),
        }
        save_stream_checkpoint(directory, int(done_chunks), payload, meta)

    def _state_payload(self, state: NetworkState) -> Dict[str, Any]:
        """NetworkState -> plain containers of arrays (name-keyed, so the
        snapshot survives pytree-registration details of actor states)."""
        fifos = {name: {"buf": np.asarray(fs.buf), "rd": np.asarray(fs.rd),
                        "wr": np.asarray(fs.wr), "occ": np.asarray(fs.occ)}
                 for name, fs in zip(state.fifo_names, state.fifos)}
        actors = {name: [np.asarray(leaf) for leaf in jax.tree.leaves(a)]
                  for name, a in zip(state.actor_names, state.actors)}
        return {"fifos": fifos, "actors": actors}

    def _state_from_payload(self, payload: Mapping[str, Any]) -> NetworkState:
        """Rebuild a NetworkState on this program's network from a
        snapshot payload, validating names/shapes and restoring each
        actor's pytree structure from the template init state."""
        template = self.network.init_state()
        fifos = []
        for name, fs in zip(template.fifo_names, template.fifos):
            if name not in payload["fifos"]:
                raise CheckpointIntegrityError(
                    f"snapshot has no channel {name!r}; it was taken on a "
                    "different network")
            d = payload["fifos"][name]
            buf = jnp.asarray(np.asarray(d["buf"]), fs.buf.dtype)
            if buf.shape != fs.buf.shape:
                raise CheckpointIntegrityError(
                    f"snapshot channel {name!r} ring has shape "
                    f"{tuple(np.asarray(d['buf']).shape)}, this network "
                    f"allocates {tuple(fs.buf.shape)}; capacities (Eq. 1) "
                    "or token shapes differ")
            fifos.append(FifoState(
                buf=buf, rd=jnp.asarray(np.asarray(d["rd"]), jnp.int32),
                wr=jnp.asarray(np.asarray(d["wr"]), jnp.int32),
                occ=jnp.asarray(np.asarray(d["occ"]), jnp.int32)))
        actors = []
        for name, a in zip(template.actor_names, template.actors):
            if name not in payload["actors"]:
                raise CheckpointIntegrityError(
                    f"snapshot has no actor {name!r}; it was taken on a "
                    "different network")
            tmpl_leaves, treedef = jax.tree.flatten(a)
            saved = payload["actors"][name]
            if len(saved) != len(tmpl_leaves):
                raise CheckpointIntegrityError(
                    f"snapshot actor {name!r} carries {len(saved)} state "
                    f"leaves, this network expects {len(tmpl_leaves)}")
            leaves = []
            for tl, sl in zip(tmpl_leaves, saved):
                arr = jnp.asarray(np.asarray(sl), jnp.asarray(tl).dtype)
                if arr.shape != jnp.asarray(tl).shape:
                    raise CheckpointIntegrityError(
                        f"snapshot actor {name!r} leaf has shape "
                        f"{tuple(arr.shape)}, expected "
                        f"{tuple(jnp.asarray(tl).shape)}")
                leaves.append(arr)
            actors.append(jax.tree.unflatten(treedef, leaves))
        return dataclasses.replace(template, fifos=tuple(fifos),
                                   actors=tuple(actors))

    def resume_stream(self, checkpoint_dir: str, feeds: Mapping[str, Any],
                      on_fault: str = "raise", max_retries: int = 2,
                      checkpoint_every: int = 1) -> Dict[str, jax.Array]:
        """Continue an interrupted ``stream(checkpoint_dir=...)`` run.

        Call it on a freshly compiled program over the same network with
        the SAME feeds: the newest intact snapshot under
        ``checkpoint_dir`` restores the network state, fetched windows,
        fire counts, sweep total and trace ring, and the chunk loop
        continues at the first unfinished chunk.  The returned windows —
        and every piece of telemetry — are bit-identical to the
        uninterrupted run (Kahn determinism: each chunk is a pure
        function of the restored state and its feed slice).  Snapshots
        that fail their CRC (torn by the kill) are skipped in favor of
        the next-newest intact one.
        """
        with jax.profiler.TraceAnnotation(SPAN_PREPARE):
            arrays, total, chunk, n_chunks, slab_bytes, ring_bytes = \
                self._prepare_stream(feeds, on_fault, max_retries, False,
                                     checkpoint_dir, checkpoint_every)
        payload, meta, step = load_stream_checkpoint(checkpoint_dir)
        if meta.get("kind") != "stream":
            raise ValueError(
                f"resume_stream: {checkpoint_dir!r} holds a "
                f"{meta.get('kind')!r} checkpoint; those resume via "
                "Program.resume_run")
        if (int(meta["chunk_windows"]) != chunk
                or int(meta["total_windows"]) != total):
            raise ValueError(
                f"resume_stream: snapshot covers chunks of "
                f"{meta['chunk_windows']} windows over a "
                f"{meta['total_windows']}-window stream, but this program "
                f"streams {chunk}-window chunks over {total} windows; "
                "resume with the original plan and feeds")
        state = self._state_from_payload(payload["state"])
        outs: Dict[str, list] = {
            f: [jnp.asarray(w) for w in payload["outs"].get(f, [])]
            for f in self._fetch_by_fifo}
        counts = (dict(payload["fire_counts"])
                  if payload.get("fire_counts") is not None else None)
        traces = [_trace_from_payload(d) for d in payload.get("traces", [])]
        report: List[Dict[str, Any]] = []
        self.last_stream_report = report
        return self._stream_chunked(
            arrays, total, chunk, n_chunks, on_fault, max_retries,
            slab_bytes, ring_bytes, report, checkpoint_dir, checkpoint_every,
            start_chunk=int(meta["chunk"]), state=state, outs=outs,
            traces=traces, counts=counts, sweeps=int(payload.get("sweeps", 0)))

    # ------------------------------------------------------------------ #
    # Durable segmented runs: run() for quiescence graphs, checkpointed
    # every N sweeps so a killed process resumes bit-identically.
    # ------------------------------------------------------------------ #
    def _segment_program(self, every_sweeps: int) -> "Program":
        seg = self._segment_progs.get(every_sweeps)
        if seg is None:
            seg = Program(self.source_network,
                          dataclasses.replace(self.plan,
                                              max_sweeps=every_sweeps))
            self._segment_progs[every_sweeps] = seg
        return seg

    def _run_one_segment(self, seg_prog: "Program",
                         state: Any) -> Tuple[RunResult, bool]:
        """Run one bounded segment; returns (result, stalled).

        A segment that exhausts its sweep budget without quiescing is the
        NORMAL case mid-run, so the stall diagnostics a plain ``run()``
        would raise/warn about are re-read as "segment boundary" — but a
        segment that stalls with real fault flags set still raises.
        """
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                res = seg_prog.run(state)
        except NetworkFaultError as err:
            diag = err.diagnostics
            if (diag is not None and diag.stalled and not diag.faults
                    and getattr(err, "result", None) is not None):
                return err.result, True
            raise
        return res, bool(res.diagnostics.stalled
                         if res.diagnostics is not None else False)

    def run_checkpointed(self, checkpoint_dir: str, every_sweeps: int,
                         state: Optional[Any] = None,
                         keep: int = 3) -> RunResult:
        """``run()`` with durable progress snapshots every ``every_sweeps``.

        The run is split into bounded segments (a twin program with
        ``max_sweeps=every_sweeps``); after each segment the full
        :class:`NetworkState`, accumulated fire counts, sweep total and
        trace ring are committed to ``checkpoint_dir`` as a CRC'd,
        atomically-renamed snapshot.  After a process kill,
        :meth:`resume_run` continues from the newest intact snapshot and
        the final :class:`RunResult` is bit-identical to the
        uninterrupted run — each sweep is a deterministic function of the
        state, so cutting the run at sweep boundaries changes nothing but
        wall time.  Works at any ``devices=`` count (the sharded runner
        takes and returns host exit-merged states).

        Only modes that run to data-dependent quiescence segment
        meaningfully (``dynamic``, ``megakernel``); heterogeneous plans
        checkpoint through ``stream(checkpoint_dir=...)`` instead.
        """
        if self.plan.mode not in ("dynamic", "megakernel"):
            raise ValueError(
                f"Program.run_checkpointed: mode {self.plan.mode!r} runs a "
                "fixed iteration count, not to quiescence; checkpoint "
                "streams via stream(checkpoint_dir=...) instead")
        if self.plan.accelerated is not None:
            raise ValueError(
                "Program.run_checkpointed: heterogeneous plans execute via "
                "stream(); use stream(checkpoint_dir=...) for durability")
        if not isinstance(every_sweeps, int) or isinstance(every_sweeps, bool) \
                or every_sweeps < 1:
            raise ValueError(
                f"Program.run_checkpointed: every_sweeps must be an int "
                f">= 1, got {every_sweeps!r}")
        st = self.init_state() if state is None else state
        return self._run_segments(self._segment_program(every_sweeps), st,
                                  counts=None, sweeps_total=0, traces=[],
                                  segment=0, checkpoint_dir=checkpoint_dir,
                                  every_sweeps=every_sweeps, keep=keep)

    def _run_segments(self, seg_prog: "Program", st: Any,
                      counts: Optional[Dict[str, int]], sweeps_total: int,
                      traces: List[Trace], segment: int, checkpoint_dir: str,
                      every_sweeps: int, keep: int) -> RunResult:
        while True:
            res, stalled = self._run_one_segment(seg_prog, st)
            st = res.state
            if res.fire_counts is not None:
                if counts is None:
                    counts = {}
                for k, v in res.fire_counts.items():
                    counts[k] = counts.get(k, 0) + int(v)
            if res.sweeps is not None:
                sweeps_total += int(res.sweeps)
            if res.trace is not None:
                traces.append(res.trace)
            segment += 1
            done = not stalled
            over_budget = stalled and sweeps_total >= self.plan.max_sweeps
            payload = {
                "state": self._state_payload(
                    st if isinstance(st, NetworkState)
                    else self.network.state_from_dict(st)),
                "outs": {},
                "fire_counts": dict(counts) if counts is not None else None,
                "sweeps": int(sweeps_total),
                "traces": [_trace_to_payload(t) for t in traces],
            }
            meta = {"kind": "run", "segment": int(segment),
                    "every_sweeps": int(every_sweeps),
                    "done": bool(done or over_budget),
                    "mode": self.plan.mode,
                    "devices": int(self.plan.devices)}
            save_stream_checkpoint(checkpoint_dir, segment, payload, meta,
                                   keep=keep)
            if over_budget:
                # Mirror run()'s budget-exhausted contract on the FULL
                # budget (the segment budget is an implementation detail).
                if self.plan.guards and res.diagnostics is not None:
                    err = NetworkFaultError(res.diagnostics)
                    err.result = self._final_run_result(
                        st, counts, sweeps_total, traces, res)
                    raise err
                warnings.warn(
                    f"Program.run_checkpointed: stalled after "
                    f"{sweeps_total} sweeps (max_sweeps="
                    f"{self.plan.max_sweeps}) without quiescing",
                    RuntimeWarning, stacklevel=2)
                done = True
            if done:
                final = self._final_run_result(st, counts, sweeps_total,
                                               traces, res)
                self._last = final
                self._last_is_stream_chunk = False
                return final

    def _final_run_result(self, st: Any, counts: Optional[Dict[str, int]],
                          sweeps_total: int, traces: List[Trace],
                          res: RunResult) -> RunResult:
        return RunResult(
            state=st,
            fire_counts=dict(counts) if counts is not None else None,
            sweeps=sweeps_total if res.sweeps is not None else None,
            diagnostics=res.diagnostics,
            trace=merge_traces(traces) if traces else None)

    def resume_run(self, checkpoint_dir: str, keep: int = 3) -> RunResult:
        """Continue (or recover the result of) a ``run_checkpointed``.

        Loads the newest intact snapshot under ``checkpoint_dir``: if the
        run had already quiesced (``done``), the final
        :class:`RunResult` is reconstructed from the snapshot; otherwise
        the segment loop continues until quiescence.  Either way the
        result is bit-identical to the uninterrupted run.
        """
        payload, meta, step = load_stream_checkpoint(checkpoint_dir)
        if meta.get("kind") != "run":
            raise ValueError(
                f"resume_run: {checkpoint_dir!r} holds a "
                f"{meta.get('kind')!r} checkpoint; those resume via "
                "Program.resume_stream")
        st = self._state_from_payload(payload["state"])
        counts = (dict(payload["fire_counts"])
                  if payload.get("fire_counts") is not None else None)
        sweeps_total = int(payload.get("sweeps", 0))
        traces = [_trace_from_payload(d) for d in payload.get("traces", [])]
        if meta.get("done"):
            final = RunResult(
                state=st,
                fire_counts=dict(counts) if counts is not None else None,
                sweeps=sweeps_total if sweeps_total else None,
                diagnostics=None,
                trace=merge_traces(traces) if traces else None)
            self._last = final
            self._last_is_stream_chunk = False
            return final
        return self._run_segments(
            self._segment_program(int(meta["every_sweeps"])), st,
            counts=counts, sweeps_total=sweeps_total, traces=traces,
            segment=int(meta["segment"]), checkpoint_dir=checkpoint_dir,
            every_sweeps=int(meta["every_sweeps"]), keep=keep)

    # ------------------------------------------------------------------ #
    def stats(self) -> ProgramStats:
        """Sweep counts, buffer bytes and the per-actor FLOP roofline."""
        net = self.network
        flops: Dict[str, int] = {}
        byts: Dict[str, int] = {}
        for name, a in net.actors.items():
            flops[name] = int(a.cost_flops)
            moved = sum(spec.rate * spec.token_size_bytes
                        for _, spec, _ in net.in_port_specs[name])
            moved += sum(spec.rate * spec.token_size_bytes
                         for _, spec, _ in net.out_port_specs[name])
            ctl = net.control_specs[name]
            if ctl is not None:
                moved += ctl[0].token_size_bytes
            byts[name] = int(moved)
        intensity = {n: (flops[n] / byts[n] if byts[n] else 0.0)
                     for n in net.actors}
        last = self._last
        scratch = transient = hbm = None
        forwarded = reclaimed = None
        grid_cores = part_actors = core_bytes = None
        shared_bytes = shared_names = part_counts = None
        cursor_split = cut_obj = None
        if self._layout is not None:
            from repro.core.megakernel import state_hbm_bytes
            scratch = self._layout.scratch_bytes
            transient = self._layout.transient_scratch_bytes
            if last is not None:
                # State pytree (rings, cursors, actor states) plus the
                # hoisted closure arrays — every HBM operand the kernel
                # touches.
                hbm = (state_hbm_bytes(last.state)
                       + getattr(self._runners[False],
                                 "hoisted_const_bytes", 0))
            part = self._partition
            if part is not None:
                names = tuple(net.actors)
                # Effective scratch under this partition's forwarding set:
                # the layout's no-forwarding footprint minus the rings
                # transient forwarding turned into loop-carried windows.
                scratch = part.scratch_bytes(self._layout)
                forwarded = tuple(self._layout.fifo_names[i]
                                  for i in part.forwarded_fifos)
                reclaimed = part.reclaimed_ring_bytes(self._layout)
                grid_cores = part.n_cores
                part_actors = tuple(
                    tuple(names[i] for i in rows) for rows in part.core_rows)
                core_bytes = part.private_ring_bytes(self._layout)
                shared_bytes = (part.shared_ring_bytes(self._layout)
                                + part.semaphore_bytes())
                shared_names = tuple(self._layout.fifo_names[i]
                                     for i in part.shared_fifos)
                cursor_split = part.core_cursor_rows
                cut_obj = part.objective
                if last is not None and last.fire_counts is not None:
                    part_counts = tuple(
                        sum(int(last.fire_counts[names[i]]) for i in rows)
                        for rows in part.core_rows)
        dev_actors = coll_bytes = allreduces = None
        if self._shard_partition is not None:
            from repro.core.shard import collective_bytes_per_sweep
            names = tuple(net.actors)
            dev_actors = tuple(
                tuple(names[i] for i in rows)
                for rows in self._shard_partition.core_rows)
            coll_bytes = collective_bytes_per_sweep(
                self._shard_layout, self._shard_partition)
            if last is not None and last.sweeps is not None:
                allreduces = int(last.sweeps)
        return ProgramStats(
            mode=self.plan.mode,
            n_actors=len(net.actors),
            n_fifos=len(net.fifos),
            buffer_bytes=net.buffer_bytes(),
            register_fifos=tuple(sorted(net.register_fifos)),
            iteration_flops=iteration_token_flops(net),
            actor_flops=flops,
            actor_window_bytes=byts,
            actor_intensity=intensity,
            last_sweeps=(int(last.sweeps) if last is not None
                         and last.sweeps is not None else None),
            last_fire_counts=({k: int(v) for k, v in last.fire_counts.items()}
                              if last is not None
                              and last.fire_counts is not None else None),
            resolved_donate=self.donate,
            resolved_donate_threshold=self._donate_threshold(self.plan),
            scratch_bytes=scratch,
            transient_scratch_bytes=transient,
            forwarded_fifos=forwarded,
            reclaimed_scratch_bytes=reclaimed,
            hbm_state_bytes=hbm,
            grid_cores=grid_cores,
            partition_actors=part_actors,
            core_scratch_bytes=core_bytes,
            shared_scratch_bytes=shared_bytes,
            shared_fifos=shared_names,
            core_cursor_rows=cursor_split,
            cut_objective=cut_obj,
            partition_fire_counts=part_counts,
            last_stream_chunks=(self._last_stream["chunks"]
                                if self._last_stream else None),
            last_stream_persistent=(self._last_stream["persistent"]
                                    if self._last_stream else None),
            last_stream_staged_bytes_per_chunk=(
                self._last_stream["staged_bytes_per_chunk"]
                if self._last_stream else None),
            last_stream_total_staged_bytes=(
                self._last_stream["total_staged_bytes"]
                if self._last_stream else None),
            devices=self.plan.devices,
            device_partition_actors=dev_actors,
            collective_bytes_per_sweep=coll_bytes,
            quiescence_allreduces=allreduces,
        )
