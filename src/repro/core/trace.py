"""Firing-level event tracing and occupancy profiling.

The runtime's aggregate telemetry (``fire_counts``, ``sweeps``, fault
high-water marks) says *what* happened but not *when*: which actors fired
in which sweep, which channels saturated, where a grid core idled waiting
on a crossing FIFO.  This module adds the missing timeline layer, shared
by both dynamic backends:

  * the **host dynamic executor** records one event per firing *attempt*
    (actor index, sweep number, fired-or-skipped, per-channel occupancy
    sampled after the attempt) into a loop-carried :class:`TraceState`;
  * the **megakernel** writes the same rows into a fixed-capacity
    device-side trace ring — an extra output ref threaded through the
    sweep loop exactly like the PR 6 fault refs, so ``trace=False``
    contributes an empty pytree and bit-identical HLO.

Capacity is fixed at compile time (``ExecutionPlan(trace_capacity=...)``,
default :data:`TRACE_CAPACITY_DEFAULT`); when the run outgrows it the
ring wraps and the *oldest* events are dropped, with the drop count
surfaced on the decoded :class:`Trace`.

On host, :func:`decode_trace` unwraps the ring into a :class:`Trace`:

  * ``trace.to_perfetto(path)`` exports Chrome trace-event JSON — one
    thread track per actor (grouped per core under grid partitioning),
    an occupancy counter track per channel — viewable in Perfetto
    (https://ui.perfetto.dev) or chrome://tracing;
  * ``trace.profile()`` derives a :class:`Profile`: per-actor firing
    counts and per-channel occupancy churn (the ring has no clock, so
    it measures counts and order, never time);
  * ``Profile.as_cut_weights()`` feeds
    ``ExecutionPlan(cut_objective="profile", profile=...)`` so the grid
    partition cut uses *measured* churn instead of static capacity
    bytes — the measurement half of the ROADMAP autotuner.

Time comes from the profiler instead: :class:`repro.core.program.Program`
and :class:`repro.serve.ActorEngine` open host spans
(``jax.profiler.TraceAnnotation``) named by the ``SPAN_*`` constants
below, which a profiler trace puts on the same clock as the device's
operations.  With no profiler running each span costs one inactive check.

Event rows are int32 vectors ``[actor_index, sweep, fired,
occ_0..occ_{F-1}]`` (width ``3 + n_fifos``); the column offsets are the
module constants ``COL_ACTOR`` / ``COL_SWEEP`` / ``COL_FIRED`` /
``COL_OCC``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Default trace-ring capacity in events (one event per firing attempt).
TRACE_CAPACITY_DEFAULT = 4096

# Event-row column layout (int32): [actor, sweep, fired, occ_0..occ_{F-1}].
COL_ACTOR, COL_SWEEP, COL_FIRED, COL_OCC = 0, 1, 2, 3

# Host spans of the program, in the profiler's trace.  ``Program.run``
# spans nest in a stream's chunk; ``Program.run/wait`` is the host blocked
# on the device, every other phase is host work.
SPAN_STREAM = "Program.stream"              # a whole stream call
SPAN_PREPARE = "Program.stream/prepare"     # validation, feeds to device
SPAN_STAGE = "Program.stream/stage"         # a chunk's state and feeds
SPAN_LAUNCH = "Program.run/launch"          # dispatch (and any compile)
SPAN_WAIT = "Program.run/wait"              # the run's one host sync
SPAN_DECODE = "Program.run/decode"          # health and trace-ring decode
SPAN_COLLECT = "Program.stream/collect"     # counts, outputs, snapshots
SPAN_BUILD = "ActorEngine.generate/build"   # request staging, net, compile


# --------------------------------------------------------------------------- #
# Device-side state (loop-carried on the dynamic executor, an output ref
# pair on the megakernel).
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TraceState:
    """Fixed-capacity event ring + monotonic attempt counter.

    ``ring`` is ``(capacity, 3 + n_fifos)`` int32; ``count`` is the total
    number of events ever recorded (so ``count > capacity`` means the
    ring wrapped and the oldest ``count - capacity`` events are gone).
    """

    ring: jax.Array
    count: jax.Array

    def record(self, actor_index, sweep, fired, occs) -> "TraceState":
        """Append one event row (functional; wraps when full)."""
        row = jnp.concatenate([
            jnp.stack([jnp.asarray(actor_index, jnp.int32),
                       jnp.asarray(sweep, jnp.int32),
                       jnp.asarray(fired, jnp.int32)]),
            jnp.asarray(occs, jnp.int32),
        ])
        slot = self.count % self.ring.shape[0]
        return TraceState(ring=self.ring.at[slot].set(row),
                          count=self.count + 1)


def init_trace(n_fifos: int, capacity: int = TRACE_CAPACITY_DEFAULT
               ) -> TraceState:
    """Empty trace ring for a network with ``n_fifos`` channels."""
    return TraceState(
        ring=jnp.zeros((int(capacity), COL_OCC + int(n_fifos)), jnp.int32),
        count=jnp.int32(0))


# --------------------------------------------------------------------------- #
# Host-side decoded trace.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Trace:
    """Chronologically ordered firing events, decoded on host."""

    actor_names: Tuple[str, ...]
    fifo_names: Tuple[str, ...]
    #: ``(n_events, 3 + n_fifos)`` int32 — see the COL_* constants.
    events: np.ndarray
    capacity: int
    dropped: int = 0
    #: Static per-actor cost estimates (flops), for the cut weights.
    actor_flops: Tuple[int, ...] = ()
    #: Per-channel token sizes (bytes), for churn-in-bytes profiles.
    fifo_token_bytes: Tuple[int, ...] = ()
    #: Core index per actor under grid partitioning (None = single core).
    actor_cores: Optional[Tuple[int, ...]] = None

    @property
    def n_events(self) -> int:
        return int(self.events.shape[0])

    def firing_counts(self) -> Dict[str, int]:
        """Events with ``fired == 1`` per actor (drops excluded)."""
        fired = self.events[self.events[:, COL_FIRED] == 1, COL_ACTOR]
        return {nm: int((fired == i).sum())
                for i, nm in enumerate(self.actor_names)}

    def attempt_counts(self) -> Dict[str, int]:
        """All recorded attempts (fired + skipped) per actor."""
        return {nm: int((self.events[:, COL_ACTOR] == i).sum())
                for i, nm in enumerate(self.actor_names)}

    def occupancy(self, fifo: str) -> np.ndarray:
        """The sampled occupancy series of one channel, in event order."""
        return self.events[:, COL_OCC + self.fifo_names.index(fifo)]

    def extend(self, other: "Trace") -> "Trace":
        """Concatenate a later chunk's trace onto this one (stream use):
        the other trace's sweep numbers are offset past this trace's
        last sweep so the merged timeline stays monotonic."""
        if (other.actor_names != self.actor_names
                or other.fifo_names != self.fifo_names):
            raise ValueError("Trace.extend: traces come from different "
                             "networks")
        offset = (int(self.events[:, COL_SWEEP].max()) + 1
                  if self.n_events else 0)
        ev = other.events.copy()
        ev[:, COL_SWEEP] += offset
        return dataclasses.replace(
            self, events=np.concatenate([self.events, ev], axis=0),
            dropped=self.dropped + other.dropped)

    # ------------------------------------------------------------------ #
    def profile(self) -> "Profile":
        """Derive per-actor firing counts and per-channel churn."""
        firings = self.firing_counts()
        flops = {nm: max(1, int(f)) for nm, f in
                 zip(self.actor_names, self.actor_flops or
                     (1,) * len(self.actor_names))}
        # Occupancy churn: total |delta occ| between consecutive samples,
        # scaled to bytes by the channel token size — a measured stand-in
        # for "traffic through this channel" that a crossing cut wants to
        # keep inside one core.
        tok_bytes = (self.fifo_token_bytes or
                     (1,) * len(self.fifo_names))
        churn: Dict[str, int] = {}
        for i, nm in enumerate(self.fifo_names):
            occ = self.events[:, COL_OCC + i].astype(np.int64)
            delta = int(np.abs(np.diff(occ)).sum()) if len(occ) > 1 else 0
            churn[nm] = delta * max(1, int(tok_bytes[i]))
        return Profile(actor_names=self.actor_names,
                       firing_counts=firings, actor_flops=flops,
                       channel_churn_bytes=churn, dropped=self.dropped)

    # ------------------------------------------------------------------ #
    def to_perfetto(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (Perfetto / chrome://tracing).

        One thread track per actor — named ``actor [core k]`` under grid
        partitioning — plus one counter track per channel (``occ:name``,
        emitted on change).  Fired attempts are complete ("X") slices,
        skipped attempts thread-scoped instants ("i").  Timestamps are
        event ordinals, one microsecond per attempt: the ring records
        order, not time.
        """
        n = self.n_events
        ev: List[dict] = [{"name": "process_name", "ph": "M", "pid": 0,
                           "tid": 0, "args": {"name": "actor network"}}]
        for i, nm in enumerate(self.actor_names):
            label = nm
            if self.actor_cores is not None:
                label = f"{nm} [core {self.actor_cores[i]}]"
            ev.append({"name": "thread_name", "ph": "M", "pid": 0,
                       "tid": i + 1, "args": {"name": label}})
        prev_occ: Dict[str, int] = {}
        for k in range(n):
            row = self.events[k]
            ts = float(k)
            a = int(row[COL_ACTOR])
            base = {"cat": "firing", "pid": 0, "tid": a + 1, "ts": ts,
                    "args": {"sweep": int(row[COL_SWEEP])}}
            if int(row[COL_FIRED]):
                ev.append({"name": self.actor_names[a], "ph": "X",
                           "dur": 1.0, **base})
            else:
                ev.append({"name": f"{self.actor_names[a]} (skipped)",
                           "ph": "i", "s": "t", **base})
            for i, fnm in enumerate(self.fifo_names):
                occ = int(row[COL_OCC + i])
                if prev_occ.get(fnm) != occ:
                    prev_occ[fnm] = occ
                    ev.append({"name": f"occ:{fnm}", "ph": "C", "pid": 0,
                               "ts": ts, "args": {"tokens": occ}})
        doc = {"traceEvents": ev, "displayTimeUnit": "ms",
               "otherData": {"dropped_events": self.dropped,
                             "capacity": self.capacity}}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


def merge_traces(traces: Sequence[Trace]) -> Optional[Trace]:
    """Fold per-chunk traces into one stream-long trace (sweep offsets
    applied chunk by chunk); None when the sequence is empty."""
    traces = [t for t in traces if t is not None]
    if not traces:
        return None
    out = traces[0]
    for t in traces[1:]:
        out = out.extend(t)
    return out


def merge_device_traces(traces: Sequence[Trace]) -> Optional[Trace]:
    """Interleave the per-device traces of ONE sharded run
    (``ExecutionPlan(devices=k)``) into a single timeline.

    Unlike :func:`merge_traces`, sweep numbers are NOT offset — the
    devices ran the same barrier rounds concurrently, so round ``r``
    means the same instant everywhere; events are stable-sorted by
    round (device order breaks ties) and drop counts summed.  Each
    device records only its own actors' attempts, so the merged
    ``firing_counts`` are exact; occupancy samples keep each recording
    device's local (conservative-between-barriers) view.
    """
    traces = [t for t in traces if t is not None]
    if not traces:
        return None
    first = traces[0]
    for t in traces[1:]:
        if (t.actor_names != first.actor_names
                or t.fifo_names != first.fifo_names):
            raise ValueError("merge_device_traces: traces come from "
                             "different networks")
    events = np.concatenate([t.events for t in traces], axis=0)
    order = np.argsort(events[:, COL_SWEEP], kind="stable")
    return dataclasses.replace(
        first, events=events[order],
        dropped=sum(t.dropped for t in traces))


# --------------------------------------------------------------------------- #
# Derived profile -> partition weights.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Profile:
    """Measured per-actor firings and per-channel occupancy churn."""

    actor_names: Tuple[str, ...]
    firing_counts: Dict[str, int]
    actor_flops: Dict[str, int]
    channel_churn_bytes: Dict[str, int]
    dropped: int = 0

    def as_cut_weights(self) -> Dict[str, Dict[str, int]]:
        """Integer weights for ``cut_objective="profile"``: per-actor
        load (firings x flops, floor 1 so unfired actors keep a seat)
        and per-channel measured churn in bytes."""
        actors = {nm: max(1, self.firing_counts.get(nm, 0)
                          * self.actor_flops.get(nm, 1))
                  for nm in self.actor_names}
        return {"actors": actors,
                "channels": dict(self.channel_churn_bytes)}


# --------------------------------------------------------------------------- #
# Decode (device pytree -> host Trace).
# --------------------------------------------------------------------------- #
def decode_trace(network, trace: Optional[TraceState],
                 actor_cores: Optional[Mapping[str, int]] = None
                 ) -> Optional[Trace]:
    """Unwrap a device trace ring into a chronological :class:`Trace`."""
    if trace is None:
        return None
    ring = np.asarray(trace.ring)
    total = int(trace.count)
    cap = int(ring.shape[0])
    if total <= cap:
        events = ring[:total].copy()
    else:
        s = total % cap
        events = np.concatenate([ring[s:], ring[:s]], axis=0)
    actor_names = tuple(network.actors)
    fifo_names = tuple(network.fifos)
    flops = tuple(max(1, int(getattr(a, "cost_flops", 1) or 1))
                  for a in network.actors.values())
    tok_bytes = tuple(int(spec.token_size_bytes)
                      for spec in network.fifos.values())
    cores = None
    if actor_cores is not None:
        cores = tuple(int(actor_cores.get(nm, 0)) for nm in actor_names)
    return Trace(actor_names=actor_names, fifo_names=fifo_names,
                 events=events, capacity=cap,
                 dropped=max(0, total - cap),
                 actor_flops=flops, fifo_token_bytes=tok_bytes,
                 actor_cores=cores)


# --------------------------------------------------------------------------- #
# Chrome trace-event schema validation (used by the CI trace job).
# --------------------------------------------------------------------------- #
_REQUIRED_KEYS = {
    "M": ("name", "ph", "pid", "args"),
    "X": ("name", "ph", "pid", "tid", "ts", "dur"),
    "i": ("name", "ph", "pid", "tid", "ts", "s"),
    "C": ("name", "ph", "pid", "ts", "args"),
}


def validate_chrome_trace(doc: Any) -> List[str]:
    """Validate a Chrome trace-event document; returns a list of problem
    strings (empty == valid).  Checks the JSON object format, per-phase
    required keys, and non-decreasing timestamps per track (thread
    tracks keyed by (pid, tid); counter tracks by (pid, name))."""
    problems: List[str] = []
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return ["JSON-object format: 'traceEvents' missing or not a "
                    "list"]
    elif isinstance(doc, list):
        events = doc
    else:
        return [f"document is {type(doc).__name__}, expected dict or list"]
    last_ts: Dict[tuple, float] = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = e.get("ph")
        if ph is None:
            problems.append(f"event {i}: missing 'ph'")
            continue
        required = _REQUIRED_KEYS.get(ph)
        if required is None:
            # Other phases (B/E/b/e/s/f/...) are legal Chrome events;
            # the exporter here only emits M/X/i/C, so just sanity-check.
            required = ("name", "ph")
        missing = [k for k in required if k not in e]
        if missing:
            problems.append(f"event {i} (ph={ph!r}): missing keys "
                            f"{missing}")
            continue
        if ph == "M":
            continue
        if "ts" in e:
            key = ((e["pid"], "C", e["name"]) if ph == "C"
                   else (e["pid"], e.get("tid")))
            ts = float(e["ts"])
            if ts < last_ts.get(key, float("-inf")):
                problems.append(
                    f"event {i} (ph={ph!r}, track {key}): ts {ts} goes "
                    f"backwards (prev {last_ts[key]})")
            last_ts[key] = ts
    return problems
