"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

The traced window is the host span named ``WINDOW`` that the harness opens
around it.  Device operations are the events of each ``/device:TPU:<n>``
plane's ``XLA Ops`` line (on a backend with no device plane, the host
events that carry an ``hlo_op`` stat: the CPU rehearsal).  From them:

* busy time: the union of the operations' intervals inside the window,
  averaged over the devices; idle share is 1 - busy / window;
* kernel time: the summed durations of the operations a predicate picks;
* idle gaps: the stretches of the window in which no operation ran on a
  device, each charged to the innermost host span open at its middle;
* ``breakdown``: the ten operations (by HLO instruction name) that took
  most time and the ten host spans that idle time was charged to most, as
  ``[name, seconds]``.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HLO_NAME = re.compile(r"^%(\S+) = ")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclass
class TraceSummary:
    t0_ns: float
    t1_ns: float
    devices: List[List[Event]]          # leaf operations, per device
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(union_ns(d) for d in self.devices) * 1e-9 / len(
            self.devices)

    def kernel_s(self, pick: Callable[[Event], bool]) -> float:
        """Summed durations of the picked operations, over all devices."""
        return sum(e.seconds for d in self.devices for e in d if pick(e))

    def gaps(self) -> List[Tuple[float, float]]:
        """Stretches of the window with no operation on the first device."""
        return idle_gaps(self.devices[0] if self.devices else [],
                         self.t0_ns, self.t1_ns)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for e in d:
                ops[op_name(e)] += e.seconds / len(self.devices)
        idle: Dict[str, float] = defaultdict(float)
        gaps = self.gaps()
        names = host_activity(self.host, [(g0 + g1) / 2 for g0, g1 in gaps])
        for (g0, g1), name in zip(gaps, names):
            idle[name] += (g1 - g0) * 1e-9
        return {"device_ops": _top(ops, top), "idle_gaps": _top(idle, top)}


def op_name(e: Event) -> str:
    """The HLO instruction's name (``fusion.12``) of a device operation,
    whose event name is the instruction's text (``%fusion.12 = ...``)."""
    m = _HLO_NAME.match(e.name)
    return m.group(1) if m else e.name


def _top(d: Dict[str, float], n: int) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def union_ns(events: List[Event]) -> float:
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.end_ns <= end:
            continue
        total += e.end_ns - max(e.start_ns, end)
        end = e.end_ns
    return total


def idle_gaps(events: List[Event], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    gaps, end = [], t0
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = max(end, e.end_ns)
    if t1 > end:
        gaps.append((end, t1))
    return gaps


def host_activity(host: List[Event], times: List[float]) -> List[str]:
    """For each time (ascending), the name of the innermost host span open
    then: the latest-starting one that has not ended."""
    spans = sorted((e for e in host if e.end_ns > e.start_ns
                    and e.name != WINDOW), key=lambda e: e.start_ns)
    heap: List[Tuple[float, int]] = []
    out, i = [], 0
    for t in times:
        while i < len(spans) and spans[i].start_ns <= t:
            heapq.heappush(heap, (-spans[i].start_ns, i))
            i += 1
        while heap and spans[heap[0][1]].end_ns <= t:
            heapq.heappop(heap)
        out.append(spans[heap[0][1]].name if heap else "no host span")
    return out


def _leaves(events: List[Event]) -> List[Event]:
    """Drop events that enclose a later event on the same line (a ``while``
    around its body): each stretch of device time is counted once, by the
    operation that ran."""
    ev = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt.start_ns < e.end_ns \
                and nxt.end_ns <= e.end_ns:
            continue
        out.append(e)
    return out


def _clip(e, t0: float, t1: float, stats: bool) -> Optional[Event]:
    """The event's part inside [t0, t1], or None; ``stats`` keeps its
    stats (which tell the CPU backend's compiled ops from other spans)."""
    s, t = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
    if t < s or (t == s and e.duration_ns > 0):
        return None
    return Event(e.name, s, t, tuple(e.stats) if stats else ())


def find_xspace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def summarize(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` and reduce it to the traced window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_plane = pd.find_plane_with_name("/host:CPU")
    host_raw = [e for line in (host_plane.lines if host_plane else [])
                for e in line.events]
    wins = [e for e in host_raw if e.name == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found {len(wins)}")
    t0, t1 = wins[0].start_ns, wins[0].start_ns + wins[0].duration_ns
    devices = []
    for plane in pd.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        evs = [_clip(e, t0, t1, False) for ln in plane.lines
               if ln.name == "XLA Ops" for e in ln.events]
        devices.append(_leaves([e for e in evs if e is not None]))
    cpu_backend = not devices
    host = [e for e in (_clip(e, t0, t1, cpu_backend) for e in host_raw)
            if e is not None]
    if cpu_backend:
        # No device plane: the host events of compiled ops stand in.
        compiled = [any(k == "hlo_op" for k, _ in e.stats) for e in host]
        devices = [_leaves([e for e, c in zip(host, compiled) if c])]
        host = [e for e, c in zip(host, compiled) if not c]
    return TraceSummary(t0, t1, devices, host)
