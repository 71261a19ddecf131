"""The program's own host spans in a reduced trace (``TraceSummary.host``).

``Program.stream``, ``Program.run`` and ``ActorEngine.generate`` open host
spans (``jax.profiler.TraceAnnotation``) named by ``repro.core.trace``'s
``SPAN_*`` constants; the names are repeated here so that the benchmark
reads a program that lacks them (it then finds nothing, and every reader
returns None).  From them:

* idle time by program phase: each idle gap of the device charged to the
  innermost program span open at its middle, runtime events ignored;
* counts of the runtime's own host events (executable launches,
  device-to-host reads) that start inside a stream call.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional

from bench.lib import trace_reduce

STREAM = "Program.stream"
PREPARE = "Program.stream/prepare"
STAGE = "Program.stream/stage"
LAUNCH = "Program.run/launch"
WAIT = "Program.run/wait"
DECODE = "Program.run/decode"
COLLECT = "Program.stream/collect"
BUILD = "ActorEngine.generate/build"
PROGRAM = (STREAM, PREPARE, STAGE, LAUNCH, WAIT, DECODE, COLLECT)
#: Phases in which the host works (``WAIT`` is the host blocked on the
#: device, so idle time there is the device's own).
HOST_PHASES = (PREPARE, STAGE, LAUNCH, DECODE, COLLECT)

#: Runtime host events: an executable launch and a blocking read of a
#: device buffer (TPU runtime names).
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"
READ_EVENT = "tpu::System::TransferFromDevice"


def named(summary: trace_reduce.TraceSummary, name: str
          ) -> List[trace_reduce.Event]:
    return [e for e in summary.host if e.name == name]


def idle_by_phase(summary: trace_reduce.TraceSummary) -> Dict[str, float]:
    """Seconds of device idle time charged to each program span that was
    the innermost open at the gap's middle (``"no host span"`` outside
    every program span); empty when the trace holds no program span."""
    prog = [e for e in summary.host if e.name in PROGRAM]
    if not prog:
        return {}
    gaps = summary.gaps()
    names = trace_reduce.host_activity(prog, [(g0 + g1) / 2
                                              for g0, g1 in gaps])
    idle: Dict[str, float] = defaultdict(float)
    for (g0, g1), name in zip(gaps, names):
        idle[name] += (g1 - g0) * 1e-9
    return dict(idle)


def per_stream_call(summary: trace_reduce.TraceSummary, event: str
                    ) -> Optional[float]:
    """Host events named ``event`` that start inside a ``Program.stream``
    span, per span; None without such spans or such events."""
    calls = sorted(named(summary, STREAM), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in calls]
    n = 0
    for e in named(summary, event):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        n += i >= 0 and e.start_ns < calls[i].end_ns
    return n / len(calls) if calls and n else None
