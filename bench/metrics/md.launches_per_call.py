"""Executable launches (``PJRT_LoadedExecutable_Execute`` host events)
that start inside a ``Program.stream`` span, per traced call."""
from bench.lib import spans


def read(obs):
    return spans.per_stream_call(obs["trace"], spans.LAUNCH_EVENT)
