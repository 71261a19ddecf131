"""Executor sweeps per ``Program.stream`` call (``last_stream_sweeps``)."""


def read(obs):
    calls = obs["calls"]
    return sum(r["sweeps"] for r in calls) / len(calls) if calls else None
