"""Seconds per ``generate`` call that JAX spent tracing, lowering and
compiling, from its own monitoring events in the traced window (whose
compiles bypass the persistent cache)."""


def read(obs):
    calls = obs["calls"]
    return sum(obs["compile"].values()) / len(calls) if calls else None
