"""Executor sweeps per generated token (``ActorEngine.last_sweeps``)."""


def read(obs):
    tokens = sum(r["tokens"] for r in obs["calls"])
    return sum(r["sweeps"] for r in obs["calls"]) / tokens if tokens else None
