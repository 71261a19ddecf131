"""Seconds per ``generate`` call spent in ``ActorEngine.generate/build``
spans: staging the requests, building the serving network and compiling
it under the plan (JAX's trace, lower and compile come later, at the
run's launch)."""
from bench.lib import spans


def read(obs):
    builds = spans.named(obs["trace"], spans.BUILD)
    if not builds or not obs["calls"]:
        return None
    return sum(e.seconds for e in builds) / len(obs["calls"])
