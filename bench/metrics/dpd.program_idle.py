"""Share of the traced window in which the device was idle while the
innermost open program span was host work (``Program.stream/prepare``,
``/stage``, ``/collect``, ``Program.run/launch``, ``/decode``; not
``Program.run/wait``), from ``bench.lib.spans.idle_by_phase``."""
from bench.lib import spans


def read(obs):
    tr = obs["trace"]
    idle = spans.idle_by_phase(tr)
    if not idle:
        return None
    return 100.0 * sum(idle.get(p, 0.0) for p in spans.HOST_PHASES) \
        / tr.window_s
