"""Share of the roofline that the whole stream reaches: the least time the
chip needs for the traced calls' useful work (every active actor firing,
``bench.lib.work.dpd_window``) over the traced window."""
from bench.lib import work


def read(obs):
    if obs["peaks"] is None or not obs["calls"]:
        return None
    n = len(obs["calls"])
    return 100.0 * work.least_s(obs["call_flops"] * n, obs["call_bytes"] * n,
                                obs["peaks"]) / obs["window_s"]
