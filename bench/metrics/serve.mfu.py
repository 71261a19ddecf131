"""Model operations of the traced calls' requests (every prompt token and
every generated token, no padding; ``bench.lib.work.lm_request_flops``) per
second of the traced window, as a share of the chip's bf16 peak."""
from bench.lib import work


def read(obs):
    if obs["peaks"] is None or not obs["calls"]:
        return None
    flops = sum(work.lm_request_flops(obs["model"], p, g)
                for r in obs["calls"]
                for p, g in zip(r["prompt_lens"], r["gen_lens"]))
    return 100.0 * flops / obs["window_s"] / obs["peaks"]["bf16_flops_per_s"]
