"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run holds: the
configuration and mix files with their sizes shrunk, every other key as
committed."""
from __future__ import annotations

import json
import os

from bench import harness

# The smoke LM keeps the full model's depth and logit scale (init std x
# sqrt(width) = 0.02 x sqrt(3840)): the comparison's readings grow with
# depth, so at the published 24 layers the program, its control and a
# wrong token read about as they do at full width.
LM_SMOKE = dict(num_hidden_layers=24, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=503, initializer_range=0.155)


def _config(workload: str) -> dict:
    spec = harness.load_spec()
    w = {c["name"]: c for c in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(harness.ROOT, conf["file"])) as f:
        return json.load(f)


def cell(workload: str) -> harness.Cell:
    full = harness.resolve(harness.load_spec(), workload)
    c = _config(workload)
    mix = dict(full.traffic)
    if c["driver"] == "stream":
        c.update(block_l=1024, reconf_period_samples=2048)
        c["plan"] = dict(c["plan"], n_iterations=2)
        mix.update(windows_per_call=8, signal_pool=3)
    else:
        c.update(LM_SMOKE)
        c["serve"] = dict(c["serve"], batch_size=4, max_prompt=16, max_new=8)
        p = mix["prompt_len"]
        # A fixed length fills the prompt window at full size, so here too.
        prompt = (dict(median=16, sigma=0.0, lo=16, hi=16) if p["lo"] == p["hi"]
                  else dict(median=8, sigma=p["sigma"], lo=3, hi=16))
        mix.update(requests_per_call=6, prompt_len=prompt,
                   budget=dict(median=4, sigma=0.7, lo=2, hi=8))
    return harness.Cell(workload, full.chips, c, mix, full.end_to_end,
                        full.per_layer, full.units)


def workloads(driver: str):
    """The committed cells that ``driver`` runs."""
    return [w["name"] for w in harness.load_spec()["workloads"]
            if _config(w["name"])["driver"] == driver]

