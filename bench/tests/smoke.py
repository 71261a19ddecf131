"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run holds: the
configuration and mix files with their sizes shrunk by the cell's driver
(its ``smoke``), every other key as committed."""
from __future__ import annotations

import json
import os

from bench import harness


def _config(workload: str) -> dict:
    spec = harness.load_spec()
    w = {c["name"]: c for c in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(harness.ROOT, conf["file"])) as f:
        return json.load(f)


def driver(cell: harness.Cell):
    """The driver module that runs ``cell``."""
    return harness.load_module("drivers", cell.config["driver"])


def cell(workload: str) -> harness.Cell:
    full = harness.resolve(harness.load_spec(), workload)
    c, mix = driver(full).smoke(_config(workload), dict(full.traffic))
    return harness.Cell(workload, full.chips, c, mix, full.end_to_end,
                        full.per_layer, full.units)
