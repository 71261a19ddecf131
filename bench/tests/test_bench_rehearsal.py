"""Every committed cell, end to end on the CPU at smoke sizes: set-up, a
short window (measured, then traced), the comparison with the reference,
and the result line's shape."""
import json
import time

import pytest

from bench import harness
from bench.tests import smoke

CELLS = [w["name"] for w in harness.load_spec()["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload, trace):
    cell = smoke.cell(workload)
    line = harness.run(cell, 2**31 + 7, 0.5, bool(trace),
                       time.perf_counter(), require_chip=False)
    json.dumps(line)
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"], name
    if trace:
        assert set(line["metrics"]) <= set(cell.per_layer)
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > 0
        for part in ("device_ops", "idle_gaps"):
            assert 0 < len(line["breakdown"][part]) <= 10
    else:
        assert set(line["metrics"]) == set(cell.end_to_end)
        assert "setup_s" in line["metrics"]
