"""A run whose timed path is broken underneath comes out not correct: an
answer altered where the program produces it (the driver's ``corrupt``:
one output value of a stream call; the last token of every served
request)."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_spec()["workloads"]])
def test_altered_answer_is_not_correct(workload, monkeypatch):
    cell = smoke.cell(workload)
    smoke.driver(cell).corrupt(monkeypatch)
    line = harness.run(cell, 2**31 + 11, 0.3, False, time.perf_counter(),
                       require_chip=False)
    assert line["correct"] is False
    assert all(np.isfinite(c["value"]) for c in line["checks"].values())
