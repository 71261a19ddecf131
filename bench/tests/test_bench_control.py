"""The comparison that decides ``correct`` rejects its control: the plain
reference put in the program's place, one precision lower than the
configuration states (float32 -> bfloat16 for the DPD, bfloat16 -> fp8 for
the LM), and passes the program itself."""
import pytest

from bench import harness
from bench.limits import CONTROL
from bench.tests import smoke


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_spec()["workloads"]])
def test_control_fails_program_passes(workload):
    cell = smoke.cell(workload)
    driver = cell.config["driver"]
    sess = harness.load_module("drivers", driver).Session(cell, 2**31 + 3)
    calls, _ = harness.window(sess, 0.3)
    sess.release()
    (_, program, limit), = sess.check(calls)
    (_, control, _), = sess.check(calls, **CONTROL[driver])
    assert program <= limit < control
