"""The comparison that decides ``correct`` rejects its control: the plain
reference put in the program's place, one precision lower than the
configuration states (the driver's ``CONTROL``: float32 -> bfloat16 for a
streamed graph, bfloat16 -> fp8 for the LM), and passes the program
itself."""
import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_spec()["workloads"]])
def test_control_fails_program_passes(workload):
    cell = smoke.cell(workload)
    driver = smoke.driver(cell)
    sess = driver.Session(cell, 2**31 + 3)
    calls, _ = harness.window(sess, 0.3)
    sess.release()
    (_, program, limit), = sess.check(calls)
    (_, control, _), = sess.check(calls, **driver.CONTROL)
    assert program <= limit < control
