"""The reduction from a profiler trace to busy time, idle gaps, kernel time
and the breakdown, on hand-made events and on a trace recorded on the chip
(``data/dpd_small.xplane.pb.gz``: one DPD stream call of 8 windows of 1024
samples, Pallas FIR, 2-window chunks, on one TPU v5e)."""
import gzip
import os
import shutil

from bench import harness
from bench.lib import trace_reduce as tr

E = tr.Event
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "dpd_small.xplane.pb.gz")


def test_union_and_gaps():
    ops = [E("a", 0, 10), E("b", 5, 15), E("c", 20, 30), E("d", 22, 25)]
    assert tr.union_ns(ops) == 25
    assert tr.idle_gaps(ops, -5, 40) == [(-5, 0), (15, 20), (30, 40)]


def test_enclosing_events_are_not_counted_twice():
    ops = [E("while", 0, 100), E("x", 10, 20), E("y", 30, 40)]
    assert [e.name for e in tr._leaves(ops)] == ["x", "y"]


def test_gap_charged_to_innermost_host_span():
    host = [E(tr.WINDOW, 0, 100), E("call", 0, 90), E("stage", 10, 20),
            E("other thread", 5, 50)]
    assert tr.host_activity(host, [1, 15, 30, 95]) == [
        "call", "stage", "other thread", "no host span"]


def test_breakdown_of_hand_made_window():
    s = tr.TraceSummary(0, 100, [[E("k", 10, 30), E("m", 50, 60)]],
                        [E("stage", 0, 10), E("fetch", 60, 100)])
    assert abs(s.busy_s - 30e-9) < 1e-18
    assert abs(s.window_s - 100e-9) < 1e-18
    assert s.kernel_s(lambda e: e.name == "k") == 20e-9
    b = s.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["k", "m"]
    assert dict(b["idle_gaps"]) == {"fetch": 40e-9, "stage": 10e-9,
                                    "no host span": 20e-9}


def test_op_name_is_the_hlo_instruction():
    assert tr.op_name(E("%fusion.12 = f32[2]{0} fusion(...)", 0, 1)) == \
        "fusion.12"
    assert tr.op_name(E("dot.3", 0, 1)) == "dot.3"


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "dpd_small.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = tr.summarize(str(path))
    assert len(s.devices) == 1
    assert 0 < s.busy_s < s.window_s
    fir = harness.load_module("metrics", "dpd.fir_roofline").is_fir_kernel
    assert 0 < s.kernel_s(fir) < s.busy_s
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    busy_ops = sum(v for _, v in b["device_ops"])
    assert busy_ops <= s.busy_s * 1.000001
