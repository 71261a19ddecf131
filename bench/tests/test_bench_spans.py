"""The readers of the program's host spans (``bench/lib/spans.py`` and the
metrics on it), on hand-made windows and on a trace recorded on the chip
(``data/dpd_spans.xplane.pb.gz``: one DPD stream call of 8 windows of
1024 samples, Pallas FIR, 2-window chunks, on one TPU v5e, with the
program's spans)."""
import gzip
import os
import shutil

import pytest

from bench import harness
from bench.lib import spans
from bench.lib import trace_reduce as tr

E = tr.Event
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "dpd_spans.xplane.pb.gz")
DPD_READERS = ["dpd.program_idle", "dpd.launches_per_call",
               "dpd.device_reads_per_call"]


def read(name, summary, calls=1):
    return harness.load_module("metrics", name).read(
        {"trace": summary, "calls": [{}] * calls,
         "window_s": summary.window_s})


def window():
    """Device busy 0-10, 40-60, 90-100 of a 0-100 window; the gap 10-40
    falls in a runtime event inside ``stage``, 60-90 in ``wait``."""
    ops = [[E("op", 0, 10), E("op", 40, 60), E("op", 90, 100)]]
    host = [E(spans.STREAM, 0, 100), E(spans.STAGE, 5, 40),
            E("DeferredTpuAllocator::Allocate", 15, 35),
            E(spans.LAUNCH, 40, 45), E(spans.WAIT, 45, 90),
            E(spans.COLLECT, 90, 100)]
    return tr.TraceSummary(0, 100, ops, host)


def test_gap_under_runtime_event_is_charged_to_its_program_span():
    assert spans.idle_by_phase(window()) == pytest.approx(
        {spans.STAGE: 30e-9, spans.WAIT: 30e-9})
    # The whole-trace breakdown still names the runtime event.
    assert dict(window().breakdown()["idle_gaps"])[
        "DeferredTpuAllocator::Allocate"] == pytest.approx(30e-9)


@pytest.mark.parametrize("wait_gap", [False, True])
def test_program_idle_counts_host_phases_only(wait_gap):
    s = window()
    if not wait_gap:        # device busy through the wait
        s.devices[0].append(E("op", 60, 90))
    assert read("dpd.program_idle", s) == pytest.approx(30.0)


def test_gap_outside_program_spans_is_not_counted():
    s = window()
    s.t1_ns = 150           # idle 100-150, after the call
    assert spans.idle_by_phase(s)["no host span"] == pytest.approx(50e-9)
    assert read("dpd.program_idle", s) == pytest.approx(20.0)


@pytest.mark.parametrize("name,event", [
    ("dpd.launches_per_call", spans.LAUNCH_EVENT),
    ("dpd.device_reads_per_call", spans.READ_EVENT)])
def test_events_count_inside_stream_calls_only(name, event):
    host = [E(spans.STREAM, 0, 50), E(spans.STREAM, 60, 100),
            E(event, 5, 6), E(event, 49, 52), E(event, 55, 56),
            E(event, 70, 71), E(event, 100, 101), E("other", 20, 21)]
    s = tr.TraceSummary(0, 110, [[E("op", 0, 1)]], host)
    assert read(name, s, calls=2) == 1.5


def test_build_seconds_per_call():
    host = [E(spans.BUILD, 0, 2e9), E(spans.BUILD, 3e9, 4e9),
            E(spans.LAUNCH, 4e9, 9e9)]
    s = tr.TraceSummary(0, 10e9, [[E("op", 9e9, 1e10)]], host)
    assert read("serve.build_s_per_call", s, calls=2) == pytest.approx(1.5)


@pytest.mark.parametrize("name", DPD_READERS + ["serve.build_s_per_call"])
def test_missing_span_reads_none(name):
    # A program without spans: runtime events only, as a parent commit
    # that predates them records.
    host = [E("stream.call", 0, 90), E(spans.LAUNCH_EVENT, 5, 6),
            E(spans.READ_EVENT, 7, 8)]
    s = tr.TraceSummary(0, 100, [[E("op", 10, 20)]], host)
    assert read(name, s) is None


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("spans") / "dpd_spans.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.summarize(str(path))


@pytest.mark.parametrize("name", DPD_READERS)
def test_recorded_chip_trace_reads(chip_trace, name):
    assert read(name, chip_trace) > 0


def test_recorded_chip_trace_program_idle_within_device_idle(chip_trace):
    assert read("dpd.program_idle", chip_trace) <= read(
        "dpd.device_idle", chip_trace)


def test_recorded_chip_trace_holds_one_call(chip_trace):
    calls = spans.named(chip_trace, spans.STREAM)
    assert len(calls) == 1
    assert {e.name for e in chip_trace.host} >= set(spans.PROGRAM)
