"""The motion-detection readers on hand-made traces: the Gauss kernel is
found by the name its ``vmap`` gives the custom call, and a window without
it, or without peaks, reads None."""
import pytest

from bench import harness
from bench.lib import trace_reduce as tr
from bench.lib import work

E = tr.Event
GAUSS = ('%vmap_jit_gauss5x5__.4 = f32[4,240,320]{2,1,0} custom-call('
         '%concatenate.4), custom_call_target="tpu_custom_call"')
PEAKS = work.peaks("TPU v5 lite")


def obs(ops, peaks=PEAKS):
    g_flops, g_bytes = work.gauss_call(240, 320, 96)
    return {"trace": tr.TraceSummary(0, 1e9, [ops]), "calls": [{}, {}],
            "window_s": 1.0, "peaks": peaks, "gauss_flops": g_flops,
            "gauss_bytes": g_bytes}


def read(name, o):
    return harness.load_module("metrics", name).read(o)


def test_gauss_roofline_reads_the_kernel_events():
    ops = [E(GAUSS, 0, 1e6), E(GAUSS, 2e6, 3e6), E("%fusion.3 = f32[]", 3e6, 9e6)]
    least = 2 * 96 * 240 * 320 * 2 / PEAKS["hbm_bytes_per_s"]
    assert read("md.gauss_roofline", obs(ops)) == pytest.approx(
        100 * least / 2e-3)


@pytest.mark.parametrize("ops,peaks", [
    ([E("%fusion.3 = f32[]", 0, 1e6)], PEAKS), ([E(GAUSS, 0, 1e6)], None)])
def test_gauss_roofline_without_kernel_or_peaks_reads_none(ops, peaks):
    assert read("md.gauss_roofline", obs(ops, peaks)) is None
