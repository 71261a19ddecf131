"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler refuses what Pallas interpret mode accepts: tiles not
aligned to (8, 128), too much fast memory, programs larger than the
chip's HBM.  These tests compile the main path's kernels and the DPD
dynamic program at the paper's sizes for one chip of a ``v5e:2x2``
topology, and check that each kernel is lowered by Mosaic
(``tpu_custom_call``) and that every program fits the chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off here (a
compile for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.executor import _compile_dynamic
from repro.graphs.dpd import BLOCK_L, build_dpd
from repro.graphs.motion_detection import FRAME_H, FRAME_W
from repro.kernels.dyn_fir import N_BRANCHES, N_TAPS
from repro.kernels.dyn_fir.kernel import dpd_branch_pallas
from repro.kernels.gauss5x5.kernel import gauss5x5_pallas
from repro.kernels.motion_post.kernel import motion_post_pallas

V5E_HBM_BYTES = 16 * 10**9
DPD_FIRINGS = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used
    return used


KERNELS = {
    "dpd_branch_pallas": (
        lambda xr, xi, hr, hi: dpd_branch_pallas(xr, xi, hr, hi,
                                                 order=N_BRANCHES),
        [(BLOCK_L + N_TAPS - 1,)] * 2 + [(N_TAPS,)] * 2),
    "gauss5x5_pallas": (gauss5x5_pallas, [(FRAME_H, FRAME_W)]),
    "motion_post_pallas": (motion_post_pallas, [(FRAME_H, FRAME_W)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    """Mosaic lowers the kernel at the paper's size (DPD token L = 32768,
    order 10; QVGA frames with the default ``block_h``)."""
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(
        *[_sds(s, one_chip) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_dpd_dynamic_program_compiles_for_v5e(one_chip):
    """The token-driven DPD program (64 firings of 32768 samples) compiles
    as one program for the chip, its state on the chip's HBM."""
    net = build_dpd(DPD_FIRINGS, block_l=BLOCK_L)
    runner = _compile_dynamic(net, return_sweeps=True)
    state = jax.tree.map(lambda x: _sds(x.shape, one_chip, x.dtype),
                         jax.eval_shape(net.init_state))
    compiled = jax.jit(runner).lower(state).compile()
    signal_bytes = 2 * DPD_FIRINGS * BLOCK_L * 4
    assert _fits(compiled) >= 2 * signal_bytes + net.buffer_bytes()
