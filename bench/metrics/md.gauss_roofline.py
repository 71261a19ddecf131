"""Share of its roofline that the Pallas Gauss kernel reaches: the least
time the chip needs for the traced calls' Gauss work (operations and u8
bytes from the frame shape, ``bench.lib.work.gauss_frame``) over the
summed device time of the kernel's events."""
from bench.lib import trace_reduce, work


def is_gauss_kernel(e) -> bool:
    """A Mosaic custom call of ``gauss5x5`` (``repro.kernels.gauss5x5``
    ``ops.gauss5x5``, the jitted op that wraps the Pallas kernel), which
    Gauss's ``vmap`` over the frames of a token names
    ``vmap_jit_gauss5x5__.<n>``."""
    return ("gauss5x5" in trace_reduce.op_name(e)
            and "tpu_custom_call" in e.name)


def read(obs):
    if obs["peaks"] is None:
        return None
    t = obs["trace"].kernel_s(is_gauss_kernel)
    if t <= 0:
        return None
    n = len(obs["calls"])
    return 100.0 * work.least_s(obs["gauss_flops"] * n,
                                obs["gauss_bytes"] * n, obs["peaks"]) / t
