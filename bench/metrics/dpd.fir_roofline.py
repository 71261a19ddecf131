"""Share of its roofline that the Pallas FIR kernel reaches: the least time
the chip needs for the traced calls' FIR launches (operations and bytes
from their shapes, ``bench.lib.work.fir_branch``) over the summed device
time of the kernel's events."""
from bench.lib import trace_reduce, work


def is_fir_kernel(e) -> bool:
    """A Mosaic custom call of ``dpd_branch`` (``repro.kernels.dyn_fir``
    ``ops.dpd_branch``, the jitted op that wraps the Pallas kernel)."""
    return (trace_reduce.op_name(e).startswith("dpd_branch")
            and "tpu_custom_call" in e.name)


def read(obs):
    if obs["peaks"] is None:
        return None
    t = obs["trace"].kernel_s(is_fir_kernel)
    if t <= 0:
        return None
    n = len(obs["calls"])
    return 100.0 * work.least_s(obs["fir_flops"] * n, obs["fir_bytes"] * n,
                                obs["peaks"]) / t
