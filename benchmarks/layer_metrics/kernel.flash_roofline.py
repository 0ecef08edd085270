"""Layer: flash_kernel.  Roofline share, %: the least time the chip could
take for the traced flash calls (per call the larger of operations over
the bf16 peak and bytes over the HBM peak, from shapes:
benchmarks/flops/<config>.py) over the time they took.  At sequence 1024
and head size 64 the bound is compute."""
from benchmarks.trace import kernels, reduce


def read(obs):
    trace, peaks = obs["trace"], obs["peaks"]
    if trace is None or peaks is None:
        return None
    flops = obs["cell"].flops()
    least = took = 0.0
    for backward, match in ((False, kernels.is_flash_forward),
                            (True, kernels.is_flash_backward)):
        seconds, calls = reduce.op_seconds(trace, match)
        ops, nbytes = flops.flash_call(obs["config"], obs["traffic"],
                                       backward)
        # the backward pass is `kernels.FLASH_BACKWARD_CALLS` kernel calls
        # that together do one backward's work
        per = kernels.FLASH_BACKWARD_CALLS if backward else 1
        least += calls / per * max(ops / peaks["bf16_flops_per_s"],
                                   nbytes / peaks["hbm_bytes_per_s"])
        took += seconds
    return None if not took else 100.0 * least / took
