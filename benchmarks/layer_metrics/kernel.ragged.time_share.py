"""Layer: paged_kernel.  Share of device 0's busy time, %, spent in the
ragged paged-attention Pallas calls."""
from benchmarks.trace import kernels, reduce


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    seconds, _ = reduce.op_seconds(trace, kernels.is_ragged)
    return 100.0 * seconds / trace["busy_s"]
