"""Layer: flash_kernel.  Share of device 0's busy time, %, spent in the
Pallas flash-attention calls (forward and backward).  0 where dropout
gates the kernel off (ops/attention.py): the bypass."""
from benchmarks.trace import kernels, reduce


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    seconds, _ = reduce.op_seconds(trace, kernels.is_flash)
    return 100.0 * seconds / trace["busy_s"]
