"""Layer: step.  Share of device 0's busy time, %, spent under the step's
`attention` part: each layer's norm, projections, rotary, row writes, the
attention kernel and the transposes around it, and the work lists the
kernels walk (`benchmarks/trace/scopes.py`: leaves joined with the
program's `device_op_scopes()` by module and instruction).  None from a
program that keeps no such map."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.share(obs, "attention")
