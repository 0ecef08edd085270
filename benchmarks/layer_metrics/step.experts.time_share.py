"""Layer: step.  Share of device 0's busy time, %, spent under the step's
`experts` part: the router, the sort, the gathers and relayouts, the
grouped products (which `moe.time_share` counts alone) and the shared
expert, with the layer's norm and residual, read by scope
(`benchmarks/trace/scopes.py`).  None from a program that keeps no such
map."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.share(obs, "experts")
