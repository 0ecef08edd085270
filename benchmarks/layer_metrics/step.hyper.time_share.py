"""Layer: step.  Share of device 0's busy time, %, spent under the
step's `hyper` part: the mixing of a residual several streams wide
(each sublayer's coefficients, its Sinkhorn, the streams' sums in and
out; `benchmarks/trace/scopes.py`).  None from a program that keeps no
map or has no such part."""
from benchmarks.trace import scopes


def read(obs):
    found = scopes.read(obs)
    busy = obs["trace"] and obs["trace"]["busy_s"]
    if not found or not busy or "hyper" not in found["parts"]:
        return None
    return 100.0 * found["parts"]["hyper"] / busy
