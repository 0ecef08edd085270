"""Layer: step.  Share of device 0's busy time, %, spent under the step's
`head` part: the final norm, the vocabulary product, the argmax and the
step's counters, read by scope (`benchmarks/trace/scopes.py`).  None
from a program that keeps no such map."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.share(obs, "head")
