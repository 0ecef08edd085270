"""Layer: step.  Share of device 0's busy time, %, spent under the step's
`mlp` part: the dense feed-forward layers with their norms and residual
(all of OPT's; GLM's and Trinity's leading layers), read by scope
(`benchmarks/trace/scopes.py`).  None from a program that keeps no such
map."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.share(obs, "mlp")
