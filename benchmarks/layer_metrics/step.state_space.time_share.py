"""Layer: step.  Share of device 0's busy time, %, spent under the step's
`state_space` part: the state-space layers' norm, projections, the
one-token update and the chunked scan (its `update` and `scan` scopes),
the gated norm and the residual, read by scope
(`benchmarks/trace/scopes.py`; `ssm.time_share` reads nearly the same
operations by the arrays they name).  None from a program that keeps no
such map."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.share(obs, "state_space")
