"""Layer: state_space.  Share of device 0's busy time, %, spent in the
state-space layers: their projections and gated norm, the one-token
update of every slot's state, and the chunked scan's loops, told apart
by the arrays the instructions name (`benchmarks/trace/state_ops.py`
has the rule and why the program's scopes cannot be read), from the
profile the run wrote.  The experts' grouped products and the attention
call are not in it.  None from a program without such operations."""
from benchmarks.trace import state_ops


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    found = state_ops.seconds(obs)
    if not found or not sum(found.values()):
        return None
    return 100.0 * sum(found.values()) / trace["busy_s"]
