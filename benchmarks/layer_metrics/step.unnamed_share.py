"""Layer: step.  Share of device 0's busy time, %, that no part of the
step names: operations of other programs, instructions XLA made whose
users no part names, and operations of the step outside every part
(`benchmarks/trace/scopes.py`).  What the other `step.*` shares leave
out; the seven parts and this add up to busy.  None from a program that
keeps no map."""
from benchmarks.trace import scopes


def read(obs):
    found = scopes.read(obs)
    busy = obs["trace"] and obs["trace"]["busy_s"]
    if not found or not busy:
        return None
    return 100.0 * found["unnamed_s"] / busy
