"""Layer: step.  Median duration, ms, of the ragged step programs' events
(`jit_ragged_step_p<bucket>`) on device 0's "XLA Modules" line inside
the traced window (`benchmarks/trace/scopes.py`): the device's own time
for a step, which `step.ragged_ms_p50` (the host's span, the step
period) equals where the device idles about never.  None where no such
program ran (a tree before PR 38 names its step `jit_step`)."""
from benchmarks.harness import stats
from benchmarks.trace import scopes


def read(obs):
    found = scopes.read(obs)
    return stats.median(found["step_ms"]) if found and found["step_ms"] \
        else None
