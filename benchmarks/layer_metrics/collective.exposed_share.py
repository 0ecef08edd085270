"""Layer: collectives.  Share of the traced window, %, in which device 0's
operation line runs a collective (all-gather, reduce-scatter, all-reduce,
collective-permute, their -start and -done halves) and so no compute: the
line runs one operation at a time, and an asynchronous collective shows
there only while the core waits for it."""
from benchmarks.trace import reduce


def read(obs):
    trace = obs["trace"]
    if trace is None:
        return None
    seconds, _ = reduce.op_seconds(trace, reduce.is_collective)
    return 100.0 * seconds / trace["window_s"]
