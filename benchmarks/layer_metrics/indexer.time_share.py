"""Layer: indexer.  Share of device 0's busy time, %, spent under the
step's scope `attention/indexer`: the index queries, keys and weights,
the index keys' row write, the scoring kernel and the exact top-k
(`benchmarks/trace/sub_scopes.py`).  None from a program that keeps no
such scope."""
from benchmarks.trace import sub_scopes


def read(obs):
    busy = obs["trace"] and obs["trace"]["busy_s"]
    found = sub_scopes.seconds(obs, "attention/indexer")
    if not busy or not found:
        return None
    return 100.0 * found / busy
