"""Layer: benchmark.  95th percentile over the window's requests of how
long after it was due the load generator handed a request to the engine,
ms.  A generator that runs late offers less load than the traffic file
says, which flatters every serving metric, and time to first token is
counted from the due time, so this is part of it: it has to stay small."""
from benchmarks.harness import stats


def read(obs):
    late = obs["result"].get("lateness_s")
    p95 = stats.percentile(late, 95) if late else None
    return None if p95 is None else p95 * 1e3
