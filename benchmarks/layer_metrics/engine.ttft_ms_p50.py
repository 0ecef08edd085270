"""Layer: scheduler.  Median over the requests whose first token fell in
the window of first token pushed minus the time the request was due, ms:
queue wait plus chunked prefill (less what the prefix cache served).

What a chat user feels first, and yet no end-to-end metric: at today's step
times a window holds ~32 requests, and their median spread by 12-25 % between
seeds on one tree (PERF.md, PR 22), past any bound the contract allows.  It
is recorded here until a window holds hundreds of requests."""
from benchmarks.harness import stats


def read(obs):
    ttft = obs["result"].get("ttft_s")
    p50 = stats.percentile(ttft, 50) if ttft else None
    return None if p50 is None else p50 * 1e3
