"""Layer: scheduler.  Median time from a request's first admission to
its first token, ms: the program's `first_token_s - admitted_s`, over
the requests `engine.ttft_ms_p50` takes (first token inside the
window): the steps its prompt's chunks rode (handle.prefill_chunks of
them on the ragged path), less what the prefix cache served."""
from benchmarks.harness import request_stamps


def read(obs):
    return request_stamps.delta_ms_p50(obs, "first_token_s", "admitted_s")
