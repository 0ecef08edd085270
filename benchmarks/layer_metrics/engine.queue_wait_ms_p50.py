"""Layer: scheduler.  Median wait for a slot, ms: the program's
`admitted_s - submitted_s` on the request's handle, over the requests
`engine.ttft_ms_p50` takes (first token inside the window).  With
`engine.prefill_ms_p50` it splits the program's own time to first token
exactly: queue wait + prefill = `first_token_s - submitted_s`."""
from benchmarks.harness import request_stamps


def read(obs):
    return request_stamps.delta_ms_p50(obs, "admitted_s", "submitted_s")
