"""Layer: paged_kernel.  Roofline share, %: the least time the chip could
take per call, over the mean time a call took in the trace.  The least
time is memory's: every key and value of the sequences in the step read
once at the HBM peak (benchmarks/flops/<config>.py), against which the
operations are a rounding error at these batch sizes.

The keys in a step are those of the sequences that were decoding at the
step's start, prompt plus tokens delivered by then, from the benchmark's
own stamps; a sequence still in prefill is left out, so the share is a
little understated in a cell with long prompts."""
import bisect

from benchmarks.trace import kernels, reduce


def live_kv_tokens(tracked, at):
    total = 0
    for t in tracked:
        if t.token_s and t.token_s[0] <= at and (
                t.done_abs is None or t.done_abs > at):
            total += len(t.request.prompt) + bisect.bisect_right(
                t.token_s, at)
    return total


def read(obs):
    trace, peaks, result = obs["trace"], obs["peaks"], obs["result"]
    if trace is None or peaks is None or "tracked" not in result:
        return None
    seconds, calls = reduce.op_seconds(trace, kernels.is_ragged)
    if not calls:
        return None
    # the traced part is short against a request: three samples of it
    lo, hi = obs["clock"]["traced"]
    at = [lo + f * (hi - lo) for f in (0.25, 0.5, 0.75)]
    kv = sum(live_kv_tokens(result["tracked"], a) for a in at) / len(at)
    ops, nbytes = obs["cell"].flops().ragged_call(obs["config"], kv, kv)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
