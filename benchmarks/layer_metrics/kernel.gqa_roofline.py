"""Layer: gqa_kernel.  Roofline share, %: the least time the chip could
take for ONE STEP's attention (all layers), over the time the
grouped-query kernel took a step in the trace.

The least time is `ragged_call` of benchmarks/flops/<config>.py over the
sequences that were decoding at three instants of the traced part
(prompt plus tokens delivered by then, from the benchmark's own stamps,
as `kernel.latent_roofline` counts them): each one's K and V rows once a
layer at the HBM peak — all of them in a full layer, those inside its
window in a window layer — or the (query row, visible key) pairs'
operations at the bf16 peak, whichever is longer.  The time a step is
the kernel's seconds inside the traced part
(`benchmarks/trace/attention_calls.py`) over the steps dispatched there
(`generation::dispatch` spans), so that splitting a layer's call in two
does not halve the reading.  The work is the algorithm's whatever
implements it: a kernel that reads behind the window reads LOW here.  A
sequence still in prefill is left out of the least time while its
chunk's cells are in the kernel's, so the share is understated in a cell
whose steps carry chunks.  None from a program without such a call or
without the spans."""
import bisect

from benchmarks.trace import attention_calls


def live_contexts(tracked, at):
    """Context lengths of the requests decoding at `at`."""
    return [len(t.request.prompt) + bisect.bisect_right(t.token_s, at)
            for t in tracked
            if t.token_s and t.token_s[0] <= at
            and (t.done_abs is None or t.done_abs > at)]


def read(obs):
    peaks, result, trace = obs["peaks"], obs["result"], obs["trace"]
    if peaks is None or trace is None or "tracked" not in result:
        return None
    steps = len(trace.get("spans", {}).get("generation::dispatch", ()))
    found = attention_calls.seconds_and_calls(obs)
    if found is None or not found[1] or not steps:
        return None
    window = int(obs["config"]["builder"]["model_args"]["sliding_window"])
    lo, hi = obs["clock"]["traced"]
    full = inside = 0.0
    for f in (0.25, 0.5, 0.75):
        contexts = live_contexts(result["tracked"], lo + f * (hi - lo))
        full += sum(contexts) / 3
        inside += sum(min(c, window) for c in contexts) / 3
    # a decode row sees every key of its context (its window's)
    ops, nbytes = obs["cell"].flops().ragged_call(
        obs["config"], full, inside, full, inside)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (found[0] / steps)
