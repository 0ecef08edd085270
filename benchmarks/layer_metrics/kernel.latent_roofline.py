"""Layer: latent_kernel.  Roofline share, %: the least time the chip
could take for one latent-attention call, over the mean time such a call
took in the trace.  The least time is that of reading each live
sequence's cached rows once at the HBM peak, or of the absorbed
attention's operations at the bf16 peak, whichever is longer
(`ragged_call` of benchmarks/flops/<config>.py).  The calls are the
latent kernel's alone (`benchmarks/trace/custom_calls.py`), one a layer
and step; the live rows are counted as `kernel.ragged_roofline` counts
them, from the benchmark's own stamps at three instants of the traced
part.  None from a program without such a call."""
from benchmarks.trace import custom_calls


def read(obs):
    peaks, result = obs["peaks"], obs["result"]
    if peaks is None or "tracked" not in result:
        return None
    found = custom_calls.seconds_and_calls(obs, custom_calls.is_latent)
    if found is None or not found[1]:
        return None
    seconds, calls = found
    live = obs["cell"].module("layer_metrics",
                              "kernel.ragged_roofline").live_kv_tokens
    lo, hi = obs["clock"]["traced"]
    at = [lo + f * (hi - lo) for f in (0.25, 0.5, 0.75)]
    kv = sum(live(result["tracked"], a) for a in at) / len(at)
    ops, nbytes = obs["cell"].flops().ragged_call(obs["config"], kv, kv)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
