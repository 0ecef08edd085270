"""Layer: sparse_latent_kernel.  Roofline share, %, of the selected-token
latent attention calls: the least time the chip could spend in them a
second of serving, over the time it spent in them a second of the
traced part.

The least time is `sparse_call` of benchmarks/flops/<config>.py over
the whole window: each selected token's latent row (1,152 B) read once
a row and layer at the HBM peak, or the pairs' operations at the bf16
peak, whichever is longer, from the window delta of the program's
counter `generation.dsa_tokens_attended`, over the window's seconds.
The time spent is that of the calls named `sparse_latent_paged_
attention` inside the traced part, over its seconds.  None from a
program without the counter or the calls."""
import re

from benchmarks.trace import custom_calls

_NAME = re.compile(r"^%?sparse_latent_paged_attention")


def read(obs):
    peaks, trace, result = obs["peaks"], obs["trace"], obs["result"]
    pairs = (result.get("counters") or {}).get(
        "generation.dsa_tokens_attended")
    if peaks is None or trace is None or not pairs:
        return None
    found = custom_calls.seconds_and_calls(obs, _NAME.match)
    if found is None or not found[1]:
        return None
    ops, nbytes = obs["cell"].flops().sparse_call(obs["config"], pairs)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * (least / result["window_s"]) / (
        found[0] / trace["window_s"])
