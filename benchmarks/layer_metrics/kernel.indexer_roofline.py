"""Layer: indexer.  Roofline share, %, of the indexer's scoring calls:
the least time the chip could spend in them a second of serving, over
the time it spent in them a second of the traced part.

The least time is `indexer_call` of benchmarks/flops/<config>.py over
the whole window: the index keys of every scored context (256 B a
token) read once a `full` layer at the HBM peak, or the (row, context
token) pairs' operations at the bf16 peak, whichever is longer, from the
window deltas of the program's counters `generation.dsa_index_keys`
(each descriptor's context once a `full` layer) and
`generation.dsa_context_tokens` (each row's context, over all layers,
of which the `full` layers' share is scored), over the window's
seconds.  The time spent is that of the calls named
`dsa_indexer_scores` inside the traced part, over its seconds.  None
from a program without the counters or the calls."""
import re

from benchmarks.trace import custom_calls

_NAME = re.compile(r"^%?dsa_indexer_scores")


def read(obs):
    peaks, trace, result = obs["peaks"], obs["trace"], obs["result"]
    counters = result.get("counters") or {}
    keys = counters.get("generation.dsa_index_keys")
    context = counters.get("generation.dsa_context_tokens")
    if peaks is None or trace is None or not keys or not context:
        return None
    found = custom_calls.seconds_and_calls(obs, _NAME.match)
    if found is None or not found[1]:
        return None
    m = obs["config"]["builder"]["model_args"]
    full = m["indexer_types"].count("full") / len(m["indexer_types"])
    ops, nbytes = obs["cell"].flops().indexer_call(
        obs["config"], context * full, keys)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * (least / result["window_s"]) / (
        found[0] / trace["window_s"])
