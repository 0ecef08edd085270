"""Layer: experts.  Roofline share, %, of the expert layers' grouped
products (`jax.lax.ragged_dot`, XLA:TPU's grouped-matmul kernel): the
least time the chip could spend in them a second of serving, over the
time it spent in them a second of the traced part.

The least time is `moe_call` of benchmarks/flops/<config>.py over the
whole window: the three matrices of every expert that got a row read
once at the HBM peak, or the (token, expert) pairs' operations at the
bf16 peak, whichever is longer, from the program's counters (window
deltas) `generation.moe_experts_touched` (distinct experts with a row,
summed over layers and steps) and `generation.moe_assignments_total`,
over the window's seconds.  The time spent is that of the custom calls
named `ragged-dot...` (`benchmarks/trace/custom_calls.py`) inside the
traced part, over its seconds.  The traced part is a stretch of the
window under the same load, so the two rates describe the same steps.
None from a program without the counters or the calls."""
from benchmarks.trace import custom_calls


def read(obs):
    peaks, trace, result = obs["peaks"], obs["trace"], obs["result"]
    counters = result.get("counters") or {}
    touched = counters.get("generation.moe_experts_touched")
    pairs = counters.get("generation.moe_assignments_total")
    if peaks is None or trace is None or not touched or not pairs:
        return None
    found = custom_calls.seconds_and_calls(obs, custom_calls.is_grouped)
    if found is None or not found[1]:
        return None
    ops, nbytes = obs["cell"].flops().moe_call(obs["config"], pairs, touched)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * (least / result["window_s"]) / (
        found[0] / trace["window_s"])
