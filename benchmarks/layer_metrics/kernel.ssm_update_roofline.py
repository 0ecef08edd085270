"""Layer: state_space.  Roofline share, %, of the state-space layers'
one-token update, which is memory-bound: the least time the chip could
spend in it a second of serving, over the time it spent in it a second
of the traced part.

The least time is `ssm_update_bytes` of benchmarks/flops/<config>.py
(a slot's recurrent state and convolution tail read once and written
once for every (row, state layer) pair) over the window's own counter
`generation.ssm_rows_updated` (window delta: one-row descriptors x state
layers, counted inside the step), at the HBM peak, over the window's
seconds: the same count whatever implements the update.  The time spent
is that of every operation outside the scan's loops that names a
slot-state or a tail array, whatever XLA fused into it
(`benchmarks/trace/state_ops.py`: "update"), inside the traced part,
over its seconds.  An update that walks every slot's state where few
slots have a row reads LOW here, as it should: the floor counts the
rows updated, not the slots.  None from a program without the counter
or the operations."""
from benchmarks.trace import state_ops


def read(obs):
    peaks, trace, result = obs["peaks"], obs["trace"], obs["result"]
    rows = (result.get("counters") or {}).get("generation.ssm_rows_updated")
    if peaks is None or trace is None or not rows:
        return None
    found = state_ops.seconds(obs)
    if not found or not found["update"]:
        return None
    least = obs["cell"].flops().ssm_update_bytes(obs["config"], rows) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * (least / result["window_s"]) / (
        found["update"] / trace["window_s"])
