"""Layer: trainer.  Model FLOP/s utilization, %: the operations one step's
forward and backward passes require (benchmarks/flops/<config>.py: no
gathers, no recomputation) over the median step time (`trainer.step_ms`),
over chips times the chip's bf16 peak.  From the median step and not from
the window's rate, which in a traced run holds the profiler's own stalls."""
from benchmarks.harness import stats


def read(obs):
    step_s = obs["result"].get("step_s")
    if not step_s or obs["peaks"] is None:
        return None
    per_step = obs["cell"].flops().train_step_flops(
        obs["config"], obs["traffic"], obs["result"]["data_replicas"])
    return 100.0 * per_step / stats.median(step_s) / (
        obs["device"]["count"] * obs["peaks"]["bf16_flops_per_s"])
