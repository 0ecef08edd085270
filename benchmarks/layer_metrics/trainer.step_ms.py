"""Layer: trainer.  Median over the window of the host time between two
loss reads divided by the steps between them, in ms."""
from benchmarks.harness import stats


def read(obs):
    step_s = obs["result"].get("step_s")
    return None if not step_s else stats.median(step_s) * 1e3
