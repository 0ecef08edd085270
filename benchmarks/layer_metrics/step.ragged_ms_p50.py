"""Layer: step.  Median of the program's own `generation::ragged_step`
spans inside the traced part of the window, ms: pack, dispatch, the one
host fetch and sampling."""
from benchmarks.harness import stats


def read(obs):
    trace = obs["trace"]
    spans = trace and trace["spans"].get("generation::ragged_step")
    return None if not spans else stats.median(spans) * 1e3
