"""Layer: step.  The serial host time of one ragged step, ms: what the
device waits for.  Summed durations of the program's
`generation::schedule`, `pack`, `dispatch`, `emit` and `account` spans
inside the traced part of the window, over the number of steps
dispatched there.  `post_dispatch` is left out (it runs while the device
does the step) and so is `fetch` (the wait for the device).

A step is counted by its `generation::dispatch` span, not by its
`generation::ragged_step`: the step in flight when the trace begins
leaves its closing phases in the trace and the one in flight when it
ends its opening phases, but neither its `ragged_step`, so over the
`ragged_step` spans the mean would read one step in N too high.

None with no trace, or from a program without the spans."""
SERIAL = ("schedule", "pack", "dispatch", "emit", "account")


def read(obs):
    spans = (obs["trace"] or {}).get("spans", {})
    phases = [spans.get("generation::" + name) for name in SERIAL]
    if not all(phases):
        return None
    steps = len(spans["generation::dispatch"])
    return sum(sum(p) for p in phases) / steps * 1e3
