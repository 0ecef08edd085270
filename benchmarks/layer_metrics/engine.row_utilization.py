"""Layer: scheduler.  Useful rows over dispatched rows of the ragged
step's fixed token axis, %, from the program's counters over the window."""


def read(obs):
    c = obs["result"].get("counters", {})
    sent = c.get("generation.step_rows_dispatched")
    if not sent:
        return None
    return 100.0 * c.get("generation.step_rows_useful", 0) / sent
