"""Layer: cache.  Share, %, of the window layers' pages that went back to
their free list BEHIND the window while their sequence lived: window
delta of the program's counters `generation.kv_window_pages_released`
over `generation.kv_window_pages_reserved` (what a finished sequence
returns is not counted as released).  0 where no context passes the
window (the bypass); about 1 - window / context for long ones.  None
from a program without a window group."""


def read(obs):
    counters = obs["result"].get("counters") or {}
    reserved = counters.get("generation.kv_window_pages_reserved")
    if not reserved:
        return None
    return 100.0 * counters.get("generation.kv_window_pages_released",
                                0) / reserved
