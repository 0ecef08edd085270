"""The program's own stamps on a request's handle, for the readers that
split time to first token into its parts.

The engine stamps, all on `time.monotonic()` (which `obs["clock"]
["traced"]` also is, so a request can be laid beside the traced steps):
`submitted_s` in `submit`, `admitted_s` when the scheduler first gives
the request a slot, `first_token_s` at its first token, `finished_s`
when it is retired.  A program that lacks a stamp leaves the readers
nothing to read, and they return None.
"""
import collections

from benchmarks.harness import stats


def first_token_in_window(result):
    """The tracked requests `engine.ttft_ms_p50` takes: those whose
    first token fell inside the window.  `loadgen.window_view` lists
    their `first token - due time` in `result["ttft_s"]`; the same
    subtraction on the same stamps finds them again.  A result without
    that list gives every tracked request with a first token."""
    served = [t for t in result.get("tracked") or [] if t.token_s]
    if result.get("ttft_s") is None:
        return served
    want = collections.Counter(result["ttft_s"])
    picked = []
    for t in served:
        ttft = t.token_s[0] - t.due_abs
        if want[ttft] > 0:
            want[ttft] -= 1
            picked.append(t)
    return picked


def delta_ms_p50(obs, later, earlier):
    """Median over those requests of handle.<later> - handle.<earlier>,
    ms; None when no request carries both stamps."""
    deltas = []
    for t in first_token_in_window(obs["result"]):
        a = getattr(t.handle, earlier, None)
        b = getattr(t.handle, later, None)
        if a is not None and b is not None:
            deltas.append(b - a)
    p50 = stats.median(deltas)
    return None if p50 is None else p50 * 1e3
