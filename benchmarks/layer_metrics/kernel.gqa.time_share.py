"""Layer: gqa_kernel.  Share of device 0's busy time, %, spent in the
grouped-query attention Pallas calls (eight a step, one a layer): the
custom calls with an s32 first operand that are not XLA's grouped
products (`benchmarks/trace/attention_calls.py`), read from the profile
the run wrote.  None from a program without such a call."""
from benchmarks.trace import attention_calls


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    found = attention_calls.seconds_and_calls(obs)
    if found is None or not found[1]:
        return None
    return 100.0 * found[0] / trace["busy_s"]
