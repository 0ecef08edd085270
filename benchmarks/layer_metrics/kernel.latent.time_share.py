"""Layer: latent_kernel.  Share of device 0's busy time, %, spent in the
latent-attention Pallas calls alone: the custom calls with an s32 first
operand that are not XLA's grouped products
(`benchmarks/trace/custom_calls.py` has the rule and samples), read from
the profile the run wrote.  None from a program without such a call."""
from benchmarks.trace import custom_calls


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    found = custom_calls.seconds_and_calls(obs, custom_calls.is_latent)
    if found is None or not found[1]:
        return None
    return 100.0 * found[0] / trace["busy_s"]
