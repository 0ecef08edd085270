"""Layer: sparse_latent_kernel.  Share of device 0's busy time, %, spent
in the selected-token latent attention calls alone: the custom calls
whose instruction is named `sparse_latent_paged_attention`
(`benchmarks/trace/custom_calls.py` reads them from the profile the run
wrote).  None from a program without such a call."""
import re

from benchmarks.trace import custom_calls

_NAME = re.compile(r"^%?sparse_latent_paged_attention")


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    found = custom_calls.seconds_and_calls(obs, _NAME.match)
    if found is None or not found[1]:
        return None
    return 100.0 * found[0] / trace["busy_s"]
