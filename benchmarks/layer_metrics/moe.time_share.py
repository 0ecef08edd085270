"""Layer: experts.  Share of device 0's busy time, %, spent in the expert
layers' grouped products: the custom calls that
`benchmarks/trace/custom_calls.py` tells apart by their instruction name
(`ragged-dot...`: the products and the group metadata they are walked
by), read from the profile the run wrote."""
from benchmarks.trace import custom_calls


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    found = custom_calls.seconds_and_calls(obs, custom_calls.is_grouped)
    if found is None:
        return None
    return 100.0 * found[0] / trace["busy_s"]
