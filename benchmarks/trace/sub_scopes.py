"""The device's time under a SCOPE PATH of the step, not only a part:
`attention/indexer` where `benchmarks/trace/scopes.py` reads the part
`attention` whole.  The same join as `scopes.split` (a leaf's self time
on the "XLA Ops" line, its module by the enclosing "XLA Modules" event,
its path in the program's `device_op_scopes()` map), summed over the
leaves whose path is `prefix` or lies beneath it.  None without a
trace, a profile, a window span or a map."""
import bisect
import os

from benchmarks.trace import reduce, scopes


def seconds(obs, prefix):
    """Seconds inside the traced window of the operations under scope
    path `prefix`, or None where nothing can be read."""
    if obs["trace"] is None:
        return None
    trace_dir = os.path.join(obs["cell"].root, "benchmarks", "out", "trace",
                             obs["cell"].name)
    try:
        from paddle_tpu import profiler
        maps = profiler.device_op_scopes()
        window, lines = scopes._profile(reduce.find_xplane(trace_dir))
    except (ImportError, AttributeError, OSError, ValueError):
        return None
    if window is None or not lines or reduce.OPS_LINE not in lines:
        return None
    modules = sorted((s, s + d, scopes.module_name(n))
                     for n, s, d in lines.get(scopes.MODULES_LINE, []))
    starts = [m[0] for m in modules]
    total = 0.0
    for name, start, own in scopes.self_times(lines[reduce.OPS_LINE],
                                               *window):
        i = bisect.bisect_right(starts, start) - 1
        module = modules[i][2] if i >= 0 and start < modules[i][1] else None
        m = scopes._INSTRUCTION.match(name)
        path = maps.get(module, {}).get(m.group(1)) if m else None
        if path and (path == prefix or path.startswith(prefix + "/")):
            total += own / 1e9
    return total
