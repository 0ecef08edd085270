"""From a profiler trace to numbers.

`read_xplane()` turns an `.xplane.pb` into a plain dictionary (what
`benchmarks/tests/data/*.json` hold, so the arithmetic below is tested
without a chip):

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host":    [[name, start_ns, dur_ns], ...]}

`devices` holds the operations of each device's "XLA Ops" line, named by
`kernels.short_name()`; `host`
holds the spans the program and the benchmark wrote with
`jax.profiler.TraceAnnotation` (names with "::" in them), all threads
together.  Everything after that is interval arithmetic in nanoseconds:

    window()          the span the benchmark opened around the traced part
    busy_ns()         union of the intervals in which an operation ran
    self_times()      per operation name, its time minus its children's
                      (a `while` that holds a scanned layer stack covers
                      its body's operations; only leaves are work)
    idle_gaps()       the complement of the union, each gap with the
                      innermost host span open at its middle
"""
import glob
import os
import re

from benchmarks.trace import kernels

WINDOW_SPAN = "bench::window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
# spans written from Python by the program (RecordEvent) or the benchmark:
# "generation::ragged_step", "bench::window"; C++ TraceMe names are CamelCase
PROGRAM_SPAN = re.compile(r"^[a-z_]+::[a-z_0-9]+$")
COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast", "send", "recv")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path):
    """The dictionary above from one `.xplane.pb`.  On a backend with no
    device planes (the CPU rehearsal) the operations XLA ran on host
    threads, which carry an `hlo_op` stat, stand in as one device
    "cpu": enough to rehearse the arithmetic, never a device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, cpu_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [kernels.short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if PROGRAM_SPAN.match(e.name) and e.duration_ns > 0:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.name, float(e.start_ns),
                                        float(e.duration_ns)])
    if not devices and cpu_ops:
        devices["cpu"] = cpu_ops
    for events in devices.values():
        events.sort(key=lambda e: (e[1], -e[2]))
    host.sort(key=lambda e: (e[1], -e[2]))
    return {"devices": devices, "host": host}


def window(trace):
    """(start_ns, end_ns) of the traced window: the `bench::window` span
    if the trace holds one, else the extent of the device operations."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    starts = [e[1] for ev in trace["devices"].values() for e in ev]
    ends = [e[1] + e[2] for ev in trace["devices"].values() for e in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events, lo, hi):
    """The merged intervals of [lo, hi) in which some operation ran."""
    merged = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events, lo, hi):
    return sum(b - a for a, b in busy_intervals(events, lo, hi))


def self_times(events, lo, hi):
    """{name: [self_ns, calls]} over [lo, hi): an operation's time minus
    the time of the operations that run inside it on the same line."""
    out = {}
    stack = []  # [name, end, self_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            rec = out.setdefault(name, [0.0, 0])
            rec[0] += own
            rec[1] += 1

    for name, a, b in sorted(_clip(events, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            # a child: its span comes off its parent's own time (clipped
            # to the parent, should the trace's clocks let it stick out)
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return out


def idle_gaps(events, host, lo, hi):
    """[(gap_start, gap_end, span_name)] for every gap of the busy union
    inside [lo, hi).  The span is the innermost host span (other than the
    window's own) open at the gap's middle, or "(no span)"."""
    gaps, cursor = [], lo
    for a, b in busy_intervals(events, lo, hi):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = [s for s in host if s[0] != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        name, best = "(no span)", None
        for s_name, s_start, s_dur in spans:
            if s_start <= mid < s_start + s_dur and (
                    best is None or s_dur < best):
                name, best = s_name, s_dur
        out.append((a, b, name))
    return out


def is_collective(name):
    """`name` as kernels.short_name() gives it: "<instr> <opcode> <shape>"."""
    parts = name.split(" ")
    return len(parts) > 1 and parts[1].startswith(COLLECTIVE_PREFIXES)


def reduce(trace):
    """Everything the per-layer readers and the last line take from a
    trace, as plain numbers in seconds.  Device numbers are averaged over
    the devices in the trace; `ops` and `gaps` are those of the first."""
    lo, hi = window(trace)
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy = [busy_ns(ev, lo, hi) for ev in devices.values()]
    first = next(iter(devices.values()))
    ops = self_times(first, lo, hi)
    gaps = {}
    for a, b, name in idle_gaps(first, trace["host"], lo, hi):
        gaps[name] = gaps.get(name, 0.0) + (b - a)
    spans = {}
    for name, a, b in _clip([s for s in trace["host"]
                             if s[0] != WINDOW_SPAN], lo, hi):
        spans.setdefault(name, []).append((b - a) / 1e9)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(devices),
        "ops": {n: [ns / 1e9, calls] for n, (ns, calls) in ops.items()},
        "gaps": {n: ns / 1e9 for n, ns in gaps.items()},
        "spans": spans,
    }


def breakdown(reduced, top=10):
    """The `breakdown` of a traced run's last line."""
    ops = sorted(((n, s) for n, (s, _) in reduced["ops"].items()),
                 key=lambda e: -e[1])[:top]
    gaps = sorted(reduced["gaps"].items(), key=lambda e: -e[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def op_seconds(reduced, match):
    """(seconds, calls) of the operations whose name `match` accepts."""
    picked = [(s, c) for n, (s, c) in reduced["ops"].items() if match(n)]
    return sum(s for s, _ in picked), sum(c for _, c in picked)
