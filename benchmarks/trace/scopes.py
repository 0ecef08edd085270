"""The device's time in a step by PART of the step, read by name (PR 38).

Since PR 38 the program names what the device runs: each step program's
module (`jit_ragged_step_p128`: the ragged step at the 128-page bucket)
on the first device's "XLA Modules" line, each of its operations by
instruction on the "XLA Ops" line (`%fusion.12`, `%latent_paged_attention.3`),
and, in the compiled text, the `jax.named_scope` every instruction sits
under.  `paddle_tpu.profiler.device_op_scopes()` hands the benchmark that
text's map, ``{module: {instruction: scope path}}``, in the process that
ran the cell; the profile carries no scope of its own (PR 36).

The join, over the traced window (`bench::window`):

    leaf        an operation's self time on the "XLA Ops" line, as
                `reduce.self_times` takes it (a `while` covers its body's
                operations; what it does not cover is its own)
    module      the "XLA Modules" event that encloses the leaf's start
    part        the first component of the leaf's scope path in the
                module's map, if it is one of the step's parts
                (`fused.STEP_SCOPES`); else the leaf is UNNAMED (another
                program, an instruction XLA made and no user of it named,
                an operation of the program outside every part)

`read()` gives ``{"parts": {part: s}, "unnamed_s": s, "step_ms": [...]}``,
`step_ms` being the durations of the ragged step programs' module
events wholly inside the window.  None without a trace, a profile, a
window span or a device line, and None where the program keeps no map
(a tree before PR 38).  Every reader of the `step.*` shares divides by
the reduced trace's `busy_s`.
"""
import bisect
import functools
import os
import re

from benchmarks.trace import reduce

MODULES_LINE = "XLA Modules"
STEP_PROGRAM = re.compile(r"^jit_ragged_step_p\d+$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def module_name(event_name):
    """``jit_ragged_step_p128(42)`` -> ``jit_ragged_step_p128``."""
    return _PROGRAM_ID.sub("", event_name)


def self_times(events, lo, hi):
    """[(name, start_ns, self_ns)] of every operation of `events`
    clipped to [lo, hi): `reduce.self_times` event by event."""
    out, stack = [], []          # stack: [name, start, end, own]

    def close(until):
        while stack and stack[-1][2] <= until:
            name, start, _, own = stack.pop()
            out.append((name, start, own))

    for name, a, b in sorted(reduce._clip(events, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append([name, a, b, b - a])
    close(float("inf"))
    return out


def split(window, modules, ops, scopes, parts):
    """The join of the module's docstring over plain lists:
    `modules` and `ops` [(event name, start_ns, duration_ns)] of one
    device, `scopes` the program's map, `parts` the step's part names."""
    lo, hi = window
    modules = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    starts = [m[0] for m in modules]
    seconds, unnamed = dict.fromkeys(parts, 0.0), 0.0
    for name, start, own in self_times(ops, lo, hi):
        i = bisect.bisect_right(starts, start) - 1
        module = modules[i][2] if i >= 0 and start < modules[i][1] else None
        m = _INSTRUCTION.match(name)
        path = scopes.get(module, {}).get(m.group(1)) if m else None
        part = path.split("/")[0] if path else None
        if part in seconds:
            seconds[part] += own / 1e9
        else:
            unnamed += own / 1e9
    steps = [(end - s) / 1e6 for s, end, name in modules
             if STEP_PROGRAM.match(name) and lo <= s and end <= hi]
    return {"parts": seconds, "unnamed_s": unnamed, "step_ms": steps}


@functools.lru_cache(maxsize=2)
def _profile(path):
    """(window, modules, ops) of the first device in the profile at
    `path`, each line as [(event name, start_ns, duration_ns)]."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    window, lines = None, None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == reduce.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith(reduce.DEVICE_PLANE) and lines is None:
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (MODULES_LINE, reduce.OPS_LINE)}
    return window, lines


@functools.lru_cache(maxsize=2)
def _read(path):
    try:
        from paddle_tpu import profiler
        from paddle_tpu.generation.fused import STEP_SCOPES
        maps = profiler.device_op_scopes
    except (ImportError, AttributeError):
        return None
    window, lines = _profile(path)
    if window is None or not lines or reduce.OPS_LINE not in lines:
        return None
    return split(window, lines.get(MODULES_LINE, []), lines[reduce.OPS_LINE],
                 maps(), STEP_SCOPES)


def read(obs):
    """The split of the cell's traced run (module docstring), or None."""
    if obs["trace"] is None:
        return None
    trace_dir = os.path.join(obs["cell"].root, "benchmarks", "out", "trace",
                             obs["cell"].name)
    try:
        return _read(reduce.find_xplane(trace_dir))
    except (OSError, ValueError):
        return None


def share(obs, part):
    """Part `part`'s seconds, % of the device's busy time; None where
    nothing was read."""
    found = read(obs)
    busy = obs["trace"] and obs["trace"]["busy_s"]
    if not found or not busy:
        return None
    return 100.0 * found["parts"][part] / busy
