"""The operations of a cell's traced run that belong to the state-space
layers, told apart by the arrays their instructions name.

An event of a device's "XLA Ops" line is named by its HLO instruction
(`kernels.py`) and carries no stat but its times: the program's
`jax.named_scope`s (`state_space`, `state_space/update`,
`state_space/scan`) are in the compiled module's metadata and NOT in the
profile (a chip trace of the cell, PR 36: the only stats are
`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`), and a
fusion's name says nothing (`%fusion.525`).  What an instruction does
say is the type of its result and of every operand:

    %fusion.525 = (f32[65,128,64,128]{...}, f32[65,128,64]{...}) fusion(f32[65,128,64,128]{...} %flat_11_.1, f32[65,128]{...} %get-tuple-element.7376, ...), kind=kLoop, calls=...
    %while.395 = (s32[], f32[576,8192]{...}, f32[65,128,64,128]{...}, bf16[65,3,8448]{...}, ...) while(...)
    %fusion.91 = f32[576,16768]{...} fusion(bf16[576,4096]{...} %x, bf16[4096,16768]{...} %w), kind=kOutput, ...

and the state-space layers' arrays have shapes nothing else in the model
has, all of them functions of the configuration: a layer's recurrent
states `[slots + 1, heads, P, N]`, its tails `[slots + 1, K - 1, C]`,
and rows of the widths of `in_proj` (2 d_inner + 2 N + heads), of the
convolution (C = d_inner + 2 N) and of `d_inner`.  The rule, in one
place, over the LEAVES of the line (a `while` spans its body's
operations, which are listed themselves):

    scan         every leaf inside a `while` whose own type names the
                 state array: the loop over a step's chunks and, inside
                 it, the loop over a chunk's blocks
    update       a leaf outside those loops that names the state array
                 or the tail array: the one-token update of every slot,
                 whatever XLA fused into it
    projections  a leaf outside those loops that names a row of one of
                 the three widths: `in_proj`, the gated norm, `out_proj`
                 and the copies between them

A grouped product or an attention call (`custom_calls.py`) is none of
them whatever it names.
"""
import functools
import os
import re

from benchmarks.trace import kernels, reduce

_WHILE = re.compile(r"^%?while[.\w\-]* = ")


def shapes(config):
    """(state, tail, row widths) as an instruction names them, or None
    for a configuration without state-space layers."""
    b = config["builder"]
    m = b["model_args"]
    if "mamba_n_heads" not in m:
        return None
    rows = b["engine"]["max_decode_slots"] + 1
    d_inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = d_inner + 2 * m["mamba_d_state"]
    return (f"[{rows},{m['mamba_n_heads']},{m['mamba_d_head']},"
            f"{m['mamba_d_state']}]",
            f"[{rows},{m.get('mamba_d_conv', 4) - 1},{conv}]",
            tuple(f",{w}]" for w in (d_inner + conv + m["mamba_n_heads"],
                                     conv, d_inner)))


def classify(events, state, tail, widths):
    """[(kind or None, start_ns, duration_ns)] of the leaves of
    `events` [(instruction, start_ns, duration_ns)], by the rule in the
    module's docstring."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, loops = [], []       # loops: the open `while`s, (end, is a scan)
    for i, (name, start, dur) in enumerate(events):
        while loops and loops[-1][0] <= start:
            loops.pop()
        end = start + dur
        if _WHILE.match(name):
            loops.append((end, state in name))
        if i + 1 < len(events) and events[i + 1][1] < end \
                and events[i + 1][1] + events[i + 1][2] <= end:
            continue                      # holds the next one: no leaf
        if kernels.PALLAS in name:
            kind = None
        elif any(scan for _, scan in loops):
            kind = "scan"
        elif state in name or tail in name:
            kind = "update"
        elif any(w in name for w in widths):
            kind = "projections"
        else:
            kind = None
        out.append((kind, start, dur))
    return out


@functools.lru_cache(maxsize=2)
def _ops(path):
    """(window, [(instruction, start_ns, duration_ns)]) of the first
    device's operations in the profile at `path`."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    window, events = None, None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == reduce.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith(reduce.DEVICE_PLANE) and events is None:
            for line in plane.lines:
                if line.name == reduce.OPS_LINE:
                    events = [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events]
    return window, events or []


def seconds(obs):
    """``{"scan": s, "update": s, "projections": s}`` inside the traced
    window, from the profile of the cell's traced run; None with no
    trace, no profile, no window span, or a configuration without
    state-space layers."""
    found = shapes(obs["config"])
    if obs["trace"] is None or found is None:
        return None
    trace_dir = os.path.join(obs["cell"].root, "benchmarks", "out", "trace",
                             obs["cell"].name)
    try:
        window, events = _ops(reduce.find_xplane(trace_dir))
    except (OSError, ValueError):
        return None
    if window is None:
        return None
    lo, hi = window
    out = {"scan": 0.0, "update": 0.0, "projections": 0.0}
    for kind, start, dur in classify(events, *found):
        if kind is not None:
            out[kind] += max(0.0, min(start + dur, hi) - max(start, lo)) / 1e9
    return out
