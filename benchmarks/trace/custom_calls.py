"""The `tpu_custom_call`s of a cell's traced run, by INSTRUCTION name.

`kernels.short_name()` files every `tpu_custom_call` whose first operand
is s32 under "pallas:ragged".  In a cell that serves a latent model with
experts two different things begin with an s32 operand: the program's
latent-attention Pallas kernel (its grid bound) and XLA:TPU's own
grouped-matmul kernel, which is what `jax.lax.ragged_dot` reaches the
chip as (its group sizes).  A chip trace of the cell shows (PR 28)

    %latent_attention.7 = bf16[10,160,512]{...} custom-call(s32[]{...} %bitcast.3, s32[13312]{...} %copy-done.78, s32[13312]{...} %copy-done.72, s32[1]{...} %dynamic_slice.1, s32[17]{...} %copy-done.125, ...), custom_call_target="tpu_custom_call", ...
    %ragged-dot-none.3 = f32[320,3072]{...} custom-call(s32[1]{...} %get-tuple-element.44, s32[65]{...} %get-tuple-element.45, ...), custom_call_target="tpu_custom_call", ...
    %ragged-dot-metadata.1 = (s32[65]{...}, s32[68]{...}, s32[68]{...}, s32[1]{...}) custom-call(s32[64]{...} %get-tuple-element.142), custom_call_target="tpu_custom_call", ...

(the latent kernel's instruction carries the model's `jax.named_scope`;
the rule does not lean on it)

The reduced trace has lost the names, so the readers of this cell's
kernels go back to the profile the run wrote.  The rule, in one place:
a grouped product is a custom call whose instruction name starts with
`ragged-dot` (`-metadata` is the group bookkeeping the products are
walked by, microseconds); the latent kernel is a custom call that
`kernels.pallas_kind()` calls ragged and that is no grouped product.
"""
import functools
import os
import re

from benchmarks.trace import kernels, reduce

_GROUPED = re.compile(r"^%?ragged-dot")


def is_grouped(hlo):
    """A grouped product or its bookkeeping."""
    return kernels.PALLAS in hlo and bool(_GROUPED.match(hlo))


def is_latent(hlo):
    return (kernels.PALLAS in hlo and not _GROUPED.match(hlo)
            and kernels.pallas_kind(hlo) == kernels.RAGGED)


@functools.lru_cache(maxsize=2)
def _custom_calls(path):
    """(window, [(instruction, start_ns, duration_ns)]) of the first
    device's custom calls in the profile at `path`."""
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
                              for e in line.events if kernels.PALLAS in e.name]
    return window, events or []


def seconds_and_calls(obs, match):
    """(seconds, calls) inside the traced window of the custom calls
    whose instruction `match` accepts, from the profile of the cell's
    traced run; None with no trace, no profile or no window span."""
    if obs["trace"] is None:
        return None
    trace_dir = os.path.join(obs["cell"].root, "benchmarks", "out", "trace",
                             obs["cell"].name)
    try:
        window, events = _custom_calls(reduce.find_xplane(trace_dir))
    except (OSError, ValueError):
        return None
    if window is None:
        return None
    lo, hi = window
    inside = [max(0.0, min(s + d, hi) - max(s, lo))
              for name, s, d in events if match(name)]
    inside = [ns for ns in inside if ns > 0]
    return sum(inside) / 1e9, len(inside)
