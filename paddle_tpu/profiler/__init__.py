"""Profiler.

Reference parity: platform/profiler.{h,cc} (RecordEvent, EnableProfiler:213,
sorted per-event summary table + chrome-trace export via profiler.proto) +
fluid/profiler.py context manager.  TPU-native: host spans via RecordEvent
(summary table matches the reference's columns: Calls/Total/Min/Max/Ave/
Ratio, sorted_key in {default,calls,total,max,min,ave}), chrome-trace JSON
written to profile_path (the reference serializes a proto; chrome://tracing
and Perfetto load this JSON directly), and device traces via jax.profiler
(XLA/TPU timelines) — the CUPTI role (SURVEY §5.1) is played by the PJRT
profiler.
"""
import contextlib
import json
import os
import threading
import time
from collections import defaultdict

import jax

_state = threading.local()
# name -> [count, total_s, min_s, max_s]
_records = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
_events = []  # (name, tid, start_s, dur_s, args) for chrome-trace export
_MAX_EVENTS = 200_000
_enabled = [False]
_trace_dir = [None]
_t_origin = [0.0]


class RecordEvent:
    """RAII span (platform/profiler.h RecordEvent parity).

    Keyword attributes describe the span: with the profiler on they
    become stats of its TraceAnnotation (so they sit beside the span in
    the device's trace) and the `args` of the chrome-trace export.  They
    are read as the span CLOSES, so a callable value can say what the
    span found out (the pages bucket a dispatch took); it is called
    then and only then.  With the profiler off `begin`/`end` are one
    list read each and no attribute is evaluated."""

    def __init__(self, name, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._t0 = None
        self._jax_ctx = None

    def __enter__(self):
        self.begin()
        return self

    def begin(self):
        if _enabled[0]:
            self._t0 = time.perf_counter()
            self._jax_ctx = jax.profiler.TraceAnnotation(self.name)
            self._jax_ctx.__enter__()

    def end(self):
        if self._t0 is not None:
            t0, self._t0 = self._t0, None
            dt = time.perf_counter() - t0
            args = None
            try:
                if self._attrs:
                    args = {k: v() if callable(v) else v
                            for k, v in self._attrs.items()}
                    self._jax_ctx.set_metadata(**args)
            finally:
                self._jax_ctx.__exit__(None, None, None)
            rec = _records[self.name]
            rec[0] += 1
            rec[1] += dt
            rec[2] = min(rec[2], dt)
            rec[3] = max(rec[3], dt)
            if len(_events) < _MAX_EVENTS:
                _events.append((self.name, threading.get_ident(),
                                t0 - _t_origin[0], dt, args))

    def __exit__(self, *exc):
        self.end()
        return False


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    _enabled[0] = True
    _records.clear()
    _events.clear()
    _t_origin[0] = time.perf_counter()
    if trace_dir:
        _trace_dir[0] = trace_dir
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="default", profile_path=None):
    """EnableProfiler teardown parity (profiler.h:213-216): print the
    sorted summary table and, when profile_path is given, dump the span
    timeline as chrome-trace JSON (chrome://tracing / Perfetto).  The
    programs compiled so far are read into `device_op_scopes()` here,
    while the engine that compiled them still holds them."""
    _enabled[0] = False
    device_op_scopes()
    if _trace_dir[0]:
        jax.profiler.stop_trace()
        _trace_dir[0] = None
    if profile_path:
        export_chrome_trace(profile_path)
    return summary(sorted_key)


_SORT = {
    "default": lambda r: 0,          # insertion order, like the reference
    "calls": lambda r: -r[1],
    "total": lambda r: -r[2],
    "max": lambda r: -r[4],
    "min": lambda r: -r[3],
    "ave": lambda r: -r[5],
}


def summary(sorted_key="default"):
    """Sorted per-event table with the reference's columns
    (platform/profiler.cc PrintProfiler): Calls, Total, Min, Max, Ave,
    Ratio (share of the summed span time)."""
    if sorted_key not in _SORT:
        raise ValueError(
            f"sorted_key must be one of {sorted(_SORT)}, got {sorted_key!r}")
    grand = sum(r[1] for r in _records.values()) or 1.0
    rows = [
        (name, cnt, tot, mn if cnt else 0.0, mx,
         tot / cnt if cnt else 0.0, tot / grand)
        for name, (cnt, tot, mn, mx) in _records.items()
    ]
    rows.sort(key=_SORT[sorted_key])
    head = (f"{'Event':<36}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
            f"{'Max(ms)':>10}{'Ave(ms)':>10}{'Ratio':>8}")
    lines = ["-------------------------  Profiling Report  "
             "-------------------------", head]
    for name, cnt, tot, mn, mx, avg, ratio in rows:
        lines.append(
            f"{name:<36}{cnt:>8}{tot * 1e3:>12.3f}{mn * 1e3:>10.3f}"
            f"{mx * 1e3:>10.3f}{avg * 1e3:>10.3f}{ratio:>8.3f}")
    report = "\n".join(lines)
    print(report)
    return report


def export_chrome_trace(path):
    """Write recorded spans in chrome-trace 'traceEvents' JSON (the role
    of the reference's profiler.proto dump, directly loadable by
    chrome://tracing and Perfetto)."""
    trace = {
        "traceEvents": [
            {"name": name, "ph": "X", "pid": os.getpid(), "tid": tid,
             "ts": round(start * 1e6, 3), "dur": round(dur * 1e6, 3),
             "cat": "host", **({"args": args} if args else {})}
            for name, tid, start, dur, args in _events
        ],
        "displayTimeUnit": "ms",
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


@contextlib.contextmanager
def profiler(state="All", sorted_key="default", profile_path=None,
             trace_dir=None):
    """fluid/profiler.py:314 context-manager parity."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class Profiler:
    """paddle.profiler.Profiler-style API over jax.profiler."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 trace_dir=None):
        self.trace_dir = trace_dir

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        start_profiler(trace_dir=self.trace_dir)

    def stop(self):
        stop_profiler()

    def step(self):
        pass

    def summary(self, **kw):
        return summary(**kw)

    def export_chrome_trace(self, path):
        return export_chrome_trace(path)


from .monitor import (  # noqa: E402,F401  (monitor.h StatRegistry parity)
    Stat, StatRegistry, stat_add, stat_sub, stat_get,
)
from .device_scopes import (  # noqa: E402,F401
    device_op_scopes, register_program,
)
