"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one import of JAX (a chip belongs to one process).  The cell
is looked up in BENCHMARK.json; its configuration, traffic, runner,
reference, operation counts and per-layer readers are files found by name
(harness/manifest.py).  The last line of standard output is one JSON
object: with `--trace 0` the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics, the device's busy seconds over a traced part of the
window and a breakdown.  Everything else goes on earlier lines.

With no accelerator, or fewer chips than the cell asks for, the run fails
and prints no result.  `--rehearse` is the explicit CPU rehearsal: the
configuration's tiny `rehearsal` preset, virtual devices, `platform: cpu`
in the stamp, and no time under a metric's name.
"""
import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import device, manifest  # noqa: E402
from benchmarks.harness.compile_clock import CompileClock  # noqa: E402
from benchmarks.trace import reduce as trace_reduce  # noqa: E402

OUT_DIR = os.path.join(ROOT, "benchmarks", "out")
TRACE_AT = 0.4        # the traced part starts this far into the window
TRACE_SECONDS = 4.0   # and lasts this long unless the traffic file says


def _merge(base, over, only_existing=False):
    """`base` with `over` laid on top, dictionaries merged key by key.
    With `only_existing`, keys `base` lacks are left out: one rehearsal
    preset then serves every traffic mix of its configuration."""
    out = dict(base)
    for k, v in over.items():
        if only_existing and k not in base:
            continue
        out[k] = _merge(out[k], v, only_existing) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


class Window:
    """The measured window, and inside a traced run the few seconds in
    which the profiler is on.  The program's `RecordEvent` spans and the
    benchmark's own are written as `TraceAnnotation`s, so they land in the
    device's trace on one clock; Python-level tracing stays off, it slows
    the host it is there to watch."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.t0 = time.monotonic()
        ctx.clock["setup_s"] = self.t0 - _PROCESS_START
        ctx.clock["compile"] = ctx.compile_clock.snapshot()
        self._trace_t0 = None
        self._span = None
        self.traced = False

    def closed(self, now):
        return now - self.t0 >= self.ctx.seconds

    def poll(self, now):
        ctx = self.ctx
        if not ctx.trace:
            return
        if self._trace_t0 is None and not self.traced \
                and now - self.t0 >= TRACE_AT * ctx.seconds:
            self._start()
        elif self._trace_t0 is not None \
                and time.monotonic() - self._trace_t0 >= ctx.trace_seconds:
            self._stop()

    def finish(self):
        if self._trace_t0 is not None:
            self._stop()

    def _start(self):
        import jax

        import paddle_tpu.profiler as program_profiler

        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.ctx.trace_dir,
                                 profiler_options=options)
        program_profiler.start_profiler()     # the program's spans on
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self._trace_t0 = time.monotonic()

    def _stop(self):
        import jax

        import paddle_tpu.profiler as program_profiler

        self._span.__exit__(None, None, None)
        with contextlib.redirect_stdout(io.StringIO()):
            program_profiler.stop_profiler()  # prints its table
        jax.profiler.stop_trace()
        self.ctx.clock["traced"] = (self._trace_t0, time.monotonic())
        self._trace_t0 = None
        self.traced = True


class Context:
    """What a runner and a per-layer reader are handed."""

    def __init__(self, cell, args, jax):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.config = cell.config
        self.traffic = cell.traffic
        if args.rehearse:
            # the preset lies over the configuration key by key, and its
            # "traffic" over the traffic file
            preset = dict(cell.config["rehearsal"])
            self.traffic = _merge(self.traffic, preset.pop("traffic", {}),
                                  only_existing=True)
            self.config = _merge(self.config, preset)
        self.builder = self.config["builder"]
        self.trace_seconds = float(self.traffic.get("trace_s", TRACE_SECONDS))
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        self.clock = {}
        self.checks = {}    # what `correct` compared: {name: (value, limit)}
        self.compile_clock = CompileClock(jax)
        self._jax = jax

    def module(self, kind, name):
        return self.cell.module(kind, name)

    def note(self, text):
        print(f"  {text}", flush=True)

    def span(self, name):
        """A host span in the profiler's trace; free when none is on."""
        return self._jax.profiler.TraceAnnotation(name)

    def open_window(self):
        return Window(self)


def _plain(x):
    """A compared number as JSON can carry it: inf and nan have no JSON."""
    x = float(x)
    return x if math.isfinite(x) else str(x)


def end_to_end(ctx, result):
    """The cell's end-to-end metrics: set-up is the harness's own clock,
    the others are what the runner returned under the metric's name."""
    return {"setup_s": ctx.clock["setup_s"], **result["end_to_end"]}


def per_layer(ctx, result, reduced, stamp):
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    obs = {"cell": ctx.cell, "config": ctx.config, "traffic": ctx.traffic,
           "result": result, "trace": reduced, "clock": ctx.clock,
           "device": stamp,
           "peaks": (None if stamp["platform"] == "cpu"
                     else device.peaks(stamp["kind"]))}
    return {m["name"]: ctx.module("layer_metrics", m["name"]).read(obs)
            for m in ctx.cell.per_layer}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU; never a measurement")
    args = ap.parse_args(argv)

    cell = manifest.Cell(manifest.load(ROOT), args.workload, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    try:
        stamp = (device.stamp(jax, cell.chips) if args.rehearse
                 else device.require(jax, cell.chips))
    except device.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print(f"benchmark: cell {cell.name}, seed {args.seed}, window "
          f"{args.seconds}s, trace {args.trace}, device {stamp}, compile "
          f"cache {enable_compile_cache()}", flush=True)

    ctx = Context(cell, args, jax)
    runner = cell.module("runners", cell.config["runner"])
    result = runner.run(ctx)
    compile_at_open = ctx.clock["compile"]
    print(f"  set-up {ctx.clock['setup_s']:.1f}s: weights "
          f"{ctx.clock.get('weights_s', 0.0):.1f}s, tracing and lowering "
          f"{compile_at_open['trace_s']:.1f}s, compiling or reading the "
          f"cache {compile_at_open['backend_s']:.1f}s over "
          f"{compile_at_open['programs']} programs (persistent cache "
          f"{compile_at_open['hits']} hits, {compile_at_open['misses']} "
          f"misses)", flush=True)

    stamp["memory_peak_bytes"] = device.memory_peak_bytes(jax, cell.chips)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": stamp}
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    if ctx.trace:
        trace = trace_reduce.read_xplane(
            trace_reduce.find_xplane(ctx.trace_dir))
        # the trace as the reduction sees it (the first device's
        # operations and the host spans), for a look by hand
        first = next(iter(trace["devices"]))
        with open(os.path.join(OUT_DIR, cell.name + ".trace.json"),
                  "w") as f:
            json.dump({"devices": {first: trace["devices"][first]},
                       "host": trace["host"]}, f)
        reduced = trace_reduce.reduce(trace)
        values = per_layer(ctx, result, reduced, stamp)
        if not args.rehearse:
            stamp["busy_s"] = reduced["busy_s"]
            stamp["window_s"] = reduced["window_s"]
            line["breakdown"] = trace_reduce.breakdown(reduced)
        keep = ("program_counter",) if args.rehearse else manifest.SOURCES
        metrics = cell.per_layer
    else:
        values = end_to_end(ctx, result)
        keep = () if args.rehearse else manifest.SOURCES
        metrics = cell.end_to_end
    line["metrics"] = {
        m["name"]: {"value": float(values[m["name"]]), "unit": units[m["name"]]}
        for m in metrics
        if m["source"] in keep and values.get(m["name"]) is not None}
    if args.rehearse:
        line["rehearsal"] = True
        print("REHEARSAL: tiny preset on the CPU; says nothing of the chip",
              flush=True)
    # what `correct` compared, each number beside its limit: the last key
    # of the line and the last lines of standard error, which is what the
    # driver's record keeps of a run that was not correct
    line["checks"] = {name: {"value": _plain(value), "limit": _plain(limit)}
                      for name, (value, limit) in ctx.checks.items()}
    print(json.dumps(line), flush=True)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
