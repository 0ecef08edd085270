"""Find a serving cell's knee, once: the highest arrival rate the engine
sustains.  One process and one engine; each rate is offered for the
traffic's ramp plus `--seconds`, then the engine drains before the next.

    python3 benchmarks/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.3,0.5,0.7

A rate is sustained when the requests in flight at the window's close are
no more than the slots hold and time to first token has not left the range
of the lower rates.  The cell's traffic file then gets 0.8 of the knee as a
number; no run of the benchmark searches for one.  Not part of a check.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import device, manifest, stats  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0

    cell = manifest.Cell(manifest.load(ROOT), args.workload, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    stamp = (device.stamp(jax, cell.chips) if args.rehearse
             else device.require(jax, cell.chips))
    enable_compile_cache()
    ctx = bench.Context(cell, args, jax)
    runner = cell.module("runners", cell.config["runner"])
    engine, _, metrics, ok, _ = runner.build(ctx)
    print(f"sweep: cell {cell.name}, device {stamp}, reference check "
          f"{'passed' if ok else 'FAILED'}", flush=True)
    points = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # another schedule, prompts and system prompts at every rate:
            # what one point left in the prefix cache serves no other
            ctx.seed = args.seed + i
            traffic = dict(ctx.traffic)
            traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
            out = runner.offer(ctx, engine, metrics, traffic, args.seconds,
                               ctx.open_window)
            point = {
                "rate_per_s": rate, "attempted": out["attempted"],
                "finished": out["finished"], "failed": out["failed"],
                "in_flight_at_close": out["in_flight_at_close"],
                "finished_per_s": out["finished"] / out["window_s"],
                "tokens_per_s": out["end_to_end"]["serve_out_tokens_per_s"],
                "ttft_ms_p50": (stats.percentile(out["ttft_s"], 50) or 0)
                * 1e3,
                "ttft_ms_p95": (stats.percentile(out["ttft_s"], 95) or 0)
                * 1e3,
                "gap_ms_p50": (stats.percentile(out["gap_s"], 50) or 0) * 1e3,
                "gap_ms_p95": out["end_to_end"]["serve_gap_ms_p95"],
            }
            points.append(point)
            print("SWEEP " + json.dumps(point), flush=True)
            # drain: every request still in flight ends or times out
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and any(
                    t.done_abs is None for t in out["tracked"]):
                time.sleep(0.2)
    finally:
        engine.shutdown(timeout=30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
