#!/usr/bin/env python
"""The per-head ragged attention kernel alone, at the shapes of
opt-6.7b-d8's serving cells, over what a grid cell holds.

    chiprun -- python tools/ragged_kernel_sweep.py [--rehearse]

Two steps' attention as the cells dispatch it, 80 packed rows under 17
descriptors against float32 pools of 32 heads x 1,280 pages x 16 tokens
x 128 (kernel layout):

- `decode`: a `decode-closed` step — 16 one-row descriptors over
  contexts of 20-190 tokens, no chunk, the 16-page bucket;
- `chat`: a `chat-steady` step — 8 one-row descriptors over 300-1,000
  tokens beside one 64-row chunk behind 500 tokens, the 64-page bucket.

For each cell ``G:Hb:rows`` (pages, heads and query rows a cell:
`RAGGED_CELL_TOKENS`, `RAGGED_CELL_HEADS` and `RAGGED_CELL_ROWS` of
ops/pallas/paged_attention.py are set for the measurement, which is how
those constants were chosen: PERF.md section 6, PR 35) it prints the
milliseconds of ONE kernel call (a loop of 64 dependent calls in one
jitted program over a work list built outside it, median of 10 runs,
over 64), the call's share of its memory roofline (the live contexts' K
and V once at 819 GB/s), the grid steps walked a head block, and the
largest difference from the jnp reference over every row.  The last
line is a JSON object.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, HEAD_DIM, PAGE, POOL_PAGES, SLOTS, CHUNK = 32, 128, 16, 1280, 16, 64
CALLS, RUNS = 64, 10
HBM_BYTES_PER_S = 819e9
CELLS = ("1:1:8,4:8:8,4:32:8,8:8:8,8:16:8,8:32:8,16:8:8,16:16:8,16:32:8,"
         "32:16:8,8:32:16,16:16:16,16:32:16,8:32:32,16:16:32,16:32:32")


def _batch(rng, kv, lens, slots, bucket, page, pool_pages):
    """Descriptors of one step (the given live ones, then padding up to
    `slots` + 1) and page tables over distinct random pages."""
    pad = slots + 1 - len(kv)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1], [0] * pad])
    kv, lens = list(kv) + [0] * pad, list(lens) + [0] * pad
    pt = np.zeros((slots + 1, bucket), np.int32)
    free = iter(rng.permutation(pool_pages))
    for s, n in enumerate(kv):
        for i in range(-(-n // page)):
            pt[s, i] = next(free)
    return (pt, starts.astype(np.int32), np.asarray(lens, np.int32),
            np.asarray(kv, np.int32))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=CELLS,
                    help="the G:Hb:rows to measure, comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes in the interpreter on the CPU: no time "
                         "printed is a device time")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from paddle_tpu.generation.decode_attention import (
        ragged_paged_attention_reference)
    from paddle_tpu.ops.pallas import paged_attention as pa

    heads, dim, page, pool_pages, slots, chunk = (
        HEADS, HEAD_DIM, PAGE, POOL_PAGES, SLOTS, CHUNK)
    calls, runs = CALLS, RUNS
    rng = np.random.default_rng(35)
    steps = {
        "decode": (16, [int(rng.integers(20, 191)) for _ in range(slots)],
                   [1] * slots),
        "chat": (64, [int(rng.integers(300, 1001)) for _ in range(8)]
                 + [500 + chunk], [1] * 8 + [chunk]),
    }
    if args.rehearse:
        heads, dim, page, pool_pages, slots, chunk = 4, 8, 4, 96, 3, 6
        calls, runs = 2, 1
        steps = {"decode": (4, [5, 16, 9], [1] * 3),
                 "chat": (16, [40, 23, 30 + chunk], [1, 1, chunk])}
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        raise SystemExit(f"no chip: {device.platform}")
    key = jax.random.PRNGKey(35)
    shape = (heads, pool_pages, page, dim)
    kp = jax.random.normal(key, shape, jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    t = slots + chunk
    q = jax.random.normal(jax.random.fold_in(key, 2), (t, heads, dim),
                          jnp.float32)
    scale = dim ** -0.5
    # a budget no cell of the sweep is cut by: the rule's own is what
    # the sweep is there to set
    pa.RAGGED_VMEM_BUDGET = pa.RAGGED_VMEM_LIMIT
    interpret = True if args.rehearse else None
    results = []
    for name, (bucket, kv, lens) in steps.items():
        pt, starts, lens, kv = _batch(rng, kv, lens, slots, bucket, page,
                                      pool_pages)
        floor_s = float(kv.sum()) * heads * dim * 4 * 2 / HBM_BYTES_PER_S
        want = np.asarray(ragged_paged_attention_reference(
            q, kp, vp, pt, starts, lens, kv, scale=scale, layout="kernel"))
        for cell in args.cells.split(","):
            per, hb, rows = (int(x) for x in cell.split(":"))
            pa.RAGGED_CELL_TOKENS = per * page
            pa.RAGGED_CELL_HEADS = hb
            pa.RAGGED_CELL_ROWS = rows
            shape_ = pa.ragged_cell_shape(page, bucket, t, heads, dim, 4)
            work = jax.jit(lambda: pa.ragged_work_list(
                starts, lens, kv, page, bucket, t))()

            def attend(q, kp, vp, work):
                return pa.ragged_paged_attention_kernel(
                    q, kp, vp, pt, starts, lens, kv, scale,
                    interpret=interpret, layout="kernel", work=work)

            def loop(q, kp, vp, work):
                # each call reads the one before: none is hoisted
                return jax.lax.fori_loop(
                    0, calls, lambda i, out: attend(q + 1e-3 * out, kp, vp,
                                                    work), jnp.zeros_like(q))

            line = {"step": name, "cell": cell, "shape": list(shape_)}
            try:
                got = np.asarray(jax.jit(attend)(q, kp, vp, work))
                fn = jax.jit(loop)
                fn(q, kp, vp, work).block_until_ready()
                times = []
                for _ in range(runs):
                    t0 = time.perf_counter()
                    fn(q, kp, vp, work).block_until_ready()
                    times.append(time.perf_counter() - t0)
                call_s = float(np.median(times)) / calls
                line.update({
                    "call_ms": round(call_s * 1e3, 4),
                    "roofline_pct": round(100 * floor_s / call_s, 2),
                    "grid_steps": int(work[1][0]),
                    "page_slots": int(work[1][0]) * shape_[0],
                    "live_pages": pa.ragged_score_blocks(
                        starts, lens, kv, page, bucket, t, shape_[2])[0],
                    "max_abs_diff": float(np.abs(got - want).max()),
                })
            except Exception as e:  # a cell Mosaic refuses: say so, go on
                line["refused"] = str(e).splitlines()[0][:200]
            print(json.dumps(line), flush=True)
            results.append(line)
        results.append({"step": name, "floor_ms": round(floor_s * 1e3, 4),
                        "live_tokens": int(kv.sum()),
                        "pages_bucket": bucket})
    print(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "rehearsal": bool(args.rehearse), "rows": t,
        "descriptors": slots + 1, "sweep": results}))


if __name__ == "__main__":
    main()
