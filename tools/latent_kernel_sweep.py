#!/usr/bin/env python
"""The latent attention kernel alone, at the shapes of
glm-4.7-flash-d7.docqa-closed, over the pages a grid cell holds.

    chiprun -- python tools/latent_kernel_sweep.py [--rehearse]

One step's attention as the cell dispatches it: 80 packed rows under 17
descriptors at the 512-page bucket — 16 decode rows over contexts of
258-260 pages of 64 tokens and one 64-row chunk behind a 16,384-token
document — 20 heads against 640-lane rows of a 5,760-page pool, bf16.
For each G (pages a cell; `LATENT_CELL_TOKENS` is set to G x page_size
for the measurement, which is how the constant in
ops/pallas/paged_attention.py was chosen: PERF.md section 6, PR 33) it
prints the milliseconds of ONE kernel call (seven independent calls in
one jitted program over a work list built outside it, median of 20
runs, over seven), the call's share of its memory roofline (the live
contexts' rows once at 819 GB/s), the grid steps walked, and the largest
difference from the jnp reference on three descriptors' rows (a decode
row's, the last decode row's and the chunk's; the whole batch's scores
do not fit beside the pool).  The last line is a JSON object.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, LANES, V_WIDTH, PAGE, POOL_PAGES = 20, 640, 512, 64, 5760
SLOTS, CHUNK, BUCKET, DOCUMENT, OWN = 16, 64, 512, 16384, 224
CALLS, RUNS = 7, 20
HBM_BYTES_PER_S = 819e9


def _batch(rng, slots, chunk, bucket, document, own, page, pool_pages):
    """The descriptors of one full step and page tables over distinct
    random pages: decode rows `own // 7`..`own` tokens (a question and
    the answer so far) behind the document, the chunk a question's
    first tokens."""
    kv = [document + int(rng.integers(own // 7, own + 1))
          for _ in range(slots)] + [document + chunk]
    lens = [1] * slots + [chunk]
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    pt = np.zeros((slots + 1, bucket), np.int32)
    free = iter(rng.permutation(pool_pages))
    for s, n in enumerate(kv):
        for i in range(-(-n // page)):
            pt[s, i] = next(free)
    return (pt, starts.astype(np.int32), np.asarray(lens, np.int32),
            np.asarray(kv, np.int32))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", default="1,2,4,8,16",
                    help="the G to measure, comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes in the interpreter on the CPU: no time "
                         "printed is a device time")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from paddle_tpu.generation.decode_attention import (
        latent_ragged_attention_reference)
    from paddle_tpu.ops.pallas import paged_attention as pa

    heads, lanes, v_width, page, pool_pages = (
        HEADS, LANES, V_WIDTH, PAGE, POOL_PAGES)
    slots, chunk, bucket, document, own = SLOTS, CHUNK, BUCKET, DOCUMENT, OWN
    runs = RUNS
    if args.rehearse:
        heads, lanes, v_width, page, pool_pages = 3, 24, 16, 4, 400
        slots, chunk, bucket, document, own, runs = 3, 6, 32, 80, 40, 1
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        raise SystemExit(f"no chip: {device.platform}")
    rng = np.random.default_rng(33)
    pt, starts, lens, kv = _batch(rng, slots, chunk, bucket, document, own,
                                  page, pool_pages)
    t = int(lens.sum())
    key = jax.random.PRNGKey(33)
    pool = jax.random.normal(key, (pool_pages, page, lanes), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (t, heads, lanes),
                          jnp.bfloat16)
    scale = 1.0 / 16
    floor_s = float(kv.sum()) * lanes * 2 / HBM_BYTES_PER_S
    probe = np.array([0, slots - 1, slots])
    rows = np.concatenate([np.arange(starts[s], starts[s] + lens[s])
                           for s in probe])
    want = np.asarray(latent_ragged_attention_reference(
        q, pool, pt[probe], starts[probe], lens[probe], kv[probe], scale,
        v_width))[rows]
    interpret = True if args.rehearse else None
    results = []
    for per in (int(g) for g in args.groups.split(",")):
        pa.LATENT_CELL_TOKENS = per * page
        work = jax.jit(lambda: pa.latent_work_list(
            pt, starts, lens, kv, page, t))()

        def calls(q, pool, work):
            return sum(pa.latent_ragged_attention_kernel(
                q * (1 + i), pool, pt, starts, lens, kv, scale, v_width,
                interpret=interpret, work=work)
                for i in range(CALLS))

        one = jax.jit(lambda q, pool, work: pa.latent_ragged_attention_kernel(
            q, pool, pt, starts, lens, kv, scale, v_width,
            interpret=interpret, work=work))
        got = np.asarray(one(q, pool, work).astype(jnp.float32))[rows]
        fn = jax.jit(calls)
        fn(q, pool, work).block_until_ready()
        times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            fn(q, pool, work).block_until_ready()
            times.append(time.perf_counter() - t0)
        call_s = float(np.median(times)) / CALLS
        line = {
            "pages_per_cell": per,
            "call_ms": round(call_s * 1e3, 4),
            "roofline_pct": round(100 * floor_s / call_s, 2),
            "grid_steps": int(work[2][0]),
            "page_slots": int(work[2][0]) * per,
            "live_pages": pa.ragged_score_blocks(starts, lens, kv, page,
                                                 bucket, t)[0],
            "max_abs_diff": float(np.abs(got - want).max()),
        }
        print(json.dumps(line), flush=True)
        results.append(line)
    print(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "rehearsal": bool(args.rehearse), "floor_ms": round(floor_s * 1e3, 4),
        "rows": t, "descriptors": slots + 1, "pages_bucket": bucket,
        "sweep": results}))


if __name__ == "__main__":
    main()
