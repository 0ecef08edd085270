#!/usr/bin/env python
"""Config-driven single-op timing harness.

Reference: paddle/fluid/operators/benchmark/op_tester.cc (+op_tester.cfg):
time one op from a config of {op, shapes, dtype, repeat}.  TPU-native: each
op is timed twice — eager (per-call dispatch, tracer path) and jitted
(compiled, what production steps see) — with block_until_ready fencing.

Usage:
    python tools/op_bench.py                      # built-in suite
    python tools/op_bench.py --config ops.json    # custom suite
    python tools/op_bench.py --op matmul --shape 1024x1024 --repeat 50
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python tools/op_bench.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


DEFAULT_SUITE = [
    {"op": "matmul", "shapes": [[1024, 1024], [1024, 1024]], "repeat": 30},
    {"op": "elementwise_add", "shapes": [[4096, 1024], [4096, 1024]],
     "repeat": 50},
    {"op": "softmax", "shapes": [[256, 1024]], "repeat": 50},
    {"op": "reduce_sum", "shapes": [[4096, 1024]], "repeat": 50},
    {"op": "relu", "shapes": [[4096, 1024]], "repeat": 50},
    {"op": "layer_norm", "shapes": [[256, 1024]], "repeat": 30},
    {"op": "conv2d", "shapes": [[8, 64, 56, 56], [64, 64, 3, 3]],
     "repeat": 10},
    # attention-shaped batched matmul (scores: [B*H, S, d] x [B*H, d, S])
    {"op": "matmul", "shapes": [[96, 512, 64], [96, 64, 512]],
     "repeat": 20},
    {"op": "gelu", "shapes": [[4096, 1024]], "repeat": 50},
    {"op": "tanh", "shapes": [[4096, 1024]], "repeat": 50},
    {"op": "transpose", "shapes": [[64, 12, 128, 64]], "repeat": 30,
     "kwargs": {"perm": [0, 2, 1, 3]}},
]


def _resolve(op_name):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    if op_name == "layer_norm":
        def ln(x):
            return F.layer_norm(x, x.shape[-1:])

        return ln
    if op_name == "conv2d":
        return lambda x, w: F.conv2d(x, w, None, padding=1)
    fn = getattr(paddle, op_name, None) or getattr(F, op_name, None)
    if fn is None:
        # reference registry names (reduce_sum, ...) live in _C_ops
        from paddle_tpu import _C_ops

        try:
            fn = getattr(_C_ops, op_name)
        except NotImplementedError as e:
            raise SystemExit(str(e)) from e  # absent-with-rationale
        except AttributeError:
            raise SystemExit(f"unknown op {op_name!r}")
    return fn


def bench_one(cfg):
    import jax

    import paddle_tpu as paddle

    op = _resolve(cfg["op"])
    rng = np.random.RandomState(0)
    dtype = cfg.get("dtype", "float32")
    kwargs = dict(cfg.get("kwargs", {}))
    args = [paddle.to_tensor(rng.randn(*s).astype(dtype))
            for s in cfg["shapes"]]
    repeat = int(cfg.get("repeat", 30))

    def run_eager():
        out = op(*args, **kwargs)
        jax.block_until_ready(out._data if hasattr(out, "_data") else out)

    raw = None if kwargs else getattr(op, "raw_fn", None)
    if raw is None:
        # wrapper ops without a registered raw kernel: jit the whole
        # eager call over raw arrays (Tensors wrap tracers fine)
        from paddle_tpu.core import autograd
        from paddle_tpu.core.tensor import _wrap_data

        def raw(*vs):
            with autograd.no_grad():
                out = op(*[_wrap_data(v) for v in vs], **kwargs)
            return out._data if hasattr(out, "_data") else out

    arrs = [a._data for a in args]
    jitted = jax.jit(raw)

    run_eager()  # warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        run_eager()
    eager_us = (time.perf_counter() - t0) / repeat * 1e6

    jit_us = None
    jit_error = None
    try:
        jax.block_until_ready(jitted(*arrs))  # compile
        t0 = time.perf_counter()
        for _ in range(repeat):
            jax.block_until_ready(jitted(*arrs))
        jit_us = (time.perf_counter() - t0) / repeat * 1e6
    except Exception as e:  # host-side/untraceable op: eager timing only,
        # but record WHY so kernel regressions stay distinguishable
        jit_error = f"{type(e).__name__}: {e}"[:200]

    rec = {"op": cfg["op"], "shapes": cfg["shapes"], "dtype": dtype,
           "repeat": repeat, "eager_us": round(eager_us, 2),
           "jit_us": round(jit_us, 2) if jit_us is not None else None}
    if jit_error:
        rec["jit_error"] = jit_error
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="json list of op configs")
    ap.add_argument("--op")
    ap.add_argument("--shape", help="AxB[,CxD...] per input")
    ap.add_argument("--repeat", type=int, default=30)
    args = ap.parse_args()
    if args.op:
        shapes = [[int(d) for d in s.split("x")]
                  for s in (args.shape or "256x256").split(",")]
        suite = [{"op": args.op, "shapes": shapes, "repeat": args.repeat}]
    elif args.config:
        with open(args.config) as f:
            suite = json.load(f)
    else:
        suite = DEFAULT_SUITE
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for cfg in suite:
        print(json.dumps(bench_one(cfg)))


if __name__ == "__main__":
    sys.exit(main())
