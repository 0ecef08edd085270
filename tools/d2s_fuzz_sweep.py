#!/usr/bin/env python
"""Wide dy2static property-fuzz sweep (CPU-forced).

The committed suite (tests/test_dy2static_fuzz.py) pins 18 seeds; this
tool sweeps an arbitrary range for pre-commit confidence when touching
the transformer:

    python tools/d2s_fuzz_sweep.py 0 500

Prints one line per failure (seed, exception, message) and a summary;
exit code 1 on any failure.  Always CPU-forced: it never takes the chip.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from test_dy2static_fuzz import _compile_fn, _gen_program  # noqa: E402


def main():
    lo = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    hi = int(sys.argv[2]) if len(sys.argv) > 2 else lo + 100
    xs = [np.linspace(-1.0, 1.0, 6).astype(np.float32).reshape(2, 3),
          -np.ones((2, 3), np.float32),
          np.full((2, 3), 2.0, np.float32)]
    fails = []
    for seed in range(lo, hi):
        src = _gen_program(seed)
        try:
            f = _compile_fn(src)
            eager = [np.asarray(f(paddle.to_tensor(x)).numpy())
                     for x in xs]
            jf = paddle.jit.to_static(_compile_fn(src))
            for x, want in zip(xs, eager):
                got = np.asarray(jf(paddle.to_tensor(x)).numpy())
                np.testing.assert_allclose(got, want, rtol=1e-5,
                                           atol=1e-6)
        except Exception as e:  # noqa: BLE001 — report and continue
            fails.append((seed, type(e).__name__, str(e)[:160]))
            print(f"FAIL seed={seed}: {type(e).__name__}: "
                  f"{str(e)[:160]}", flush=True)
    print(f"{len(fails)} failures of {hi - lo}")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
