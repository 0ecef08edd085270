"""CPU perf rails: committed numbers that catch regressions without TPU.

A regression signal that needs no chip.  This tool measures (a) the op_bench
jitted-op latencies and (b) compile-time rails — time-to-first-step for
12-layer BERT/GPT CompiledTrainSteps, scan_layers on vs off (the
scan-vs-unrolled compile claim in docs/PERF.md) — and writes
BENCH_CPU_RAILS.json at the repo root.  tests/test_perf_rails.py
re-measures a fast subset and fails on >2x regressions vs the committed
file.

Run:  python tools/cpu_rails.py          # refresh the committed rails
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


RAILS_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_CPU_RAILS.json")

OP_SUITE = [
    {"op": "matmul", "shapes": [[512, 512], [512, 512]], "repeat": 20},
    {"op": "elementwise_add", "shapes": [[2048, 512], [2048, 512]],
     "repeat": 30},
    {"op": "softmax", "shapes": [[256, 512]], "repeat": 30},
    {"op": "reduce_sum", "shapes": [[2048, 512]], "repeat": 30},
    {"op": "layer_norm", "shapes": [[256, 512]], "repeat": 20},
    {"op": "conv2d", "shapes": [[4, 32, 28, 28], [32, 32, 3, 3]],
     "repeat": 10},
]


def measure_ops(repeat_scale=1.0):
    from tools.op_bench import bench_one

    out = {}
    for cfg in OP_SUITE:
        cfg = dict(cfg)
        cfg["repeat"] = max(3, int(cfg["repeat"] * repeat_scale))
        rec = bench_one(cfg)
        out[rec["op"]] = {"jit_us": rec["jit_us"],
                          "eager_us": rec["eager_us"]}
    return out


def time_to_first_step(model_kind, scan_layers, num_layers=12, hidden=256):
    """Seconds from trainer construction to the first completed step —
    dominated by trace+compile; the scan_layers rail keeps the
    'depth-constant HLO compiles ~3x faster' claim measured."""
    import paddle_tpu as paddle
    from paddle_tpu.parallel.env import build_mesh
    from paddle_tpu.parallel.hybrid import CompiledTrainStep

    paddle.seed(0)
    if model_kind == "bert":
        from paddle_tpu.models.bert import BertForPretraining, BertConfig

        cfg = BertConfig(vocab_size=1024, hidden_size=hidden,
                         num_layers=num_layers, num_heads=4,
                         ffn_hidden=hidden * 4, dropout=0.0,
                         scan_layers=scan_layers)
        model = BertForPretraining(cfg)
    else:
        from paddle_tpu.models.gpt import GPTForPretraining, GPTConfig

        cfg = GPTConfig(vocab_size=1024, hidden_size=hidden,
                        num_layers=num_layers, num_heads=4,
                        max_seq_len=64, dropout=0.0,
                        scan_layers=scan_layers)
        model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    mesh = build_mesh({"data": 1})
    tr = CompiledTrainStep(model, lambda m, i, l: m.loss(i, l), opt, mesh)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    t0 = time.perf_counter()
    loss = tr.step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    float(np.asarray(loss._data))
    return time.perf_counter() - t0


def measure_compile():
    return {
        "bert12_scan_s": round(time_to_first_step("bert", True), 2),
        "bert12_noscan_s": round(time_to_first_step("bert", False), 2),
        "gpt12_scan_s": round(time_to_first_step("gpt", True), 2),
    }


def main():
    import datetime

    os.environ["JAX_PLATFORMS"] = "cpu"  # these rails are the CPU's
    import jax

    rails = {
        "schema": 1,
        "date": datetime.date.today().isoformat(),
        "jax": jax.__version__,
        "ops": measure_ops(),
        "compile": measure_compile(),
    }
    with open(RAILS_PATH, "w") as f:
        json.dump(rails, f, indent=1, sort_keys=True)
    print(json.dumps(rails, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
