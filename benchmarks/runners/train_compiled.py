"""Runner for cells that train through `CompiledTrainStep`.

The configuration's `builder` says which model class, optimizer, mesh and
trainer arguments; the traffic file says the batch, the sequence length
and the feed.  The build is the one `bench.build_bert_trainer` and
`bench.build_gpt_trainer` make, through the same public entry points.

Measured: steps dispatched back to back, a fresh host batch from a seeded
ring each step, the loss read on the host every `read_every` steps (a
user's logging interval).  `train_samples_per_s` is steps x global batch
over the time between the first and the last read inside the window.
"""
import importlib
import time

import numpy as np


def _resolve(path):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def build(ctx):
    """(model, trainer, mesh shape) from the configuration's `builder`."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.parallel.env import build_mesh
    from paddle_tpu.parallel.hybrid import CompiledTrainStep

    b = ctx.builder
    paddle.seed(ctx.seed)
    model = _resolve(b["model"])(_resolve(b["model_config"])(
        **b["model_args"]))
    opt_args = dict(b["optimizer"])
    opt = getattr(paddle.optimizer, opt_args.pop("class"))(
        parameters=model.parameters(), **opt_args)
    trainer_args = dict(b["trainer"])
    amp = trainer_args.pop("amp_dtype", None)
    trainer = CompiledTrainStep(
        model, lambda m, ids, labels: m.loss(ids, labels), opt,
        build_mesh(b["mesh"]), amp_dtype=getattr(jnp, amp) if amp else None,
        **trainer_args)
    return model, trainer


def run(ctx):
    from benchmarks.harness import loadgen

    b = ctx.builder
    traffic = ctx.traffic
    replicas = int(b["mesh"].get("data", 1))
    ring = loadgen.batches(traffic, ctx.seed,
                           b["model_args"]["vocab_size"], replicas)
    global_batch = ring[0][0].shape[0]

    t0 = time.monotonic()
    model, trainer = build(ctx)
    ctx.clock["weights_s"] = time.monotonic() - t0
    # the plain reference's loss on the initial weights and the first
    # batch, before the trainer's donated steps take the buffers
    params = {n: p._data for n, p in model.named_parameters()}
    want = ctx.module("reference", ctx.config["reference"]).loss(
        params, *ring[0], b["model_args"])
    del params
    ctx.clock["reference_s"] = time.monotonic() - t0 - ctx.clock["weights_s"]

    def read(loss):
        return float(np.asarray(loss._data))

    # warm-up: the first step compiles (or reads the cache); three steps
    # reach the steady allocation pattern
    warm = [read(trainer.step(*ring[i % len(ring)]))
            for i in range(int(traffic["warmup_steps"]))]
    tol = ctx.config["loss_tolerance"]["rel"]
    got = warm[0]
    agrees = abs(got - want) <= tol * abs(want)
    ctx.note(f"step-0 loss: trainer {got:.6f} (bf16 step, dropout on), "
             f"reference {want:.6f} (float32, highest precision, dropout "
             f"off, {ctx.clock['reference_s']:.1f}s of set-up), relative "
             f"difference {abs(got - want) / abs(want):.4f}, tolerance {tol}")

    read_every = int(traffic["read_every"])
    losses, reads = [], []      # reads: (time, steps dispatched so far)
    steps = len(warm)
    window = ctx.open_window()
    while True:
        loss = trainer.step(*ring[steps % len(ring)])
        steps += 1
        if (steps - len(warm)) % read_every == 0:
            with ctx.span("bench::loss_read"):
                losses.append(read(loss))
            now = time.monotonic()
            reads.append((now, steps))
            window.poll(now)
            if window.closed(now):
                break
    window.finish()

    out = {"attempted": steps - len(warm),
           "failed": int(np.sum(~np.isfinite(losses))),
           "losses": losses, "end_to_end": {}}
    if len(reads) >= 3:
        (t_a, n_a), (t_b, n_b) = reads[0], reads[-1]
        out["end_to_end"] = {"train_samples_per_s":
                             (n_b - n_a) * global_batch / (t_b - t_a)}
        out["step_s"] = [(t1 - t0_) / (n1 - n0) for (t0_, n0), (t1, n1)
                         in zip(reads, reads[1:])]
    k = max(1, len(losses) // 5)
    # the last fifth of the losses less the first: nan with fewer than two
    change = float(np.mean(losses[-k:]) - np.mean(losses[:k])) \
        if len(losses) >= 2 else float("nan")
    falls = change < 0
    ctx.note(f"{steps - len(warm)} steps, global batch {global_batch}; loss "
             f"read every {read_every} steps: first {losses[:3]}, last "
             f"{losses[-3:]}; falls: {bool(falls)}")
    out["correct"] = bool(agrees and falls and out["failed"] == 0
                          and np.isfinite(warm).all())
    ctx.checks.update({
        "first_loss_rel_difference": (abs(got - want) / abs(want), tol),
        "loss_last_fifth_minus_first": (change, 0.0),
        "losses_not_finite": (out["failed"], 0)})
    out["global_batch"] = global_batch
    out["data_replicas"] = replicas
    return out
