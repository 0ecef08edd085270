"""Benchmark: BERT-base pretraining, ResNet-50 static, LeNet dygraph and
GPT-2 + ZeRO throughput on the TPU this process owns.

One process, one import of JAX, one pass over the four configurations
(BASELINE.md configs 1, 2, 3 and the single-host slice of 5).  The last
line of stdout is one JSON record stamped with the device it ran on.  There
is no fallback: no accelerator, a device missing from the peak table, or a
failure in any configuration is a traceback and a non-zero exit.

Reported fields:
- value/unit: headline = BERT-base samples/s/chip (aggregate wall-clock
  over dependent steps, the honest async-dispatch number)
- samples_per_sec_median_synced: per-step host-synced median (latency view)
- mfu: model FLOPs utilization vs the chip's bf16 peak
- extra.resnet50_*, extra.lenet_*, extra.gpt2_*: the other configurations
"""
import json
import time

import numpy as np

# per-chip bf16 peak FLOP/s by device_kind substring (first match wins),
# from the Google Cloud TPU documentation pages of each generation ("TPU
# v5e": 197 TFLOP/s).  Only the v5e row has met a chip in this repo.
_PEAKS = [
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def peak_flops(device):
    """bf16 peak of `device`; a device that is not in the table is an
    error, not a default."""
    kind = device.device_kind.lower()
    for sub, val in _PEAKS:
        if sub in kind:
            return val
    raise RuntimeError(
        f"no peak FLOP/s on record for device_kind "
        f"{device.device_kind!r} (platform {device.platform!r}): add it "
        f"to bench._PEAKS with its source")


def device_stamp(jax):
    """The device every result is stamped with, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator(jax):
    """device_stamp(), or RuntimeError when JAX sees only the CPU: a
    timing from the CPU backend says nothing about this system."""
    stamp = device_stamp(jax)
    if stamp["platform"] == "cpu":
        raise RuntimeError(
            "JAX found no accelerator (platform 'cpu'); this program "
            "measures the chip and has no CPU mode")
    peak_flops(jax.devices()[0])
    return stamp


def _measured_flops(cost, fallback):
    """(flops, source): XLA cost_analysis when it reports FLOPs, else the
    hand model.  cost_analysis counts executed FLOPs (incl. remat, excl.
    embedding gathers), so MFU is not inflated by counting embedding
    tables as matmul params.  `cost` is None for a TPU *lowering* (jax
    0.9.0: only the compiled executable is analysed there), which is why
    chip records read "analytic"."""
    f = cost.get("flops") if cost else None
    if f and f > 0:
        return float(f), "xla_cost_analysis"
    return float(fallback), "analytic"


def _time_steps(step_fn, sync_fn, warmup, iters):
    """(median per-step synced, aggregate per-step over dependent steps)."""
    for _ in range(warmup):
        step_fn()
    sync_fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        step_fn()
    sync_fn()
    agg = (time.perf_counter() - t0) / iters
    times = []
    for _ in range(iters):
        t1 = time.perf_counter()
        step_fn()
        sync_fn()
        times.append(time.perf_counter() - t1)
    return float(np.median(times)), agg


def host_sync(tensor):
    """Wait for `tensor` by reading it on the host."""
    return float(np.asarray(tensor._data))


def build_bert_trainer(mesh, **cfg_overrides):
    """BASELINE config 3: BERT-base, bf16 AMP, AdamW, layers scanned
    (depth-constant HLO: nn/scan_stack.py).  `cfg_overrides` shrink the
    model for a CPU rehearsal.  Returns (cfg, model, trainer)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertForPretraining, BertConfig
    from paddle_tpu.parallel.hybrid import CompiledTrainStep

    cfg = BertConfig(**{"dropout": 0.1, "scan_layers": True,
                        **cfg_overrides})
    paddle.seed(0)
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    trainer = CompiledTrainStep(
        model,
        lambda m, ids, labels: m.loss(ids, labels),
        opt, mesh, amp_dtype=jnp.bfloat16, zero_shard_states=False,
    )
    return cfg, model, trainer


def token_batch(vocab_size, batch, seq):
    """One seeded (ids, labels) batch, staged on the device."""
    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.randint(0, vocab_size, (batch, seq)).astype(np.int32)
    return paddle.to_tensor(ids), paddle.to_tensor(labels)


BERT_BATCH, BERT_SEQ = 64, 128


def bench_bert(jax):
    from paddle_tpu.parallel.env import build_mesh

    batch, seq, warmup, iters = BERT_BATCH, BERT_SEQ, 3, 10
    n_dev = len(jax.devices())
    cfg, model, trainer = build_bert_trainer(build_mesh({"data": n_dev}))
    B = batch * n_dev
    t_ids, t_labels = token_batch(cfg.vocab_size, B, seq)

    holder = {}

    def step():
        holder["loss"] = trainer.step(t_ids, t_labels)

    def sync():
        host_sync(holder["loss"])

    med, agg = _time_steps(step, sync, warmup, iters)

    n_params = sum(int(np.prod(p._data.shape))
                   for p in model.parameters())
    # analytic fallback: 3x fwd; fwd = 2*N*tokens + attention scores
    # (4*B*S^2*H per layer: QK^T and AV, mult+add counted)
    analytic = 3 * (2 * n_params * B * seq
                    + 4 * B * seq * seq * cfg.hidden_size * cfg.num_layers)
    flops, flops_src = _measured_flops(
        trainer.cost_analysis(t_ids, t_labels), analytic)
    # the step is shard_map-lowered, so cost_analysis FLOPs are per-shard
    # (= per device); the analytic model counts the global batch
    per_dev = flops if flops_src == "xla_cost_analysis" else flops / n_dev
    return {
        "samples_per_sec_per_chip": B / agg / n_dev,
        "samples_per_sec_median_synced": B / med / n_dev,
        "step_time_s": agg,
        "flops_per_step": per_dev * n_dev,
        "flops_source": flops_src,
        "mfu": per_dev / agg / peak_flops(jax.devices()[0]),
        "batch": B, "seq": seq, "n_params": n_params,
    }


def _build_static_resnet50(static, batch):
    """ResNet-50 through the static Program/Executor path (config 2).
    Returns (main, startup, loss_var, fwd_flops_per_image)."""
    flops = [0]

    def conv_bn(x, cout, k, stride=1, pad=0, act=None):
        cin = x.shape[1]
        y = static.nn.conv2d(x, cout, k, stride=stride, padding=pad,
                             bias_attr=False)
        flops[0] += 2 * cout * y.shape[2] * y.shape[3] * cin * k * k
        return static.nn.batch_norm(y, act=act)

    def bottleneck(x, width, stride=1, downsample=False):
        out = conv_bn(x, width, 1, act="relu")
        out = conv_bn(out, width, 3, stride=stride, pad=1, act="relu")
        out = conv_bn(out, width * 4, 1)
        if downsample:
            x = conv_bn(x, width * 4, 1, stride=stride)
        return static.nn.relu(out + x)

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        img = static.data("image", [batch, 3, 224, 224])
        label = static.data("label", [batch, 1], dtype="int64")
        x = conv_bn(img, 64, 7, stride=2, pad=3, act="relu")
        x = static.nn.pool2d(x, pool_size=3, pool_type="max", pool_stride=2,
                             pool_padding=1)
        for width, blocks, stride in [(64, 3, 1), (128, 4, 2),
                                      (256, 6, 2), (512, 3, 2)]:
            for i in range(blocks):
                x = bottleneck(x, width, stride=stride if i == 0 else 1,
                               downsample=(i == 0))
        x = static.nn.pool2d(x, global_pooling=True, pool_type="avg")
        x = static.nn.flatten(x, axis=1)
        logits = static.nn.fc(x, 1000)
        flops[0] += 2 * x.shape[1] * 1000
        loss = static.nn.softmax_with_cross_entropy(logits, label)
        loss = static.nn.mean(loss)
        import paddle_tpu as paddle

        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        # static AMP: the perf path the reference ships trains under the
        # mixed-precision program rewrite (decorator.py:37); engage ours
        opt = static.amp.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss, flops[0]


RESNET_BATCH = 64


def resnet_feed(batch):
    """One seeded host batch for the static ResNet-50 program."""
    rng = np.random.RandomState(0)
    return {"image": rng.rand(batch, 3, 224, 224).astype(np.float32),
            "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64)}


def bench_resnet(jax):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.static as static

    batch, chain = RESNET_BATCH, 20
    paddle.seed(0)
    main, startup, loss, fwd_flops = _build_static_resnet50(static, batch)

    exe = static.Executor()
    exe.run(startup)
    host_feed = resnet_feed(batch)
    # the batch is staged on the device ONCE (what the BERT/GPT benches do
    # via to_tensor): these numbers are the step's, not the host feed's
    feed = {k: jnp.asarray(v) for k, v in host_feed.items()}

    # device-side chained steps (Executor.run_chained = DeviceWorker inner
    # loop): n dependent steps in one dispatch.  run_chained returns host
    # numpy, so each timed call is synced end-to-end.
    exe.run_chained(main, feed=feed, fetch_list=[loss],
                    n_steps=chain)  # compile + warmup
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        exe.run_chained(main, feed=feed, fetch_list=[loss], n_steps=chain)
        times.append((time.perf_counter() - t0) / chain)
    agg = min(times)

    # latency view: one dispatch per step, loss synced to host each step
    exe.run(main, feed=feed, fetch_list=[loss])
    stepped = []
    for _ in range(3):
        t0 = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=[loss])
        stepped.append(time.perf_counter() - t0)
    med = sorted(stepped)[len(stepped) // 2]

    flops, flops_src = _measured_flops(
        exe.cost_analysis(main, feed=host_feed, fetch_list=[loss]),
        3 * fwd_flops * batch)
    return {
        "imgs_per_sec_per_chip": batch / agg,
        "imgs_per_sec_median_synced": batch / med,
        "step_time_s": agg,
        "flops_source": flops_src,
        "mfu": flops / agg / peak_flops(jax.devices()[0]),
        "batch": batch, "chain_steps": chain,
    }


def bench_lenet(jax):
    """BASELINE config 1: LeNet/MNIST single-device dygraph (eager tape +
    per-op dispatch — the imperative-path throughput number)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    net = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    B, warmup, iters = 128, 3, 10
    rng = np.random.RandomState(0)
    img = paddle.to_tensor(rng.rand(B, 1, 28, 28).astype(np.float32))
    lbl = paddle.to_tensor(rng.randint(0, 10, (B, 1)).astype(np.int64))

    holder = {}

    def step():
        loss = paddle.mean(F.softmax_with_cross_entropy(net(img), lbl))
        loss.backward()
        opt.step()
        opt.clear_grad()
        holder["loss"] = loss

    def sync():
        host_sync(holder["loss"])  # eager dispatch is async

    med, agg = _time_steps(step, sync, warmup, iters)
    return {"imgs_per_sec": B / agg, "batch": B}


GPT_BATCH, GPT_SEQ = 8, 512


def build_gpt_trainer(mesh, zero_stage, **cfg_overrides):
    """BASELINE config 5 slice: GPT-2 small, Pallas flash attention
    (which needs attn_dropout=0; residual/MLP dropout stays), remat, bf16
    AMP, layers scanned.  `cfg_overrides` shrink the model for a CPU
    rehearsal.  Returns (cfg, model, trainer)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForPretraining, GPTConfig
    from paddle_tpu.parallel.hybrid import CompiledTrainStep

    paddle.seed(0)
    cfg = GPTConfig(**{
        "vocab_size": 50257, "hidden_size": 768, "num_layers": 12,
        "num_heads": 12, "max_seq_len": GPT_SEQ, "dropout": 0.1,
        "attn_dropout": 0.0, "use_flash": True, "scan_layers": True,
        **cfg_overrides})
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    trainer = CompiledTrainStep(model, lambda m, i, l: m.loss(i, l), opt,
                                mesh, amp_dtype=jnp.bfloat16,
                                zero_stage=zero_stage, remat=True)
    return cfg, model, trainer


def bench_gpt_zero(jax):
    """GPT-2 class train step with ZeRO sharding over the available
    devices.  The pipeline-parallel leg of config 5 needs a pod and is
    exercised by the virtual-mesh pipeline tests, not here."""
    from paddle_tpu.parallel.env import build_mesh

    B, L, warmup, iters = GPT_BATCH, GPT_SEQ, 3, 10
    n_dev = len(jax.devices())
    cfg, model, tr = build_gpt_trainer(build_mesh({"data": n_dev}),
                                       zero_stage=3 if n_dev > 1 else 1)
    t_ids, t_lbl = token_batch(cfg.vocab_size, B * n_dev, L)
    holder = {}

    def step():
        holder["loss"] = tr.step(t_ids, t_lbl)

    def sync():
        host_sync(holder["loss"])

    med, agg = _time_steps(step, sync, warmup, iters)
    n_params = sum(int(np.prod(p._data.shape)) for p in model.parameters())
    tokens = B * n_dev * L
    analytic = 3 * (2 * n_params * tokens
                    + 4 * tokens * L * cfg.hidden_size * cfg.num_layers)
    flops, flops_src = _measured_flops(
        tr.cost_analysis(t_ids, t_lbl), analytic)
    # shard_map lowering -> cost_analysis FLOPs are per-device already
    per_dev = flops if flops_src == "xla_cost_analysis" else flops / n_dev
    return {
        "tokens_per_sec_per_chip": tokens / agg / n_dev,
        "flops_source": flops_src,
        "mfu": per_dev / agg / peak_flops(jax.devices()[0]),
        "n_params": n_params,
    }


def _record(stamp, bert, resnet, lenet, gpt):
    return {
        "metric": "bert_base_pretrain_samples_per_sec_per_chip",
        "value": round(bert["samples_per_sec_per_chip"], 2),
        "unit": "samples/s/chip",
        "device": stamp,
        "mfu": round(bert["mfu"], 4),
        "flops_source": bert["flops_source"],
        "samples_per_sec_median_synced": round(
            bert["samples_per_sec_median_synced"], 2),
        "bert_config": {k: bert[k]
                        for k in ("batch", "seq", "n_params",
                                  "step_time_s")},
        "extra": {
            "resnet50_static_imgs_per_sec_per_chip": round(
                resnet["imgs_per_sec_per_chip"], 2),
            "resnet50_imgs_per_sec_median_synced": round(
                resnet["imgs_per_sec_median_synced"], 2),
            "resnet50_mfu": round(resnet["mfu"], 4),
            "resnet50_batch": resnet["batch"],
            "lenet_dygraph_imgs_per_sec": round(lenet["imgs_per_sec"], 2),
            "gpt2_zero_tokens_per_sec_per_chip": round(
                gpt["tokens_per_sec_per_chip"], 2),
            "gpt2_mfu": round(gpt["mfu"], 4),
        },
    }


def main():
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    stamp = require_accelerator(jax)
    enable_compile_cache()
    bert = bench_bert(jax)
    resnet = bench_resnet(jax)
    lenet = bench_lenet(jax)
    gpt = bench_gpt_zero(jax)
    print(json.dumps(_record(stamp, bert, resnet, lenet, gpt)), flush=True)


if __name__ == "__main__":
    main()
