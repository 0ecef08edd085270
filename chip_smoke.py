"""First-contact smoke: does the system still start on the chip?

One process, one pass.  Each phase drives one main path through the entry
points a user would call, at the full width of a model the repo supports
(depth and step counts are cut, weights come from a seed), and checks what
comes out by the repo's own means.  A failed phase is a traceback and a
non-zero exit: nothing here catches and continues.

    python chip_smoke.py              # on a machine with a TPU
    python chip_smoke.py --rehearse   # toy widths on the CPU, interpreted
                                      # kernels; prints REHEARSAL, no JSON

Phases, in rising order of device footprint so that each one can move the
memory high-water marks of the process and so report its own:

    dygraph   LeNet + Adam over DataLoader(MNIST(synthetic)); loss falls
    static    ResNet-50 through static.Executor.run + static.amp, numpy feeds
    kernels   ragged paged attention, Pallas vs jax.numpy, f32 and int8 pools
    gpt       GPT-2 small CompiledTrainStep with the Pallas flash kernel
    server    GenerationEngine over a 12-layer 8x128 TinyCausalLM, every
              policy left to the engine; ten requests, two on a shared prefix
    bert      BERT-base CompiledTrainStep, b64 s128 bf16
    and, with four or more devices:
    gpt_2x2   GPT-2 small on a data2 x model2 mesh, ZeRO-3
    server_tp the server again, tensor-parallel over four chips

Without `--rehearse`, the last line of stdout is
`{"ok": true, "device": {...}}` with the device as JAX reports it.  This is
a smoke, not a benchmark: the seconds it prints say where a run spent its
wall clock (compilation apart from the rest), never how fast the system is.
"""
import argparse
import gc
import importlib.metadata
import json
import logging
import os
import sys
import time

import numpy as np

import bench

FULL = {
    "lenet_samples": 256,
    "resnet_batch": bench.RESNET_BATCH,
    "bert": {"cfg": {}, "batch": bench.BERT_BATCH, "seq": bench.BERT_SEQ},
    "gpt": {"cfg": {}, "batch": bench.GPT_BATCH, "seq": bench.GPT_SEQ},
    "lm": dict(vocab_size=50257, num_layers=12, num_heads=8, head_dim=128,
               mlp_ratio=4, max_positions=2048),
    "engine": dict(num_pages=4096, page_size=16, max_decode_slots=8),
    # every other GenerationConfig option stays None: the engine picks
    "engine_forced": {},
    "prompt_lens": (5, 17, 64, 130, 257, 300, 511, 700),
    "new_tokens": 64,
    "shared_prefix": 256,
    "reference": ((0, 8), (3, 8)),      # (request, tokens) vs greedy_reference
    "kernel_pages": 4096,
    "kernel_kv_lens": (1, 15, 16, 17, 100, 333, 640, 1000, 700),
    "train_steps": 6,
}

# Toy widths for the CPU rehearsal.  On the CPU the engine's auto policies
# pick host pools and the eager path, so the rehearsal names the TPU's
# choices outright; the phase asserts the same facts either way.
TOY = {
    "lenet_samples": 64,
    "resnet_batch": 2,
    "bert": {"cfg": dict(num_layers=2, hidden_size=128, num_heads=2,
                         ffn_hidden=256, vocab_size=1024),
             "batch": 4, "seq": 32},
    "gpt": {"cfg": dict(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=2, max_seq_len=128),
            "batch": 4, "seq": 128},
    "lm": dict(vocab_size=256, num_layers=2, num_heads=4, head_dim=8,
               mlp_ratio=2, max_positions=256),
    "engine": dict(num_pages=128, page_size=4, max_decode_slots=4),
    "engine_forced": dict(kv_backend="device", step_mode="ragged",
                          use_kernel=True, prefill_chunk_tokens=16,
                          prefix_cache=True),
    "prompt_lens": (3, 9, 20, 41),
    "new_tokens": 8,
    "shared_prefix": 16,
    "reference": ((0, 4), (2, 4)),
    "kernel_pages": 64,
    "kernel_kv_lens": (1, 15, 16, 17, 33, 64, 100, 160, 150),
    "train_steps": 4,
}

# Pallas kernel vs the jax.numpy reference at "highest" matmul precision,
# on outputs of unit scale.  The kernel's f32 matmuls take the MXU's
# default precision; one bf16 pass perturbs a logit by ~2^-8, which moves
# an output by ~1e-2 at worst.  A wrong mask, page or scale moves it by
# ~1e-1 to 1.
KERNEL_ATOL = 3e-2


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or reading the
    persistent cache), from its own monitoring events, so a phase can say
    how much of its wall clock was compilation whichever thread did it."""

    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, jax):
        self.trace_s = self.backend_s = 0.0
        self.programs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.TRACE:
            self.trace_s += seconds
        elif event == self.BACKEND:
            self.backend_s += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def read(self):
        return np.array([self.trace_s, self.backend_s, self.programs,
                         self.hits, self.misses])


def _memory_line(jax, before):
    """Per-device memory after a phase.  Both peaks are high-water marks
    of the process; the phases run in rising order of footprint so that
    each can move them.  On this runtime `peak_bytes_in_use` counts live
    arrays and `peak_bytes_reserved` what running executables reserved
    for their temporaries."""
    stats = [d.memory_stats() for d in jax.devices()]
    if stats[0] is None:
        print("  memory: not reported by this backend")
        return before
    peaks = (stats[0]["peak_bytes_in_use"], stats[0]["peak_bytes_reserved"])
    moved = ["raised by this phase" if now > was else "set earlier"
             for now, was in zip(peaks, before)]
    print(f"  memory, dev0: peak_bytes_in_use {peaks[0] / 2**30:.2f} GiB "
          f"({moved[0]}), peak_bytes_reserved {peaks[1] / 2**30:.2f} GiB "
          f"({moved[1]}); in use now "
          + ", ".join(f"dev{d.id} {s['bytes_in_use'] / 2**30:.2f}"
                      for d, s in zip(jax.devices(), stats)) + " GiB")
    return peaks


def _shard_evidence(label, array, n_devices):
    """Print where `array` lives and require that it is split over
    `n_devices` devices, not stacked on one or copied to all."""
    shards = array.addressable_shards
    print(f"  {label}: global {tuple(array.shape)} {array.dtype}, "
          f"{array.sharding}")
    for s in shards:
        print(f"    dev{s.device.id}: {tuple(s.data.shape)} at {s.index}")
    devices = {s.device.id for s in shards}
    assert len(devices) == n_devices, (label, devices)
    assert len({str(s.index) for s in shards}) > 1, \
        f"{label} is replicated, not sharded"


def _spread_evidence(jax, n_devices):
    """Per-device memory while a sharded phase's arrays are alive: every
    one of the `n_devices` chips holds a share, none holds it all."""
    stats = [d.memory_stats() for d in jax.devices()[:n_devices]]
    if stats[0] is None:
        print("  memory: not reported by this backend")
        return
    used = [s["bytes_in_use"] for s in stats]
    print("  memory in use while sharded state is alive: "
          + ", ".join(f"dev{i} {b / 2**30:.2f}" for i, b in enumerate(used))
          + " GiB")
    assert min(used) > 0.25 * max(used), used


def _losses_fall(name, losses):
    print(f"  {name} losses: " + " ".join(f"{v:.4f}" for v in losses))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"{name}: loss did not fall: {losses}"


# --------------------------------------------------------------- phases

def phase_dygraph(jax, size):
    """LeNet + Adam over a DataLoader, eagerly (the verify skill's flow)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.io import DataLoader
    from paddle_tpu.vision.datasets import MNIST
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    net = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    data = MNIST(mode="train", synthetic_size=size["lenet_samples"])
    losses = []
    for _ in range(2):
        for img, lbl in DataLoader(data, batch_size=32):
            loss = paddle.mean(F.softmax_with_cross_entropy(net(img), lbl))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss._data)))
    half = len(losses) // 2
    _losses_fall("LeNet epoch-mean",
                 [np.mean(losses[:half]), np.mean(losses[half:])])


def phase_static(jax, size):
    """ResNet-50 through static.Executor.run with static.amp.

    A numpy feed every step (bench.py stages its batch on the device
    once)."""
    import paddle_tpu as paddle
    import paddle_tpu.static as static
    from paddle_tpu import native

    print("  planner: " + (
        "native (C++)" if native.available() else
        f"python — native build failed: {native.build_error()}"))
    batch = size["resnet_batch"]
    paddle.seed(0)
    main, startup, loss, _ = bench._build_static_resnet50(static, batch)
    exe = static.Executor()
    exe.run(startup)
    feed = bench.resnet_feed(batch)
    losses = []
    for _ in range(4):
        t0 = time.perf_counter()
        out, = exe.run(main, feed=feed, fetch_list=[loss])   # host numpy
        losses.append(out.item())
        print(f"    step {len(losses)}: loss {losses[-1]:.4f} "
              f"({time.perf_counter() - t0:.2f}s wall)")
    assert np.isfinite(losses).all(), losses


def phase_kernels(jax, size):
    """Ragged paged attention: Pallas kernel vs its jax.numpy reference.

    One seeded mixed batch — eight decode rows and one 64-row prefill
    chunk over a pool as wide as the server's — for float32 and int8
    pools."""
    import jax.numpy as jnp

    from paddle_tpu.generation import (ragged_paged_attention,
                                       ragged_paged_attention_reference)

    heads, dim = FULL["lm"]["num_heads"], FULL["lm"]["head_dim"]
    pages, page_size = size["kernel_pages"], 16
    kv_lens = np.array(size["kernel_kv_lens"])
    lens = np.array([1] * 8 + [64])
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    max_pages = int(-(-kv_lens.max() // page_size))
    rng = np.random.default_rng(0)
    # distinct pages per sequence, drawn from the whole pool; unused table
    # slots point at page 0 as the engine pads them
    perm = rng.permutation(np.arange(1, pages))
    tables = np.zeros((len(kv_lens), max_pages), np.int32)
    used = 0
    for s, n in enumerate(-(-kv_lens // page_size)):
        tables[s, :n] = perm[used:used + n]
        used += n
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (int(lens.sum()), heads, dim))
    shape = (pages, page_size, heads, dim)
    pools = {
        "float32": (jax.random.normal(keys[1], shape),
                    jax.random.normal(keys[2], shape), {}),
        "int8": (jax.random.randint(keys[1], shape, -127, 128, jnp.int8),
                 jax.random.randint(keys[2], shape, -127, 128, jnp.int8),
                 {"k_scale": jax.random.uniform(keys[3], (pages, heads),
                                                minval=0.5, maxval=2.0),
                  "v_scale": jax.random.uniform(keys[4], (pages, heads),
                                                minval=0.5, maxval=2.0)}),
    }
    for name, (kp, vp, scales) in pools.items():
        args = (q, kp, vp, tables, starts, lens, kv_lens)
        got = jax.jit(lambda *a, **kw: ragged_paged_attention(
            *a, use_kernel=True, **kw))(*args, **scales)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ragged_paged_attention_reference)(*args, **scales)
        got, want = np.asarray(got), np.asarray(want)
        err = np.abs(got - want)
        print(f"  ragged kernel vs reference, {name} pools "
              f"[{pages}x{page_size}x{heads}x{dim}]: max |diff| "
              f"{err.max():.2e}, mean {err.mean():.2e}, output rms "
              f"{np.sqrt(np.mean(want ** 2)):.2f} (atol {KERNEL_ATOL})")
        assert np.isfinite(got).all()
        assert err.max() <= KERNEL_ATOL, (name, err.max())


def _train(trainer, batch, steps):
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        losses.append(bench.host_sync(trainer.step(*batch)))
        print(f"    step {i + 1}: loss {losses[-1]:.4f} "
              f"({time.perf_counter() - t0:.2f}s wall)")
    # memory_stats' peak counts live arrays only; the step's temporaries
    # are in XLA's analysis of the executable (a cache read by now)
    mem = trainer.memory_analysis(*batch)
    print("  compiled step, per device: "
          + ("memory analysis not reported" if mem is None else
             f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, "
             f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB"))
    return losses


def phase_bert(jax, size):
    """BERT-base CompiledTrainStep (the build of bench.bench_bert)."""
    from paddle_tpu.parallel.env import build_mesh

    spec = size["bert"]
    cfg, _, trainer = bench.build_bert_trainer(build_mesh({"data": 1}),
                                               **spec["cfg"])
    batch = bench.token_batch(cfg.vocab_size, spec["batch"], spec["seq"])
    _losses_fall("BERT", _train(trainer, batch, size["train_steps"]))


def phase_gpt(jax, size, mesh_shape=None, zero_stage=1):
    """GPT-2 small CompiledTrainStep with the Pallas flash kernel.

    The build of bench.bench_gpt_zero.  The step must hold the kernel,
    not the composite path ops/attention.py drops to without a word."""
    from paddle_tpu.parallel.env import build_mesh

    mesh_shape = mesh_shape or {"data": 1}
    spec = size["gpt"]
    cfg, _, trainer = bench.build_gpt_trainer(
        build_mesh(mesh_shape), zero_stage, **spec["cfg"])
    batch = bench.token_batch(
        cfg.vocab_size, spec["batch"] * mesh_shape["data"], spec["seq"])
    calls = trainer.lowered_text(*batch).count("tpu_custom_call")
    print(f"  Mosaic custom calls in the lowered step: {calls}")
    if jax.default_backend() == "tpu":
        assert calls > 0, "flash attention fell back to the composite path"
    _losses_fall("GPT-2", _train(trainer, batch, size["train_steps"]))
    n_dev = int(np.prod(list(mesh_shape.values())))
    if n_dev > 1:
        _spread_evidence(jax, n_dev)
        _shard_evidence("parameter buffer (ZeRO-3)", trainer.params, n_dev)
        name, leaf = next((k, v) for k, v in trainer.flat_opt_state.items()
                          if v.ndim)
        _shard_evidence(f"optimizer state {name!r}", leaf, n_dev)


def phase_server(jax, size, tp=None):
    """A GenerationEngine that answers ten requests.

    The engine is built the way a deployment builds it — model, pool
    size, slots — and picks its own path; this phase requires the pick to
    be the ragged Pallas step over device pools with chunking and the
    prefix cache on, the pools stored as the kernel reads them wherever
    their rows can be written in place there (the full size's float32
    heads of 128; the toy's heads of 8 keep the token layout)."""
    from paddle_tpu import generation as g
    from paddle_tpu.ops.pallas.paged_attention import pool_scatter_in_place
    from paddle_tpu.parallel.env import tp_mesh
    from paddle_tpu.profiler.monitor import StatRegistry

    model = g.TinyCausalLM(**size["lm"], seed=0)
    mesh = {"mesh": tp_mesh(tp)} if tp else {}
    config = g.GenerationConfig(**size["engine"], **size["engine_forced"],
                                **mesh)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, n).tolist()
               for n in size["prompt_lens"]]
    new = size["new_tokens"]
    # a registry of its own: the process-wide one adds engines together
    metrics = g.GenerationMetrics(StatRegistry())
    with g.GenerationEngine(model, config, metrics=metrics) as engine:
        stats = engine.stats()
        print(f"  engine picked: step_mode={engine.step_mode} "
              f"kernel_path={stats['generation.kernel_path']} "
              f"pools={type(engine.cache).__name__}"
              f"[{stats['generation.kv_pool_layout']} layout, "
              f"{engine.cache.dtype}] "
              f"chunk={engine.prefill_chunk_tokens} "
              f"prefix_cache={engine.prefix_cache_enabled} "
              f"tp={engine.tp_degree}")
        assert engine.step_mode == "ragged"
        assert stats["generation.kernel_path"] == "ragged:pallas"
        assert isinstance(engine.cache, g.DeviceKVPool)
        in_place = pool_scatter_in_place(
            (model.num_heads, config.num_pages, config.page_size,
             model.head_dim), engine.cache.dtype)
        assert (stats["generation.kv_pool_layout"]
                == engine.cache.pool_layout
                == ("kernel" if in_place else "token"))
        assert engine.prefill_chunk_tokens > 0
        assert engine.prefix_cache_enabled
        handles = [engine.submit(p, max_new_tokens=new) for p in prompts]
        # a compile or runtime error inside the worker reaches the handle:
        # result() raises it
        results = [h.result(timeout=900) for h in handles]
        # the shared prefix belongs to a prompt whose pages are registered
        # by now; two new requests extend it differently
        shared = size["shared_prefix"]
        donor = next(p for p in prompts if len(p) > shared)
        late = [donor[:shared] + rng.integers(0, model.vocab_size, 9).tolist()
                for _ in range(2)]
        late_handles = [engine.submit(p, max_new_tokens=new) for p in late]
        results += [h.result(timeout=900) for h in late_handles]
        for r in results:
            assert len(r.token_ids) == new, r
            assert all(0 <= t < model.vocab_size for t in r.token_ids), r
        hits = [h.prefix_hit_tokens for h in late_handles]
        print(f"  {len(results)} requests x {new} tokens; prefix-hit tokens "
              f"of the two late requests: {hits}")
        assert all(h and h > 0 for h in hits), hits
        if tp:
            _spread_evidence(jax, tp)
            _shard_evidence("KV pool, layer 0 keys",
                            engine.cache.layer_pools(0)[0], tp)
        stats = engine.stats()
        print("  engine counters: " + ", ".join(
            f"{k} {stats['generation.' + k]}" for k in (
                "steps_total", "prefill_chunks_total", "tokens_total",
                "decode_compiles_total", "prefix_cache_hit_tokens")))
    # the oracle recomputes the whole prefix per token, eagerly, one new
    # shape a step: a few tokens of two requests are what a smoke affords
    for index, n in size["reference"]:
        want = model.greedy_reference(prompts[index], n)
        got = results[index].token_ids[:n]
        agree = next((i for i, (a, b) in enumerate(zip(got, want))
                      if a != b), n)
        print(f"  vs greedy_reference, prompt of {len(prompts[index])} "
              f"tokens: first {agree} of {n} tokens agree")
        # a wrong mask or page table shows in the first token; a tie
        # broken differently under bf16-pass matmuls shows late, if ever
        assert agree >= n // 2, (got, want)


PHASES = {
    "dygraph": phase_dygraph,
    "static": phase_static,
    "kernels": phase_kernels,
    "gpt": phase_gpt,
    "server": phase_server,
    "bert": phase_bert,
}


def phase_gpt_2x2(jax, size):
    """GPT-2 small on a data2 x model2 mesh with ZeRO-3."""
    phase_gpt(jax, size, {"data": 2, "model": 2}, zero_stage=3)


def phase_server_tp(jax, size):
    """The server again, tensor-parallel over four chips."""
    phase_server(jax, size, tp=4)


FOUR_CHIP_PHASES = {"gpt_2x2": phase_gpt_2x2, "server_tp": phase_server_tp}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the CPU with interpreted kernels")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="  [%(name)s] %(message)s")
    logging.getLogger("jax").setLevel(logging.WARNING)
    stamp = bench.device_stamp(jax)
    print(f"chip_smoke: jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}; device {stamp}")
    if not args.rehearse:
        bench.require_accelerator(jax)
    print(f"  compile cache: {enable_compile_cache()}")

    phases = dict(PHASES)
    if stamp["count"] >= 4:
        phases.update(FOUR_CHIP_PHASES)
    size = TOY if args.rehearse else FULL

    clock = CompileClock(jax)
    t_start = time.perf_counter()
    peaks = (0, 0)
    for name, phase in phases.items():
        print(f"=== {name}: {phase.__doc__.splitlines()[0]}")
        before, t0 = clock.read(), time.perf_counter()
        phase(jax, size)
        wall = time.perf_counter() - t0
        trace_s, backend_s, programs, hits, misses = clock.read() - before
        print(f"--- {name} passed: wall {wall:.1f}s, of which tracing and "
              f"lowering {trace_s:.1f}s and compiling {backend_s:.1f}s "
              f"({int(programs)} programs; persistent cache {int(hits)} "
              f"hits, {int(misses)} misses), the rest "
              f"{max(wall - trace_s - backend_s, 0.0):.1f}s")
        gc.collect()
        peaks = _memory_line(jax, peaks)
    total = time.perf_counter() - t_start
    trace_s, backend_s, programs, hits, misses = clock.read()
    print(f"all {len(phases)} phases passed in {total:.1f}s: compiling "
          f"{backend_s:.1f}s over {int(programs)} programs (persistent cache "
          f"{int(hits)} hits, {int(misses)} misses), tracing and lowering "
          f"{trace_s:.1f}s")
    if args.rehearse:
        print("REHEARSAL: toy widths on the CPU; says nothing of the chip")
    else:
        print("PASS")
        print(json.dumps({"ok": True, "device": stamp}), flush=True)


if __name__ == "__main__":
    main()
