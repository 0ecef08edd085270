#!/usr/bin/env python
"""First-contact probes on the chip (PR 21): the facts chip_smoke.py does
not check, printed for PERF.md.  One process; no accelerator is an error.

    chiprun -- python tools/first_contact_probe.py

- the two legacy paged kernels (`step_mode="legacy"`) against their
  references at the server's width; this section goes when the kernels go
  (ROADMAP queue 3 item 2)
- does `block_until_ready` wait as long as a host read does?
- static ResNet-50: one dispatch per step against `run_chained`, a staged
  batch against a numpy feed each step
- does BERT-base b128 s128 fit one chip?

The seconds printed are observations of one run, not metrics.
"""
import gc
import os
import sys
import time

import numpy as np

# runnable as `python tools/first_contact_probe.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from chip_smoke import KERNEL_ATOL  # noqa: E402

HEADS, HEAD_DIM, NUM_PAGES, PAGE_SIZE = 8, 128, 4096, 16


def legacy_kernels(jax):
    """Legacy decode and chunk kernels vs their jax.numpy references.

    f32 and int8 pools; a kernel outside the tolerance raises."""
    import jax.numpy as jnp

    from paddle_tpu.generation import (chunk_prefill_attention,
                                       paged_decode_attention,
                                       paged_decode_attention_reference)

    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    shape = (NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM)
    scale = dict(shape=(NUM_PAGES, HEADS), minval=0.5, maxval=2.0)
    pools = {
        "float32": (jax.random.normal(keys[0], shape),
                    jax.random.normal(keys[1], shape), {}),
        "int8": (jax.random.randint(keys[0], shape, -127, 128, jnp.int8),
                 jax.random.randint(keys[1], shape, -127, 128, jnp.int8),
                 {"k_scale": jax.random.uniform(keys[2], **scale),
                  "v_scale": jax.random.uniform(keys[3], **scale)}),
    }
    seq_lens = np.array([1, 15, 16, 17, 100, 333, 640, 1000], np.int32)
    pages_of = -(-seq_lens // PAGE_SIZE)
    perm = np.random.default_rng(0).permutation(np.arange(1, NUM_PAGES))
    tables = np.zeros((len(seq_lens), pages_of.max()), np.int32)
    used = 0
    for s, n in enumerate(pages_of):
        tables[s, :n] = perm[used:used + n]
        used += n
    q_decode = jax.random.normal(keys[4], (len(seq_lens), HEADS, HEAD_DIM))
    q_chunk = jax.random.normal(keys[5], (64, HEADS, HEAD_DIM))
    start = 636     # the chunk's rows sit at 636..699 of sequence 7's pages

    def decode(use_kernel):
        if not use_kernel:
            return paged_decode_attention_reference
        return lambda *a, **kw: paged_decode_attention(
            *a, use_kernel=True, **kw)

    def chunk(use_kernel):
        return lambda *a, **kw: chunk_prefill_attention(
            *a, use_kernel=use_kernel, **kw)

    for name, (kp, vp, scales) in pools.items():
        for label, build, args in (
                ("decode", decode, (q_decode, kp, vp, tables, seq_lens)),
                ("chunk", chunk, (q_chunk, kp, vp, tables[7], start))):
            got = np.asarray(jax.jit(build(True))(*args, **scales))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(jax.jit(build(False))(*args, **scales))
            err = np.abs(got - want)
            print(f"legacy {label} kernel, {name} pools: max |diff| "
                  f"{err.max():.2e}, mean {err.mean():.2e} "
                  f"(atol {KERNEL_ATOL})", flush=True)
            if not (np.isfinite(got).all() and err.max() <= KERNEL_ATOL):
                raise AssertionError(f"legacy {label} kernel, {name} pools")


def sync_probe(jax):
    """Is block_until_ready a sync?

    20 GPT-2 small steps, waited for by it or by a host read: if it
    returned early, the host read would take longer."""
    from paddle_tpu.parallel.env import build_mesh

    cfg, _, trainer = bench.build_gpt_trainer(build_mesh({"data": 1}), 1)
    batch = bench.token_batch(cfg.vocab_size, bench.GPT_BATCH, bench.GPT_SEQ)
    for _ in range(3):
        bench.host_sync(trainer.step(*batch))
    for how in ("block_until_ready", "host read") * 2:
        t0 = time.perf_counter()
        for _ in range(20):
            loss = trainer.step(*batch)
        enqueued = time.perf_counter() - t0
        if how == "block_until_ready":
            loss._data.block_until_ready()
        else:
            bench.host_sync(loss)
        synced = time.perf_counter() - t0
        t1 = time.perf_counter()
        bench.host_sync(loss)
        print(f"20 steps, waited for by {how}: enqueued in "
              f"{enqueued * 1e3:.1f} ms, done in {synced * 1e3:.1f} ms, a "
              f"host read after that {(time.perf_counter() - t1) * 1e3:.2f} "
              f"ms", flush=True)


def _timed(fn, n):
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return np.array(times)


def resnet_dispatch(jax):
    """Static ResNet-50 b64: exe.run per step against run_chained(20).

    Per step with a staged batch, then with a numpy feed each step.
    Every call returns host numpy, so each is synced."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.static as static

    paddle.seed(0)
    main, startup, loss, _ = bench._build_static_resnet50(
        static, bench.RESNET_BATCH)
    exe = static.Executor()
    exe.run(startup)
    host = bench.resnet_feed(bench.RESNET_BATCH)
    staged = {k: jnp.asarray(v) for k, v in host.items()}
    chain = 20
    exe.run(main, feed=staged, fetch_list=[loss])
    exe.run(main, feed=host, fetch_list=[loss])
    exe.run_chained(main, feed=staged, fetch_list=[loss], n_steps=chain)
    rows = {
        "exe.run, staged batch": _timed(
            lambda: exe.run(main, feed=staged, fetch_list=[loss]), 20),
        "exe.run, numpy feed each step": _timed(
            lambda: exe.run(main, feed=host, fetch_list=[loss]), 20),
        f"run_chained({chain}), per step": _timed(
            lambda: exe.run_chained(main, feed=staged, fetch_list=[loss],
                                    n_steps=chain), 5) / chain,
    }
    for name, t in rows.items():
        print(f"{name}: median {np.median(t) * 1e3:.2f} ms/step (min "
              f"{t.min() * 1e3:.2f}, max {t.max() * 1e3:.2f}, n={len(t)})",
              flush=True)


def bert_b128(jax):
    """Does BERT-base b128 s128 fit one chip?  (bench.py runs b64.)

    Compiles and steps, or prints the compiler's refusal."""
    from paddle_tpu.parallel.env import build_mesh

    cfg, _, trainer = bench.build_bert_trainer(build_mesh({"data": 1}))
    batch = bench.token_batch(cfg.vocab_size, 128, bench.BERT_SEQ)
    try:
        losses = [bench.host_sync(trainer.step(*batch)) for _ in range(2)]
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        print("BERT b128 does not fit:", str(e)[:1500])
    else:
        print(f"BERT b128 compiled and stepped, losses {losses}; "
              f"{trainer.memory_analysis(*batch)}")


def main():
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    print("device", bench.require_accelerator(jax))
    enable_compile_cache()
    for probe in (legacy_kernels, sync_probe, resnet_dispatch, bert_b128):
        print(f"\n##### {probe.__name__}: {probe.__doc__.splitlines()[0]}",
              flush=True)
        probe(jax)
        gc.collect()    # the next probe gets the device memory back
        print("memory_stats:", jax.devices()[0].memory_stats(), flush=True)


if __name__ == "__main__":
    main()
