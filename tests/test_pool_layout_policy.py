"""The KV pool's layout follows its reader.

``GenerationConfig.pool_layout=None`` resolves, when the engine is built,
from what the engine knows then: a per-head device pool read by the
Pallas kernels is stored ``[H, P, page_size, D]`` as they consume it
(no step transposes a pool), provided its rows can be written in place
there (`ops.pallas.paged_attention.pool_scatter_in_place`: float32 heads
of 128); host pools, the jnp gather path, any other pool and a latent
pool keep the layout they had.  ``generation.kv_pool_layout`` in
``stats()`` says which.  The kernels run in the Pallas interpreter here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import generation as gen
from paddle_tpu.ops.pallas.paged_attention import (kernel_pool_scatter,
                                                   pool_scatter_in_place)
from paddle_tpu.profiler.monitor import StatRegistry

PAGE = 8


@pytest.fixture(scope="module")
def wide():
    # heads of 128: the width the in-place row writer serves
    return gen.TinyCausalLM(vocab_size=48, num_layers=2, num_heads=2,
                            head_dim=128, max_positions=128, seed=5)


@pytest.fixture(scope="module")
def narrow():
    return gen.TinyCausalLM(vocab_size=48, num_layers=2, num_heads=2,
                            head_dim=8, seed=3)


@pytest.fixture(scope="module")
def latent():
    return gen.LatentMoELM(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2, routed_scaling_factor=1.0, rope_theta=1e4,
        rms_norm_eps=1e-5, dtype="float32", seed=1)


def _build(model, **kw):
    kw.setdefault("page_size", PAGE)
    cfg = gen.GenerationConfig(max_decode_slots=2, num_pages=16, **kw)
    # a registry of its own: the process-wide one adds engines together
    eng = gen.GenerationEngine(
        model, cfg, metrics=gen.GenerationMetrics(StatRegistry()),
        start=False)
    picked = (eng.cache.pool_layout,
              eng.stats()["generation.kv_pool_layout"])
    eng.shutdown()
    return picked


RAGGED = dict(kv_backend="device", step_mode="ragged")


@pytest.mark.parametrize("which,config,stored,stat", [
    # the reader is the Pallas kernel: stored as it reads
    ("wide", dict(RAGGED, use_kernel=True), "kernel", "kernel"),
    ("wide", dict(kv_backend="device", use_kernel=True), "kernel",
     "kernel"),
    # the jnp gather, host pools: the token layout as before
    ("wide", dict(RAGGED, use_kernel=False), "token", "token"),
    ("wide", dict(RAGGED), "token", "token"),      # use_kernel: off-TPU
    ("wide", dict(kv_backend="host", use_kernel=True), "token", "token"),
    ("wide", dict(), "token", "token"),
    # no in-place row writer for this pool: heads of 8, bf16 rows, int8
    ("narrow", dict(RAGGED, use_kernel=True), "token", "token"),
    ("wide", dict(RAGGED, use_kernel=True, kv_dtype=jnp.bfloat16),
     "token", "token"),
    ("wide", dict(RAGGED, use_kernel=True, kv_dtype=np.int8), "token",
     "token"),
    ("wide", dict(RAGGED, use_kernel=True, page_size=4), "token", "token"),
    # an explicit value is obeyed
    ("wide", dict(RAGGED, use_kernel=True, pool_layout="token"), "token",
     "token"),
    ("wide", dict(RAGGED, use_kernel=False, pool_layout="kernel"),
     "kernel", "kernel"),
    ("narrow", dict(RAGGED, use_kernel=True, pool_layout="kernel"),
     "kernel", "kernel"),
    # a latent pool has no head axis: its own layout, named so
    ("latent", dict(use_kernel=True), "token", "latent"),
    ("latent", dict(), "token", "latent"),
])
def test_auto_pool_layout_follows_the_reader(request, which, config,
                                             stored, stat):
    assert _build(request.getfixturevalue(which), **config) == (stored,
                                                                stat)


def test_kernel_layout_is_refused_off_the_device_and_for_a_latent_pool(
        wide, latent):
    with pytest.raises(ValueError, match="kv_backend='device'"):
        _build(wide, kv_backend="host", pool_layout="kernel")
    with pytest.raises(gen.engine.UnsupportedModelPathError,
                       match="pool_layout='kernel'"):
        _build(latent, pool_layout="kernel")


@pytest.mark.parametrize("shape,dtype,served", [
    ((32, 1280, 16, 128), "float32", True),     # the benchmark's pool
    ((8, 4096, 16, 128), "float32", True),      # chip_smoke.py's server
    ((2, 16, 8, 128), "int32", True),
    ((32, 1280, 16, 128), "bfloat16", False),   # two rows share a word
    ((32, 1280, 16, 128), "int8", False),
    ((20, 1280, 16, 64), "float32", False),     # half a lane row
    ((8, 1280, 16, 256), "float32", False),
    ((2, 64, 4, 128), "float32", False),        # half a sublane tile
])
def test_pool_scatter_in_place_says_which_pools(shape, dtype, served):
    assert pool_scatter_in_place(shape, dtype) is served


@pytest.mark.parametrize("n", [1, 17, 150])
def test_kernel_pool_scatter_matches_the_xla_scatter(n):
    """The row DMAs write what ``pool.at[:, pages, rows].set`` writes,
    bit for bit: distinct targets in any order, sentinel pages dropped,
    one window of copies or several."""
    rng = np.random.default_rng(n)
    h, p, d = 4, 30, 128
    pool = jnp.asarray(rng.standard_normal((h, p, PAGE, d), np.float32))
    live = n - n // 5
    flat = rng.permutation(p * PAGE)[:live]
    pages = np.concatenate([flat // PAGE, np.full(n - live, p)])
    rows = np.concatenate([flat % PAGE, rng.integers(0, PAGE, n - live)])
    order = rng.permutation(n)
    pages = pages[order].astype(np.int32)
    rows = rows[order].astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, h, d), np.float32))
    want = pool.at[:, pages, rows].set(jnp.swapaxes(x, 0, 1), mode="drop")
    got = jax.jit(lambda *a: kernel_pool_scatter(*a, interpret=True))(
        pool, pages, rows, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


SYSTEM = list(range(1, 2 * PAGE + 1))       # exactly two pages
PROMPTS = [SYSTEM + [7, 7, 3], [9, 4], SYSTEM + [5], [11, 12, 13, 14, 2],
           SYSTEM]


def _serve(model, pool_layout):
    """A pool small enough to preempt, a shared two-page prefix asked
    three times (hits), once as the whole prompt (the clipped match
    writes into a shared page: copy-on-write), 5-token chunks."""
    cfg = gen.GenerationConfig(
        max_decode_slots=3, num_pages=7, page_size=PAGE,
        prefill_chunk_tokens=5, prefix_cache=True, use_kernel=True,
        pool_layout=pool_layout, **RAGGED)
    eng = gen.GenerationEngine(
        model, cfg, metrics=gen.GenerationMetrics(StatRegistry()),
        start=False)
    first = eng.submit(PROMPTS[0], max_new_tokens=10)
    eng.run_until_idle()
    rest = [eng.submit(p, max_new_tokens=10) for p in PROMPTS[1:]]
    eng.run_until_idle()
    results = [h.result(timeout=5) for h in [first] + rest]
    snap = eng.stats()
    layout = eng.cache.pool_layout
    eng.shutdown()
    return ([r.token_ids for r in results], layout, snap,
            sum(r.preemptions for r in results))


def test_greedy_tokens_identical_under_the_auto_and_the_token_layout(wide):
    """Chunked prefill, prefix hits, a copy-on-write and preemptions
    through the ragged engine: the auto (kernel) layout serves the tokens
    the token layout serves, and the oracle's."""
    auto, layout, snap, preempted = _serve(wide, None)
    token, token_layout, token_snap, _ = _serve(wide, "token")
    assert (layout, token_layout) == ("kernel", "token")
    assert snap["generation.kv_pool_layout"] == "kernel"
    assert token_snap["generation.kv_pool_layout"] == "token"
    assert snap["generation.kernel_path"] == "ragged:pallas"
    assert auto == token
    assert auto[1] == wide.greedy_reference(PROMPTS[1], 10)
    assert snap["generation.prefix_cache_hit_tokens"] >= 2 * len(SYSTEM) - 1
    assert snap["generation.cow_copies"] >= 1
    assert snap["generation.prefill_chunks_total"] > len(PROMPTS)
    assert preempted > 0
