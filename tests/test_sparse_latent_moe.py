"""SparseLatentMoELM (Hy4-preview's block kind) through the serving
engine, against the plain reference `benchmarks/reference/hy_v4.py`.

Tiny preset, float32 weights, CPU.  The indexer keeps 8 of up to 45
tokens (`index_topk` far under the context) so that selection really
drops tokens; the served path (absorbed attention over the selected
rows of a latent page pool, the index keys beside it, four residual
streams, experts held of a wider router) and the reference (expanded
attention under a selection mask, one pass) then differ by float32
summation order alone, and the tolerances are tight.  The kernels run
in the Pallas interpreter.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import hy_v4 as reference
from paddle_tpu import generation as g
from paddle_tpu.generation import blocks, decode_attention
from paddle_tpu.generation.kv_cache import SparseLatentRows
from paddle_tpu.ops.pallas import sparse_latent_attention as sla

ARGS = dict(vocab_size=211, hidden_size=64, num_layers=4, num_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=4, router_width=8,
            experts_held=[0, 4], num_experts_per_tok=2,
            routed_scaling_factor=2.827, swiglu_limit=10.0,
            index_n_heads=4, index_head_dim=16, index_topk=8,
            indexer_types=["full", "full", "shared", "shared"], hc_mult=4,
            hc_magnitude=2.0, hc_eps=1e-6, rope_theta=1e7,
            rms_norm_eps=1e-5)
SHAPE = dict(ARGS)
PAGE = 4
# float32 everywhere and the same products regrouped: logits of order 1
# agree to a few 1e-6; 2e-4 leaves room for the longest sums and still
# catches a wrong term (a dropped or extra token moves a logit by 1e-2)
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    return g.SparseLatentMoELM(**ARGS, dtype="float32", seed=11)


def _ref_logits(model, tokens, last, margins=None):
    return np.asarray(reference.next_token_logits(
        model.decode_params(), list(tokens), SHAPE, last, margins))


class _Stepper:
    """The model's ragged step over pools of its own, one sequence a
    descriptor: what `RaggedStep` dispatches, without the engine."""

    def __init__(self, model, use_kernel=False, pages=64, max_pages=16):
        self.model = model
        rows = model.kv_rows()
        self.pools = [jnp.zeros((pages, PAGE) + rows.row_shape, rows.dtype)
                      for _ in range(model.num_layers)]
        self.keys = [jnp.zeros((pages, PAGE, rows.index_width),
                               rows.number_dtype)
                     for _ in rows.index_layers]
        self.fn = jax.jit(model.ragged_step_fn(
            PAGE, pages, use_kernel=use_kernel, interpret=use_kernel))
        self.pages, self.max_pages = pages, max_pages
        self.tables, self.lens, self.free = {}, {}, list(range(pages))

    def share(self, seq, source, tokens):
        """`seq` starts as a copy of `source`'s first `tokens` tokens,
        whose whole pages it shares (a prefix hit)."""
        whole = tokens // PAGE
        self.tables[seq] = list(self.tables[source][:whole])
        self.lens[seq] = whole * PAGE

    def step(self, work, t_pad=24, s_pad=4):
        """work: [(seq, new tokens)] -> logits [len(work), V] at each
        sequence's last new token."""
        tokens, pos, pg, rw, st, ln, kv = [], [], [], [], [], [], []
        pt = np.zeros((s_pad, self.max_pages), np.int32)
        for j, (seq, new) in enumerate(work):
            table = self.tables.setdefault(seq, [])
            have = self.lens.get(seq, 0)
            while len(table) * PAGE < have + len(new):
                table.append(self.free.pop())
            st.append(len(tokens))
            for i, tok in enumerate(new):
                tokens.append(tok)
                pos.append(have + i)
                pg.append(table[(have + i) // PAGE])
                rw.append((have + i) % PAGE)
            ln.append(len(new))
            self.lens[seq] = have + len(new)
            kv.append(self.lens[seq])
            pt[j, :len(table)] = table
        pad = t_pad - len(tokens)
        fixed = [np.asarray(tokens + [0] * pad, np.int32),
                 np.asarray(pos + [0] * pad, np.int32),
                 np.asarray(pg + [self.pages] * pad, np.int32),
                 np.asarray(rw + [0] * pad, np.int32), pt] + [
            np.asarray(x + [0] * (s_pad - len(work)), np.int32)
            for x in (st, ln, kv)]
        (ids, logits, counters), self.pools, self.keys = self.fn(
            self.model.decode_params(), *fixed, self.pools, self.keys)
        self.counters = np.asarray(counters)
        return np.asarray(logits)[:len(work)]


PROMPT = np.random.default_rng(5).integers(0, ARGS["vocab_size"], 40).tolist()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_prefill_and_decode_match_the_reference(model, use_kernel):
    """A 40-token prompt in chunks of 11 (chunks start and end inside
    pages, and past the 8th token every row's selection drops tokens),
    then three decode steps, with the jnp forms and with the kernels."""
    stepper = _Stepper(model, use_kernel=use_kernel)
    for lo in range(0, len(PROMPT), 11):
        got = stepper.step([("a", PROMPT[lo:lo + 11])])[0]
    np.testing.assert_allclose(got, _ref_logits(model, PROMPT, 1)[0],
                               atol=LOGIT_TOL, rtol=0)
    seq = list(PROMPT)
    for _ in range(3):
        seq.append(int(np.argmax(got)))
        got = stepper.step([("a", seq[-1:])])[0]
        np.testing.assert_allclose(got, _ref_logits(model, seq, 1)[0],
                                   atol=LOGIT_TOL, rtol=0)
    # every row past the 8th token attended 8 of its context's tokens
    attended, context, indexed, keys = stepper.counters[4:]
    assert attended == ARGS["num_layers"] * ARGS["index_topk"]
    assert context == ARGS["num_layers"] * len(seq)
    assert (indexed, keys) == (2, 2 * len(seq))


def test_a_prefix_hit_serves_the_cold_logits(model):
    """A second sequence that shares the first's whole pages (index keys
    with them) and prefills the rest gives the logits of a cold pass."""
    stepper = _Stepper(model)
    stepper.step([("a", PROMPT[:20])])
    stepper.step([("a", PROMPT[20:])])
    stepper.share("b", "a", 30)
    got = stepper.step([("b", PROMPT[28:])])[0]
    cold = _Stepper(model)
    cold.step([("c", PROMPT[:20])])
    want = cold.step([("c", PROMPT[20:])])[0]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_the_reference_in_small_blocks_is_the_reference(model, monkeypatch):
    """The reference blocks rows, heads, index heads, keys and the
    vocabulary so that a 65,536-token pass fits beside the served model;
    the blocks change no value beyond a float32 sum's order."""
    want = _ref_logits(model, PROMPT, 3)
    for name, size in (("ROWS", 10), ("Q_ROWS", 5), ("HEADS", 2),
                       ("INDEX_HEADS", 3), ("KEY_STEP", 4),
                       ("VOCAB_COLS", 50)):
        monkeypatch.setattr(reference, name, size)
    np.testing.assert_allclose(_ref_logits(model, PROMPT, 3), want,
                               atol=2e-5, rtol=0)


def test_the_reference_reports_the_closest_calls(model):
    """One router margin an expert layer and one indexer gap a `full`
    layer, each of every position; a row that selects all has no gap."""
    margins = []
    _ref_logits(model, PROMPT, 2, margins)
    assert len(margins) == 3 + 2
    for m in margins:
        assert np.asarray(m).shape == (len(PROMPT),)
    gaps = margins[-2:]
    for gap in gaps:
        assert np.all(np.isinf(gap[:ARGS["index_topk"]]))
        assert np.all(np.isfinite(gap[ARGS["index_topk"]:]))
        assert np.all(gap[ARGS["index_topk"]:] >= 0)


def _pool_and_rows(rng, dtype, pages=12, width=24):
    rows = SparseLatentRows(width, dtype, 16, [0], 8)
    numbers = rows.lanes * rows.per_word
    data = np.zeros((pages, PAGE, numbers), np.float32)
    data[..., :width] = rng.normal(size=(pages, PAGE, width))
    data = jnp.asarray(data, dtype)
    if rows.per_word == 2:
        pool = sla.pack_rows(data, rows.lanes)
    else:
        pool = data
    return rows, data, pool[:, :, None, :]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sparse_kernel_matches_a_gather(dtype):
    """The kernel against a plain gather of the selected rows: rows with
    8, 3 and 0 selected tokens, the slots in no order, a sink a head;
    bfloat16 rows packed two numbers a word and opened in the kernel."""
    rng = np.random.default_rng(0)
    rows, data, pool = _pool_and_rows(rng, dtype)
    numbers = rows.lanes * rows.per_word
    t, h, k = 3, 4, 8
    slots = np.stack([rng.permutation(48)[:k] for _ in range(t)]).astype(
        np.int32)
    counts = np.asarray([8, 3, 0], np.int32)
    q = np.zeros((t, h, numbers), np.float32)
    q[..., :24] = rng.normal(size=(t, h, 24)) * 0.3
    q = jnp.asarray(q, dtype)
    sink = jnp.asarray(rng.normal(size=h), jnp.float32)
    got = sla.sparse_latent_attention_kernel(q, pool, slots, counts, sink,
                                             16, interpret=True)
    flat = np.asarray(data, np.float32).reshape(-1, numbers)
    want = np.zeros((t, h, 16), np.float32)
    for r in range(t):
        sel = flat[slots[r, :counts[r]]]
        z = np.asarray(q[r], np.float32) @ sel.T
        s = np.asarray(sink)[:, None]
        m = np.maximum(z.max(-1, keepdims=True, initial=-np.inf), s)
        p = np.exp(z - m)
        want[r] = (p @ sel[:, :16]) / (p.sum(-1, keepdims=True)
                                       + np.exp(s - m))
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=0)
    ref = decode_attention.sparse_latent_attention_reference(
        q, pool, jnp.asarray(slots), jnp.asarray(counts), sink, 16)
    np.testing.assert_allclose(np.asarray(ref), want, atol=1e-5, rtol=0)


def test_packed_rows_open_to_the_numbers_stored():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 256)), jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(sla.unpack_rows(sla.pack_rows(x, 128))),
        np.asarray(x, np.float32))


def test_the_indexer_kernel_matches_its_jnp_form():
    """Scores of a decode row, a 9-row chunk (two cells) and a row of a
    third descriptor, over their own page tables; the kernel's columns
    past a row are masked by the caller."""
    rng = np.random.default_rng(2)
    pages, heads, dim = 16, 4, 16
    keys = jnp.asarray(rng.normal(size=(pages, PAGE, dim)), jnp.float32)
    pt = np.asarray([[3, 7, 1, 0], [5, 2, 9, 11], [4, 0, 0, 0]], np.int32)
    starts = np.asarray([0, 1, 10], np.int32)
    lens = np.asarray([1, 9, 1], np.int32)
    kv_lens = np.asarray([11, 15, 2], np.int32)
    t = 12
    q = jnp.asarray(rng.normal(size=(t, heads, dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, heads)), jnp.float32)
    _, qpos = decode_attention.row_positions(starts, lens, kv_lens, t)
    got = decode_attention.dsa_index_scores(
        q, w, keys, pt, starts, lens, kv_lens, qpos, True, interpret=True)
    want = decode_attention.dsa_index_scores(
        q, w, keys, pt, starts, lens, kv_lens, qpos, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)
    assert np.all(np.asarray(got)[11] == decode_attention.NEG_INF)


def test_a_shared_layer_attends_over_its_full_layers_set(model):
    """With the second layer `shared`, its selection is the first's:
    the served step and a reference told the same pattern agree, and
    they differ from a model whose second layer selects its own."""
    args = dict(ARGS, indexer_types=["full", "shared", "shared", "shared"])
    shared = g.SparseLatentMoELM(**args, dtype="float32", seed=11)
    assert shared.kv_rows().index_layers == (0,)
    got = _Stepper(shared).step([("a", PROMPT[:23])])[0]
    want = np.asarray(reference.next_token_logits(
        shared.decode_params(), PROMPT[:23], args, 1))[0]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_h_res_is_doubly_stochastic():
    rng = np.random.default_rng(3)
    n, d, t = 4, 32, 6
    streams = jnp.asarray(rng.normal(size=(t, n, d)), jnp.float32)
    hc = {"phi": jnp.asarray(rng.normal(size=(n * d, n * (n + 2)))
                             / np.sqrt(n * d), jnp.float32),
          "alpha": jnp.full((n * (n + 2),), 1.0, jnp.float32),
          "bias": jnp.asarray(rng.normal(size=n * (n + 2)), jnp.float32)}
    h_pre, h_post, h_res = blocks.hyper_coefficients(
        streams, hc, 1e-5, 1e-6, 2.0, 20)
    h_res = np.asarray(h_res)
    np.testing.assert_allclose(h_res.sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(2), 1.0, atol=1e-5)
    assert np.all(h_res > 0)
    assert np.all((np.asarray(h_post) > 0) & (np.asarray(h_post) < 2))


def test_one_stream_with_unit_coefficients_is_a_plain_residual():
    """n = 1, h_pre = h_post = 1 (biases 30 and 0, no Phi) and H_res the
    1 x 1 Sinkhorn of anything: x + F(x)."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 1, 8)), jnp.float32)
    hc = {"phi": jnp.zeros((8, 3), jnp.float32),
          "alpha": jnp.zeros((3,), jnp.float32),
          "bias": jnp.asarray([30.0, 0.0, 0.3], jnp.float32)}

    def f(v):
        return jnp.tanh(v) * 3.0, None

    got, _ = blocks.hyper_connection(x, hc, f, 1e-5, magnitude=2.0)
    want = x[:, 0] + jnp.tanh(x[:, 0]) * 3.0
    np.testing.assert_allclose(np.asarray(got)[:, 0], np.asarray(want),
                               atol=1e-5, rtol=0)


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """A layer's 16 shares of its 256... at the tiny size: the 2 shares
    of 4 experts each of an 8-wide router.  Each share's routed part,
    summed, with the shared expert counted once, is the uncut reference
    layer's feed-forward output."""
    rng = np.random.default_rng(6)
    d, f, router, k = 32, 16, 8, 2
    h = jnp.asarray(rng.normal(size=(7, d)), jnp.float32)
    whole = {
        "w_router": jnp.asarray(rng.normal(size=(d, router)) / np.sqrt(d),
                                jnp.float32),
        "router_bias": jnp.asarray(rng.normal(size=router) * 0.02,
                                   jnp.float32),
        "experts_gate_up": jnp.asarray(
            rng.normal(size=(router, d, 2 * f)) / np.sqrt(d), jnp.float32),
        "experts_down": jnp.asarray(
            rng.normal(size=(router, f, d)) / np.sqrt(f), jnp.float32),
        "shared_gate_up": jnp.asarray(rng.normal(size=(d, 2 * f))
                                      / np.sqrt(d), jnp.float32),
        "shared_down": jnp.asarray(rng.normal(size=(f, d)) / np.sqrt(f),
                                   jnp.float32)}
    valid = jnp.ones((7,), bool)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts(whole, h, top_k=k, scaling=2.827,
                                     first=0, limit=10.0)
        shared = reference._swiglu(h, whole["shared_gate_up"],
                                   whole["shared_down"], 10.0)
        total = shared
        for first in (0, 4):
            part = dict(whole,
                        experts_gate_up=whole["experts_gate_up"][
                            first:first + 4],
                        experts_down=whole["experts_down"][first:first + 4])
            y, stats = blocks.feed_forward(part, h, valid, k, 2.827,
                                           "sigmoid_bias", (first, 4), 10.0)
            total = total + (y - shared)
            assert int(stats[0]) + int(stats[3]) == 7 * k
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=0)


def test_the_engine_serves_it_with_the_prefix_cache(model):
    """Through `GenerationEngine`'s ragged step: the same greedy tokens
    from a cold prompt and from one that hits the prefix cache, the
    gauges stamped, the counters counted, the other paths refused."""
    cfg = dict(num_pages=64, page_size=PAGE, max_decode_slots=4,
               prefill_chunk_tokens=16, prefix_cache=True)
    engine = g.GenerationEngine(model, g.GenerationConfig(**cfg))
    try:
        cold = engine.submit(PROMPT, max_new_tokens=4).result(
            timeout=300).token_ids
        hit = engine.submit(PROMPT, max_new_tokens=4)
        warm = hit.result(timeout=300).token_ids
        stats = engine.stats()
    finally:
        engine.shutdown(timeout=30)
    assert warm == cold
    assert hit.prefix_hit_tokens >= 36
    assert stats["generation.kv_pool_layout"] == "sparse_latent"
    assert stats["generation.kv_index_layers"] == 2
    assert stats["generation.dsa_index_topk"] == 8
    assert stats["generation.latent_pages_per_cell"] == 0
    assert stats["generation.kv_token_bytes"] == 4 * (24 * 4 + 16 * 2)
    assert 0 < stats["generation.dsa_tokens_attended"] < stats[
        "generation.dsa_context_tokens"]
    with pytest.raises(g.UnsupportedModelPathError):
        g.GenerationEngine(model, g.GenerationConfig(
            **dict(cfg, spec_mode="ngram")), start=False)


def test_a_page_copy_moves_the_index_keys_with_the_rows(model):
    """Copy-on-write copies a page in every latent pool AND in every
    index-key pool; the pools keep their shapes and the other pages."""
    rows = model.kv_rows()
    cache = g.DeviceKVPool(model.num_layers, model.num_heads, model.head_dim,
                           num_pages=6, page_size=PAGE, rows=rows)
    assert cache.state_group_sizes == (model.num_layers, 2)
    rng = np.random.default_rng(10)
    state = [jnp.asarray(rng.normal(size=a.shape), a.dtype)
             for a in cache.take_pool_state()]
    cache.put_pool_state(state)
    state = [np.asarray(a) for a in state]     # the copy donates the pools
    cache._copy_page_storage(1, 4)
    for before, now in zip(state, cache.take_pool_state()):
        now = np.asarray(now)
        assert now.shape == before.shape
        np.testing.assert_array_equal(now[4], before[1])
        np.testing.assert_array_equal(np.delete(now, 4, 0),
                                      np.delete(before, 4, 0))


def test_no_clamp_is_the_plain_swiglu_bitwise():
    """`swiglu_limit` None (GLM's, Trinity's and Granite's calls) is
    silu(g) * u to the bit; a limit clamps the gate from above and the
    up projection both ways."""
    from paddle_tpu.generation import moe

    rng = np.random.default_rng(7)
    gu = jnp.asarray(rng.normal(size=(6, 16)) * 20, jnp.float32)
    plain = jax.nn.silu(gu[:, :8]) * gu[:, 8:]
    np.testing.assert_array_equal(np.asarray(moe.swiglu(gu)),
                                  np.asarray(plain))
    clamped = np.asarray(moe.swiglu(gu, 10.0))
    want = (jax.nn.silu(jnp.minimum(gu[:, :8], 10.0))
            * jnp.clip(gu[:, 8:], -10.0, 10.0))
    np.testing.assert_array_equal(clamped, np.asarray(want))
    assert np.abs(clamped).max() <= 10.0 * 10.0 + 1e-3
    assert not np.array_equal(clamped, np.asarray(plain))


@pytest.mark.parametrize("n,masked", [(1024, 0), (4096, 1000)])
def test_the_two_stage_top_k_is_the_top_k(n, masked):
    """The k blocks with the largest maxima hold the k largest scores:
    the same set as one sort over the row, with masked (NEG_INF) tails."""
    rng = np.random.default_rng(8)
    scores = rng.normal(size=(5, n)).astype(np.float32)
    if masked:
        scores[:, -masked:] = decode_attention.NEG_INF
    got = np.asarray(decode_attention.exact_top_k(jnp.asarray(scores), 32))
    want = np.asarray(jax.lax.top_k(jnp.asarray(scores), 32)[1])
    for g_row, w_row in zip(got, want):
        assert set(g_row.tolist()) == set(w_row.tolist())


@pytest.mark.parametrize("long_rows", [3, 40])
def test_selection_over_few_rows_is_selection_over_all(long_rows,
                                                       monkeypatch):
    """A step whose rows that need a sort are few sorts those alone; the
    picks are the ones a sort over every row makes."""
    rng = np.random.default_rng(9)
    t, n, k = 48, 256, 16
    qpos = np.full(t, -1, np.int32)
    qpos[:long_rows] = rng.integers(k, n, long_rows)
    qpos[long_rows:long_rows + 4] = rng.integers(0, k, 4)
    scores = rng.normal(size=(t, n)).astype(np.float32)
    col = np.arange(n)[None, :]
    scores = np.where(col <= qpos[:, None], scores, decode_attention.NEG_INF)
    monkeypatch.setattr(decode_attention, "FEW_LONG_ROWS", 8)
    picked, counts = decode_attention.dsa_select(
        lambda: jnp.asarray(scores), jnp.asarray(qpos), k, n)
    picked, counts = np.asarray(picked), np.asarray(counts)
    np.testing.assert_array_equal(counts, np.clip(qpos + 1, 0, k))
    for r in range(t):
        if qpos[r] + 1 > k:
            top = np.argsort(-scores[r])[:k]
            assert set(picked[r].tolist()) == set(top.tolist())
        else:
            assert picked[r].tolist() == list(range(k))
