"""LatentMoELM (GLM-4.7-Flash's block kind) through the serving engine,
against the plain reference `benchmarks/reference/glm4_moe_lite.py`.

Tiny preset, float32 weights, CPU: the served path (absorbed attention
over a latent page pool, dropless grouped experts, the ragged step)
and the reference (expanded attention, a dense mask over all experts,
one pass) then differ by float32 summation order alone, so the
tolerances are tight; each is written with its reason.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import glm4_moe_lite as reference
from paddle_tpu import generation as g
from paddle_tpu.generation import decode_attention, moe
from paddle_tpu.generation.kv_cache import LatentRows
from paddle_tpu.ops.pallas import paged_attention as pa

ARGS = dict(vocab_size=211, hidden_size=64, num_layers=3, num_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, routed_scaling_factor=1.8,
            rope_theta=1e6, rms_norm_eps=1e-5)
SHAPE = dict(ARGS)
PAGE = 4
# float32 everywhere and the same products regrouped: logits of order 1
# agree to a few 1e-6; 2e-4 leaves room for the longest sums (softmax
# over 40 keys, 64-wide contractions) and still catches any wrong term,
# which moves a logit by 1e-2 and more
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    return g.LatentMoELM(**ARGS, dtype="float32", seed=11)


def _ref_logits(model, tokens, last):
    return np.asarray(reference.next_token_logits(
        model.decode_params(), list(tokens), SHAPE, last))


class _Stepper:
    """The model's ragged step over pools of its own, one sequence a
    descriptor: what `RaggedStep` dispatches, without the engine."""

    def __init__(self, model, use_kernel=False, pages=64, max_pages=16):
        self.model = model
        rows = model.kv_rows()
        self.pools = [jnp.zeros((pages, PAGE, rows.lanes), rows.dtype)
                      for _ in range(model.num_layers)]
        self.fn = jax.jit(model.ragged_step_fn(
            PAGE, pages, use_kernel=use_kernel, interpret=use_kernel))
        self.pages, self.max_pages = pages, max_pages
        self.tables, self.lens, self.free = {}, {}, list(range(pages))

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
        (ids, logits, counters), self.pools = self.fn(
            self.model.decode_params(), *fixed, self.pools)
        self.counters = np.asarray(counters)
        return np.asarray(logits)[:len(work)]


PROMPT = np.random.default_rng(5).integers(0, ARGS["vocab_size"], 23).tolist()


def test_prefill_logits_match_the_reference(model):
    got = _Stepper(model).step([("a", PROMPT)])[0]
    np.testing.assert_allclose(got, _ref_logits(model, PROMPT, 1)[0],
                               atol=LOGIT_TOL, rtol=0)


def test_the_reference_in_small_blocks_is_the_reference(model, monkeypatch):
    """The reference blocks heads, query rows, tokens and vocabulary so
    that a 16k-token pass fits beside the served model; the blocks
    change no value beyond a float32 sum's order."""
    want = _ref_logits(model, PROMPT, 3)
    for name, size in (("HEADS", 3), ("ATTN_ROWS", 5), ("FFN_ROWS", 7),
                       ("VOCAB_COLS", 50)):
        monkeypatch.setattr(reference, name, size)
    np.testing.assert_allclose(_ref_logits(model, PROMPT, 3), want,
                               atol=2e-5, rtol=0)


def test_chunked_prefill_logits_match_the_reference(model):
    """Chunks of 7: shorter than the prompt and no multiple of the
    4-token page, so chunks start and end inside pages."""
    stepper = _Stepper(model)
    for lo in range(0, len(PROMPT), 7):
        got = stepper.step([("a", PROMPT[lo:lo + 7])])[0]
        want = _ref_logits(model, PROMPT[:lo + 7], 1)[0]
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_decode_through_the_latent_cache_matches_the_reference(model):
    """Two sequences prefilled, then decoded a token at a time in one
    batch, teacher-forced: each step's logits against one dense pass of
    the reference over the whole sequence."""
    stepper = _Stepper(model)
    seqs = {"a": PROMPT[:9], "b": PROMPT[9:]}
    stepper.step([("a", seqs["a"])])
    stepper.step([("b", seqs["b"])])
    forced = np.random.default_rng(6).integers(
        0, ARGS["vocab_size"], (5, 2)).tolist()
    for ta, tb in forced:
        seqs["a"].append(ta)
        seqs["b"].append(tb)
        got = stepper.step([("a", [ta]), ("b", [tb])])
        for row, name in zip(got, "ab"):
            np.testing.assert_allclose(
                row, _ref_logits(model, seqs[name], 1)[0], atol=LOGIT_TOL,
                rtol=0)


def test_absorbed_attention_equals_the_expanded_form(model):
    """One layer's attention alone: absorbed over latent rows (what the
    cache holds) against the reference's expanded keys and values."""
    lp = model.decode_params()["layers"][1]
    t = 13
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (t, model.d_model), np.float32))
    positions = jnp.arange(t, dtype=jnp.int32)
    q_abs, row = model._queries_and_row(
        lp, g.latent_moe_model.rms_norm(x, lp["norm1"], model.eps),
        positions)
    pool = jnp.zeros((4, PAGE, row.shape[-1])).at[
        positions // PAGE, positions % PAGE].set(row)
    o_abs = decode_attention.latent_ragged_attention_reference(
        q_abs, pool, np.arange(4)[None], np.array([0]), np.array([t]),
        np.array([t]), model.scale, model.kv_rank)
    got = model._attention_out(lp, o_abs)
    with jax.default_matmul_precision("highest"):
        q, k, v = reference._qkv(
            lp, x, positions, heads=ARGS["num_heads"], lo=0,
            hi=ARGS["num_heads"], nope=ARGS["qk_nope_head_dim"], rope=ARGS["qk_rope_head_dim"],
            theta=ARGS["rope_theta"], eps=ARGS["rms_norm_eps"])
        want = reference._attend(q, k, v, positions) @ lp["w_o"]
    # float32 and 16- to 64-wide sums: a few 1e-6 on outputs of order 1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# pages a cell of the latent kernel holds in these tests: 4-token pages
# under a 12- or 16-page table never fill the module's
# `LATENT_CELL_TOKENS`, so the tests state a smaller one
GROUP = 4


@pytest.fixture
def small_cells(monkeypatch):
    monkeypatch.setattr(pa, "LATENT_CELL_TOKENS", GROUP * PAGE)


MIXES = {
    "decode_rows": ([1, 1, 1], [5, 17, 1]),
    "chunk_beside_decode": ([1, 1, 9], [6, 12, 22]),
    "chunk_crossing_tiles": ([3, 11, 1], [3, 30, 9]),
    "all_padding": ([], []),
    # one-token rows over contexts of 1, G - 1, G, G + 1 and 2G + 3 pages
    "pages_1_g-1_g_g+1_2g+3": ([1, 1, 1, 1, 1], [
        PAGE, (GROUP - 1) * PAGE, GROUP * PAGE, (GROUP + 1) * PAGE - 2,
        (2 * GROUP + 3) * PAGE - 1]),
    # a chunk that begins mid-tile (row 5) and whose rows cross a group
    # boundary: positions 13..18, of which the first tile's rows see 4
    # pages (one group) and the second tile's 5 (two)
    "chunk_crossing_a_group": ([1, 1, 1, 1, 1, 6], [
        3, GROUP * PAGE, 9, 2, (GROUP + 1) * PAGE, GROUP * PAGE + 3]),
    "an_empty_descriptor_between": ([2, 0, 7], [
        (GROUP + 1) * PAGE + 1, 0, 2 * GROUP * PAGE + 3]),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_latent_kernel_in_interpret_mode_equals_the_jnp_form(mix,
                                                             small_cells):
    lens, kv = MIXES[mix]
    rng = np.random.default_rng(2)
    h, width, v_width, pages, t, s_pad, mp = 3, 24, 16, 60, 20, 7, 12
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32) \
        if lens else np.zeros((0,), np.int32)
    pad = s_pad - len(lens)
    st, ln, kvl = (np.concatenate([np.asarray(x, np.int32),
                                   np.zeros(pad, np.int32)])
                   for x in (starts, lens, kv))
    pt = np.zeros((s_pad, mp), np.int32)
    perm = iter(rng.permutation(pages))
    for j, n in enumerate(kv):
        for i in range(-(-n // PAGE)):
            pt[j, i] = next(perm)
    q = jnp.asarray(rng.standard_normal((t, h, width), np.float32))
    pool = jnp.asarray(rng.standard_normal((pages, PAGE, width), np.float32))
    want = decode_attention.latent_ragged_attention_reference(
        q, pool, pt, st, ln, kvl, 0.25, v_width)
    got = pa.latent_ragged_attention_kernel(
        q, pool, jnp.asarray(pt), st, ln, kvl, 0.25, v_width, interpret=True)
    # the same float32 sums in another order (a group at a time, online)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    # the live cells are the ragged kernel's, grouped and walked
    # tile-major: each exactly once, beside its physical page
    assert pa.latent_pages_per_cell(PAGE, mp) == GROUP
    grouped = pa.latent_work_list(pt, st, ln, kvl, PAGE, t)
    _assert_the_groups_hold(grouped, pt, st, ln, kvl, PAGE, t)
    assert int(grouped[2][0]) == pa.latent_score_groups(
        st, ln, kvl, PAGE, mp, t)


def _live_cells(pt, st, ln, kvl, page_size, t):
    """The skip rule, spelled out: ``{(descriptor, page, tile): physical
    page}`` over the cells whose tile meets the descriptor's rows and
    whose page starts at or under the horizon of the tile's last
    in-span row."""
    qb, n_tiles = pa.ragged_query_tiles(t)
    live = {}
    for s in range(pt.shape[0]):
        for i in range(pt.shape[1]):
            for qt in range(n_tiles):
                last = min((qt + 1) * qb, st[s] + ln[s]) - 1
                if (ln[s] > 0 and qt * qb < st[s] + ln[s]
                        and (qt + 1) * qb > st[s]
                        and i * page_size <= kvl[s] - ln[s] + last - st[s]):
                    live[s, i, qt] = int(pt[s, i])
    return live


def _assert_the_groups_hold(grouped, pt, st, ln, kvl, page_size, t):
    """The grouped list against the live (descriptor, page, tile) set:
    every live cell sits in exactly one slot beside its physical page;
    every other slot of a live group is padding — a logical page past
    its tile's horizon, which the kernel masks, holding a page of that
    descriptor's table (a valid fetch); the tiles never go back; the
    tail repeats the last cell."""
    s, mp = pt.shape
    per = pa.latent_pages_per_cell(page_size, mp)
    qb, n_tiles = pa.ragged_query_tiles(t)
    tile_bits, group_bits = pa._cell_bits(s, -(-mp // per), n_tiles)
    pages, cells, count = (np.asarray(x) for x in grouped)
    n = int(count[0])
    assert len(cells) == pa.latent_grid_cells(s, mp, t, page_size) >= n
    assert len(pages) == len(cells) * per
    pages = pages.reshape(-1, per)
    want = _live_cells(pt, st, ln, kvl, page_size, t)
    assert len(want) == pa.ragged_score_blocks(st, ln, kvl, page_size, mp,
                                               t)[0]
    met = set()
    for w in range(n):
        cell = int(cells[w])
        desc, tile = cell >> group_bits, cell & ((1 << tile_bits) - 1)
        group = (cell >> tile_bits) & ((1 << (group_bits - tile_bits)) - 1)
        # the tile's last in-span row and the pages it sees
        last = min((tile + 1) * qb, st[desc] + ln[desc]) - 1
        seen = (kvl[desc] - ln[desc] + last - st[desc]) // page_size + 1
        for slot in range(per):
            key = (desc, group * per + slot, tile)
            if key in want:
                assert key not in met and want[key] == pages[w, slot]
                met.add(key)
            else:
                assert key[1] >= seen          # masked by col <= qpos
                assert pages[w, slot] == pt[desc, seen - 1]
    assert met == set(want)
    if n:
        assert (cells[n:] == cells[n - 1]).all()
        assert (pages[n:] == pages[n - 1]).all()
        assert (np.diff(cells[:n] & ((1 << tile_bits) - 1)) >= 0).all()


def test_latent_step_with_the_kernel_equals_the_jnp_step(model):
    a, b = _Stepper(model, use_kernel=True), _Stepper(model)
    for stepper in (a, b):
        stepper.step([("a", PROMPT[:10])])
        stepper.out = stepper.step([("a", PROMPT[10:11]),
                                    ("b", PROMPT[11:])])
    np.testing.assert_allclose(a.out, b.out, atol=LOGIT_TOL, rtol=0)


def test_routing_equals_the_reference_exactly_and_the_bias_decides(model):
    lp = model.decode_params()["layers"][1]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (64, model.d_model), np.float32))
    experts, weights = moe.route(x, lp["w_router"], lp["router_bias"],
                                 model.top_k, model.scaling)
    with jax.default_matmul_precision("highest"):
        want_e, want_w = reference.route(
            x, lp["w_router"], lp["router_bias"], top_k=model.top_k,
            scaling=model.scaling)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_array_equal(weights, want_w)
    # picking by the scores alone chooses other experts somewhere
    by_score, _ = moe.route(x, lp["w_router"], 0.0 * lp["router_bias"],
                            model.top_k, model.scaling)
    assert (np.sort(by_score, 1) != np.sort(experts, 1)).any()
    # the weights are of the scores, not of scores + bias
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"], precision="highest"))
    chosen = jnp.take_along_axis(s, experts, axis=1)
    np.testing.assert_allclose(
        weights, model.scaling * chosen / chosen.sum(1, keepdims=True),
        rtol=1e-6)


def test_the_reference_reports_each_position_s_closest_router_call(model):
    """`router_margin`: the last chosen expert's `s + b` less the first
    one's left out, one array an expert layer, of every position; a
    choice that hangs on less than a precision's noise is what a served
    token may differ by (`benchmarks/runners/serve_model.py`)."""
    params, margins = model.decode_params(), []
    logits = reference.next_token_logits(params, PROMPT, SHAPE, 3, margins)
    np.testing.assert_array_equal(
        logits, reference.next_token_logits(params, PROMPT, SHAPE, 3))
    expert_layers = [lp for lp in params["layers"] if "w_router" in lp]
    assert len(margins) == len(expert_layers) > 0
    assert all(m.shape == (len(PROMPT),) and (np.asarray(m) >= 0).all()
               for m in margins)
    lp = expert_layers[0]
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (16, model.d_model), np.float32))
    with jax.default_matmul_precision("highest"):
        got = reference.router_margin(x, lp["w_router"], lp["router_bias"],
                                      top_k=model.top_k)
        ranked = np.sort(np.asarray(
            jax.nn.sigmoid(x @ lp["w_router"]) + lp["router_bias"]), 1)
    np.testing.assert_allclose(
        got, ranked[:, -model.top_k] - ranked[:, -model.top_k - 1], atol=1e-6)


@pytest.mark.parametrize("k,held", [
    (2, None), (4, None), (8, None), (10, None),
    (10, (6, 8)),        # 8 of a 20-wide router's experts held here
])
def test_experts_are_dropless_with_an_idle_and_a_crowded_expert(k, held):
    """Every (token, choice) pair of a sequence's rows computed and
    brought home at every k (the pairs lie choice-major, k at 10 and 4
    no multiple of the 8-row tile): one expert most rows pick, at a
    choice that moves with the row, one held expert none, 7 padding
    rows; where the layer holds a share, picks held elsewhere come back
    0 and the fourth stat counts them."""
    rng = np.random.default_rng(4 + k)
    t, d, f, pad = 40, 16, 8, 7
    first, n = held or (0, 2 * k + 4)
    width = n if held is None else first + n + 6
    crowded, idle = first + 2, first + n - 1
    x = jnp.asarray(rng.standard_normal((t, d), np.float32))
    w_gu = jnp.asarray(rng.standard_normal((n, d, 2 * f), np.float32))
    w_d = jnp.asarray(rng.standard_normal((n, f, d), np.float32))
    others = [e for e in range(width) if e not in (crowded, idle)]
    experts = np.stack([rng.permutation(others)[:k] for _ in range(t)])
    most = np.arange(t) % 5 != 0
    experts[most, (np.arange(t) % k)[most]] = crowded
    weights = jnp.asarray(rng.random((t, k), np.float32))
    valid = np.arange(t) < t - pad
    got, stats = moe.expert_ffn(x, jnp.asarray(experts, jnp.int32), weights,
                                jnp.asarray(valid), w_gu, w_d, held)
    want = np.zeros((t, d), np.float32)
    for r in range(t - pad):
        for j in range(k):
            e = experts[r, j] - first
            if not 0 <= e < n:
                continue
            gu = np.asarray(x[r]) @ np.asarray(w_gu[e])
            hid = gu[:f] / (1 + np.exp(-gu[:f])) * gu[f:]
            want[r] += float(weights[r, j]) * (hid @ np.asarray(w_d[e]))
    # every (token, expert) pair computed: float32 sums over 16 and 8
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    picks = experts[valid].reshape(-1) - first
    here = picks[(picks >= 0) & (picks < n)]
    counts = np.bincount(here, minlength=n)
    assert counts[idle - first] == 0
    assert counts[crowded - first] == counts.max() > (t - pad) // 2
    want_stats = [len(here), counts.max(), int((counts > 0).sum())]
    if held is not None:
        assert len(here) < len(picks)
        want_stats.append(len(picks) - len(here))
    assert stats.tolist() == want_stats


def _engine(model, pages=64, slots=4, chunk=8, **kw):
    return g.GenerationEngine(
        model, g.GenerationConfig(
            num_pages=pages, page_size=PAGE, max_decode_slots=slots,
            prefill_chunk_tokens=chunk, prefix_cache=True, **kw),
        start=False)


def _assert_reference_argmax(model, prompt, got):
    """Every served token is the reference's argmax wherever the
    reference's top two logits stand further apart than the tolerance
    (closer than that, rounding may pick either)."""
    logits = _ref_logits(model, prompt + got[:-1], len(got))
    top2 = np.sort(logits, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 2 * LOGIT_TOL
    assert decided.any()
    np.testing.assert_array_equal(
        np.asarray(got)[decided], logits.argmax(-1)[decided])


def test_engine_serves_the_reference_argmax_on_a_miss_and_a_hit(model):
    eng = _engine(model)
    assert (eng.step_mode, type(eng.cache).__name__,
            eng.stats()["generation.kernel_path"]) == (
                "ragged", "DeviceKVPool", "ragged:jnp-reference")
    shared = PROMPT[:17]
    outs = []
    for tail in ([3, 1, 4], [1, 5, 9, 2]):
        h = eng.submit(shared + tail, max_new_tokens=7)
        eng.run_until_idle()
        outs.append((shared + tail, h.result(timeout=5).token_ids,
                     h.prefix_hit_tokens))
    assert outs[0][2] == 0 and outs[1][2] == 16     # whole pages only
    for prompt, got, _ in outs:
        _assert_reference_argmax(model, prompt, got)
    snap = eng.metrics.snapshot()
    rows = sum(len(p) + len(got) - 1 for p, got, _ in outs) - 16
    assert snap["generation.moe_assignments_total"] == rows * 2 * 2
    assert snap["generation.moe_assignments_max_expert"] > 0
    assert snap["generation.moe_experts_touched"] > 0
    assert snap["generation.kv_token_bytes"] == 24 * 4 * 3
    eng.shutdown()


def test_engine_serves_the_reference_argmax_after_a_preemption(model):
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, ARGS["vocab_size"], n).tolist()
               for n in (9, 11, 10)]
    eng = _engine(model, pages=9, chunk=4)
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    results = [h.result(timeout=5) for h in handles]
    assert sum(r.preemptions for r in results) > 0
    for prompt, res in zip(prompts, results):
        _assert_reference_argmax(model, prompt, res.token_ids)
    assert eng.cache.utilization() == 0.0
    eng.shutdown()


def test_latent_pool_shares_copies_truncates_and_evicts():
    rows = LatentRows(24, 16, np.float32)
    assert rows.lanes == 128 and rows.token_bytes(3) == 24 * 4 * 3
    pool = g.DeviceKVPool(3, 1, 24, num_pages=6, page_size=PAGE, rows=rows)
    assert pool.n_state_groups == 1 and len(pool.take_pool_state()) == 3
    mark = [jnp.full((6, PAGE, 128), float(i + 1)).at[0].set(7.0)
            for i in range(3)]
    pool.put_pool_state(mark)
    tokens = list(range(10))
    pool.allocate("a")
    pool.reserve("a", 10)
    pool.register_prefix("a", tokens)          # pages 0, 1 full
    pages, matched = pool.match_prefix(tokens[:8] + [99, 98])
    assert matched == 8
    pool.allocate("b")
    pool.adopt_prefix("b", pages, matched)
    assert pool.shared_pages == 2
    # b diverges inside the shared last page: rewinding into it is
    # refused, appending after a re-adoption of 7 tokens copies it
    with pytest.raises(ValueError):
        pool.truncate("b", 7)
    pool.free("b")
    pool.allocate("b")
    pool.adopt_prefix("b", pages, 7)
    before = pool.page_table("b")[1]
    pool.reserve("b", 1)                       # copy-on-write of page 1
    after = pool.page_table("b")[1]
    assert after != before and pool.take_prefix_counters()[0] == 1
    state = pool.take_pool_state()
    for layer in range(3):
        np.testing.assert_array_equal(state[layer][after],
                                      state[layer][before])
    pool.put_pool_state(state)
    # truncate a private tail: whole pages go back to the allocator
    pool.reserve("b", 6)
    free = pool.num_free_pages
    assert pool.truncate("b", 8) == 2 and pool.num_free_pages == free + 2
    # eviction: with both sequences gone the cached run yields its pages
    pool.free("a")
    pool.free("b")
    assert pool.prefix_cached_pages == 2
    pool.allocate("c")
    pool.reserve("c", 6 * PAGE)
    assert pool.prefix_cached_pages == 0 and pool.match_prefix(tokens)[1] == 0
    # what a latent pool does not carry is refused by name
    for call in (lambda: pool.layer_pools(0),
                 lambda: pool.gather_prefix("c", 0, 4),
                 lambda: pool.export_pages([0]),
                 lambda: pool.write_token("c", 0, 0, np.zeros((1, 24)),
                                          np.zeros((1, 24))),
                 lambda: pool.v_pool):
        with pytest.raises(g.UnsupportedCachePathError):
            call()
    assert pool.k_pool.shape == (3, 6, PAGE, 128)


def test_engine_re_prefills_an_evicted_document(model):
    eng = _engine(model, pages=12, chunk=8)
    doc = PROMPT[:16]
    first = eng.submit(doc + [1], max_new_tokens=3)
    eng.run_until_idle()
    # a long unrelated request takes the pool and evicts the cached run
    other = np.random.default_rng(9).integers(0, 211, 40).tolist()
    eng.submit(other, max_new_tokens=4)
    eng.run_until_idle()
    again = eng.submit(doc + [1], max_new_tokens=3)
    eng.run_until_idle()
    assert again.prefix_hit_tokens < 16      # leaves go first
    assert again.result(timeout=5).token_ids == \
        first.result(timeout=5).token_ids
    assert eng.metrics.snapshot()["generation.prefix_evictions"] > 0
    eng.shutdown()


REFUSED = {
    "host_pools": dict(kv_backend="host"),
    "fused_decode": dict(decode="fused"),
    "eager_decode": dict(decode="eager"),
    "legacy_step": dict(step_mode="legacy"),
    "looped_step": dict(loop_steps=4),
    "ngram_speculation": dict(spec_mode="ngram"),
    "int8_pool": dict(kv_dtype="int8"),
    "bf16_pool_option": dict(kv_dtype="bfloat16"),
    "kernel_layout": dict(pool_layout="kernel"),
    "one_shot_prefill": dict(prefill_chunk_tokens=0),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_paths_not_carried_for_the_model_are_refused_when_built(model, path):
    with pytest.raises(g.UnsupportedModelPathError):
        g.GenerationEngine(model, g.GenerationConfig(
            num_pages=16, page_size=PAGE, **REFUSED[path]), start=False)


def test_a_mesh_is_refused_for_the_model(model):
    from paddle_tpu.parallel import tp_mesh

    with pytest.raises(g.UnsupportedModelPathError):
        g.GenerationEngine(model, g.GenerationConfig(
            num_pages=16, page_size=PAGE, mesh=tp_mesh(2)), start=False)


def test_policies_left_to_the_engine_resolve_to_the_ragged_step(model):
    eng = g.GenerationEngine(model, g.GenerationConfig(
        num_pages=16, page_size=PAGE, max_decode_slots=2), start=False)
    assert eng.step_mode == "ragged" and eng.prefill_chunk_tokens > 0
    assert isinstance(eng.cache, g.DeviceKVPool) and eng.cache.rows.width == 24
    eng.shutdown()


def test_weights_are_seeded_and_a_large_seed_is_a_seed():
    a = g.LatentMoELM(**ARGS, seed=2147483900)
    b = g.LatentMoELM(**ARGS, seed=2147483900)
    c = g.LatentMoELM(**ARGS, seed=252)         # the same low 31 bits + 1
    assert a.params["head"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(a.params["head"], b.params["head"])
    assert (np.asarray(a.params["head"]) != np.asarray(c.params["head"])).any()
    bias = np.asarray(a.params["layers"][1]["router_bias"])
    assert 0.005 < bias.std() < 0.05


def test_latent_work_list_holds_the_ragged_lists_cells_on_random_batches(
        monkeypatch):
    """Random descriptor sets (padding descriptors, chunks that cross
    tiles, contexts up to the table's width) under cells of 1 to 4
    pages: the grouped tile-major list holds each of the ragged list's
    (cell, page) pairs exactly once, its other slots are masked
    padding, it never exceeds its capacity, its tail repeats the last
    live cell, and its tiles never go back."""
    rng = np.random.default_rng(0)
    for _ in range(24):
        s, t = int(rng.integers(1, 7)), int(rng.integers(4, 60))
        ps, mp = int(rng.choice([2, 4, 8])), int(rng.integers(1, 12))
        monkeypatch.setattr(pa, "LATENT_CELL_TOKENS",
                            int(rng.integers(1, 5)) * ps)
        lens, kv = np.zeros(s, np.int32), np.zeros(s, np.int32)
        room = t
        for i in range(int(rng.integers(0, s + 1))):
            if room <= 0:
                break
            kv[i] = rng.integers(1, mp * ps + 1)
            lens[i] = rng.integers(1, min(room, 20, kv[i]) + 1)
            room -= lens[i]
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        pt = rng.integers(0, 50, (s, mp)).astype(np.int32)
        grouped = pa.latent_work_list(pt, starts, lens, kv, ps, t)
        _assert_the_groups_hold(grouped, pt, starts, lens, kv, ps, t)
        assert int(grouped[2][0]) == pa.latent_score_groups(
            starts, lens, kv, ps, mp, t) <= pa.latent_grid_cells(s, mp, t, ps)


def test_the_grid_counter_of_a_latent_engine_counts_page_slots(model,
                                                               small_cells):
    """`generation.step_grid_cells` on the latent kernel: G x the grid
    steps, so blocks over cells is live pages over page slots.  A
    hand-built batch first — one-token rows over 3, 4 and 9 pages and a
    6-row chunk over 5 that crosses from the first query tile into the
    second, cells of 4 pages: 3 + 4 + 9 + 2 x 5 = 26 live pages in
    1 + 1 + 3 + 2 x 2 = 9 groups, 36 slots — then an engine's own
    steps; the gauge says which kernel the numbers belong to."""
    eng = _engine(model, use_kernel=True)
    step = eng._ragged
    step.last_pages_bucket = 16
    pad = [0] * (step.max_seqs - 4)
    fixed = [None] * 5 + [np.array(x + pad, np.int32) for x in (
        [0, 1, 2, 3], [1, 1, 1, 6],
        [3 * PAGE, 4 * PAGE, 8 * PAGE + 1, 4 * PAGE + 2])]
    step.count_kernel_cells(fixed)
    assert (step.last_score_blocks, step.last_grid_cells) == (26, 9 * GROUP)
    # nothing to attend: the kernel's one step, a group of empty slots
    step.count_kernel_cells(
        [None] * 5 + [np.zeros(step.max_seqs, np.int32)] * 3)
    assert (step.last_score_blocks, step.last_grid_cells) == (0, GROUP)
    h = eng.submit(PROMPT[:19], max_new_tokens=6)
    blocks = slots = 0
    while eng.scheduler.active() or eng.scheduler.pending_count():
        walked = eng.stats().get("generation.step_grid_cells", 0)
        eng.step()
        if eng.stats()["generation.step_grid_cells"] == walked:
            continue    # the call that only retires the step in flight
        shape = (step.last_pages_bucket, step.max_tokens)
        per = pa.latent_pages_per_cell(PAGE, step.last_pages_bucket)
        assert step.last_grid_cells % per == 0
        assert step.last_score_blocks <= step.last_grid_cells <= (
            per * pa.latent_grid_cells(step.max_seqs, *shape, PAGE))
        blocks += step.last_score_blocks
        slots += step.last_grid_cells
    _assert_reference_argmax(model, PROMPT[:19],
                             h.result(timeout=5).token_ids)
    snap = eng.stats()
    assert (snap["generation.step_score_blocks"],
            snap["generation.step_grid_cells"]) == (blocks, slots)
    assert 0 < blocks < slots          # 24 tokens: groups of 4 part full
    assert snap["generation.latent_pages_per_cell"] == GROUP
    assert snap["generation.kv_pool_layout"] == "latent"
    eng.shutdown()
    plain = _engine(model)             # the jnp form: no latent kernel
    assert plain.stats()["generation.latent_pages_per_cell"] == 0
    plain.shutdown()
