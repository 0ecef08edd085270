"""GQAWindowMoELM (grouped-query heads, window and full layers, four
norms, experts) behind the ragged step: the served path against the
plain reference (`benchmarks/reference/afmoe.py`) across the window's
edge, through chunks and through released pages; the kernel against its
jnp oracle; the two page groups; the work lists; the refusals.

Tiny float32 preset, seeded weights: window 8, pages of 4, 4 query heads
over 2 KV heads of 16, 8 experts top-2, one dense layer and four expert
layers, kinds (window, window, window, full, window).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import afmoe as reference
from paddle_tpu import generation as g
from paddle_tpu.generation import decode_attention
from paddle_tpu.generation.kv_cache import (DeviceKVPool, HeadRows,
                                            OutOfPagesError, WindowPageGroup)
from paddle_tpu.ops.pallas import gqa_paged_attention as gq

WINDOW = 8
ARGS = dict(vocab_size=211, hidden_size=64, num_layers=5, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, first_k_dense_replace=1,
            routed_scaling_factor=2.826, sliding_window=WINDOW,
            layer_types=["sliding_attention"] * 3 + ["full_attention",
                                                     "sliding_attention"],
            rope_theta=1e4, rms_norm_eps=1e-5)
PAGE = 4
# float32 everywhere and the same products in another order: logits of
# order 1 agree to a few 1e-6; 2e-4 leaves room for the longest sums and
# still catches any wrong term (a key outside the window let in, a
# rotation in a full layer, a norm's gain dropped), which moves a logit
# by 1e-2 and more.  The same preset in bf16 misses it by 1e-2
# (`test_a_bf16_run_of_the_preset_fails_the_tolerance`).
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    return g.GQAWindowMoELM(**ARGS, dtype="float32", seed=11)


def _ref_logits(model, tokens, last):
    return np.asarray(reference.next_token_logits(
        model.decode_params(), list(tokens), ARGS, last))


class _Stepper:
    """The model's ragged step over pools of its own and the cache's own
    bookkeeping (`DeviceKVPool` with a window group: reserve, tables,
    release after every step), one sequence a descriptor: what
    `RaggedStep` dispatches, without the engine.  `poison`: a page is
    filled with NaN for as long as it lies on the window group's free
    list (and zeroed when a sequence takes it, so that what the test
    sees is a read of a page the sequence no longer holds)."""

    def __init__(self, model, use_kernel=False, pages=64, max_pages=16,
                 chunk=7, poison=False):
        self.model = model
        kinds, window = model.kv_layer_kinds()
        cap = WindowPageGroup.pages_a_sequence(PAGE, window, chunk)
        self.cache = DeviceKVPool(
            model.num_layers, model.num_heads, model.head_dim,
            num_pages=pages, page_size=PAGE, rows=model.kv_rows(),
            window=(kinds, window, 3 * cap, chunk))
        self.fn = jax.jit(model.ragged_step_fn(
            PAGE, pages, use_kernel=use_kernel, interpret=use_kernel))
        self.max_pages, self.poison = max_pages, poison
        self.held_max = 0

    def step(self, work, t_pad=24, s_pad=4):
        """work: [(seq, new tokens)] -> logits [len(work), V] at each
        sequence's last new token."""
        cache, wg = self.cache, self.cache.window_group
        tokens, pos, desc, st, ln = [], [], [], [], []
        free_before = set(wg._free)
        for j, (seq, new) in enumerate(work):
            if not cache.has(seq):
                cache.allocate(seq)
            have = cache.reserve(seq, len(new))
            st.append(len(tokens))
            ln.append(len(new))
            tokens += list(new)
            pos += list(range(have, have + len(new)))
            desc += [j] * len(new)
        self._fill(sorted(free_before - set(wg._free)), 0.0)
        ids = [seq for seq, _ in work]
        pt, kv = cache.gather_block_tables(ids, self.max_pages)
        wpt = wg.gather_tables(ids, self.max_pages)
        self.held_max = max([self.held_max] + [wg.held(s) for s in ids])
        pos, desc = np.asarray(pos), np.asarray(desc)
        pad = t_pad - len(tokens)

        def padded(values, fill):
            return np.asarray(list(values) + [fill] * pad, np.int32)

        def descs(values):
            out = np.zeros((s_pad,) + np.shape(values)[1:], np.int32)
            out[:len(work)] = values
            return out

        fixed = [padded(tokens, 0), padded(pos, 0),
                 padded(pt[desc, pos // PAGE], cache.num_pages),
                 padded(pos % PAGE, 0), descs(pt), descs(st), descs(ln),
                 descs(kv), padded(wpt[desc, pos // PAGE], wg.num_pages),
                 descs(wpt)]
        (_, logits, counters), pools = self.fn(
            self.model.decode_params(), *fixed, cache.take_pool_state())
        cache.put_pool_state(pools)
        self.counters = np.asarray(counters)
        free_before = set(wg._free)
        cache.release_window_pages()
        self._fill(sorted(set(wg._free) - free_before), jnp.nan)
        return np.asarray(logits)[:len(work)]

    def _fill(self, pages, value):
        if not (self.poison and pages):
            return
        pools = self.cache.take_pool_state()
        for li, kind in enumerate(self.cache.layer_kinds):
            if kind == "window":
                pools[li] = pools[li].at[np.asarray(pages)].set(value)
        self.cache.put_pool_state(pools)


RNG = np.random.default_rng(5)
PROMPT = RNG.integers(0, ARGS["vocab_size"], 31).tolist()   # > 3 windows


@pytest.mark.parametrize("length", [5, WINDOW, 31],
                         ids=["under_a_window", "one_window", "three_windows"])
def test_prefill_logits_match_the_reference(model, length):
    got = _Stepper(model, chunk=31).step([("a", PROMPT[:length])], t_pad=32)
    np.testing.assert_allclose(got[0], _ref_logits(model, PROMPT[:length],
                                                   1)[0],
                               atol=LOGIT_TOL, rtol=0)


def test_the_reference_in_small_blocks_is_the_reference(model, monkeypatch):
    want = _ref_logits(model, PROMPT, 3)
    for name, size in (("ATTN_ROWS", 5), ("FFN_ROWS", 7),
                       ("VOCAB_COLS", 50)):
        monkeypatch.setattr(reference, name, size)
    np.testing.assert_allclose(_ref_logits(model, PROMPT, 3), want,
                               atol=2e-5, rtol=0)


def test_the_reference_s_window_is_a_mask_and_matters_past_it(model):
    """The same weights under a window no context reaches give the same
    logits up to one window and other logits past it."""
    wide = dict(ARGS, sliding_window=1000)
    params = model.decode_params()
    under = PROMPT[:WINDOW]
    np.testing.assert_allclose(
        reference.next_token_logits(params, under, wide, 1),
        reference.next_token_logits(params, under, ARGS, 1), atol=1e-5)
    a = np.asarray(reference.next_token_logits(params, PROMPT, ARGS, 1))
    b = np.asarray(reference.next_token_logits(params, PROMPT, wide, 1))
    assert np.abs(a - b).max() > 0.05


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_chunked_prefill_then_decode_matches_the_reference(model, use_kernel):
    """Chunks of 7 (no multiple of the 4-token page, so chunks and the
    window's edge fall inside pages), window pages released after every
    step and POISONED with NaN, then two sequences decoded beside a
    third one's chunk: every step's logits against one dense pass of the
    reference."""
    stepper = _Stepper(model, use_kernel=use_kernel, poison=True)
    for lo in range(0, len(PROMPT), 7):
        got = stepper.step([("a", PROMPT[lo:lo + 7])])[0]
        want = _ref_logits(model, PROMPT[:lo + 7], 1)[0]
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    seqs = {"a": list(PROMPT), "b": PROMPT[3:9]}
    stepper.step([("b", seqs["b"])])
    other = RNG.integers(0, ARGS["vocab_size"], 14).tolist()
    forced = RNG.integers(0, ARGS["vocab_size"], (4, 2)).tolist()
    for i, (ta, tb) in enumerate(forced):
        seqs["a"].append(ta)
        seqs["b"].append(tb)
        work = [("a", [ta]), ("b", [tb])]
        if i < 2:       # a chunk of a third sequence in the same step
            work.append(("c", other[7 * i:7 * i + 7]))
        got = stepper.step(work)
        for row, name in zip(got, "ab"):
            np.testing.assert_allclose(
                row, _ref_logits(model, seqs[name], 1)[0], atol=LOGIT_TOL,
                rtol=0)
        if i < 2:
            np.testing.assert_allclose(
                got[2], _ref_logits(model, other[:7 * i + 7], 1)[0],
                atol=LOGIT_TOL, rtol=0)
    wg = stepper.cache.window_group
    assert stepper.held_max <= wg.sequence_cap == -(-(WINDOW + 7) // PAGE) + 1
    assert wg.pages_released > 0
    assert stepper.counters[0] > 0


def test_a_bf16_run_of_the_preset_fails_the_tolerance():
    """The tolerance tells the stated precision from the one below."""
    model = g.GQAWindowMoELM(**ARGS, dtype="bfloat16", seed=11)
    got = _Stepper(model, chunk=31).step([("a", PROMPT)], t_pad=32)[0]
    assert np.abs(got - _ref_logits(model, PROMPT, 1)[0]).max() > LOGIT_TOL


# ----------------------------- the kernel -----------------------------
@pytest.fixture
def small_cells(monkeypatch):
    """Cells of 8 keys (2 pages) and tiles of 4 rows, so that tiny
    contexts walk several groups and tiles."""
    monkeypatch.setattr(gq, "GQA_CELL_TOKENS", 8)
    monkeypatch.setattr(gq, "GQA_Q_BLOCK", 4)


def _batch(rng, kv, ln, pages=96, n_pages=16, lanes=128, t=24):
    kv, ln = np.asarray(kv, np.int32), np.asarray(ln, np.int32)
    st = np.concatenate([[0], np.cumsum(ln)[:-1]]).astype(np.int32)
    pt = np.zeros((len(kv), n_pages), np.int32)
    perm, at = rng.permutation(pages), 0
    for s, n in enumerate(-(-kv // PAGE)):
        pt[s, :n] = perm[at:at + n]
        at += n
    pool = rng.normal(size=(pages, PAGE, lanes)).astype(np.float32)
    q = rng.normal(size=(t, 4, 16)).astype(np.float32)
    return q, pool, pt, st, ln, kv


# (kv_lens, lens): where the window's lower edge falls
EDGES = {
    "on_a_page_boundary": ([16, 28], [1, 1]),        # 16 - 8, 28 - 8
    "inside_a_page": ([19, 30], [1, 1]),
    "inside_a_chunk": ([21], [13]),                  # rows 8..20
    "a_decode_row_beside_a_chunk_in_one_tile": ([33, 18, 5], [1, 9, 1]),
    "under_one_window": ([3, 7], [1, 7]),
    "padding_descriptors": ([40, 0, 12, 0], [1, 0, 12, 0]),
}


@pytest.mark.parametrize("window", [None, WINDOW, 10],
                         ids=["full", "window8", "window10"])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_gqa_kernel_in_interpret_mode_equals_the_jnp_form(edge, window,
                                                          small_cells):
    rng = np.random.default_rng(len(edge))
    q, pool, pt, st, ln, kv = _batch(rng, *EDGES[edge])
    want = decode_attention.gqa_ragged_attention_reference(
        q, pool, pt, st, ln, kv, 0.25, 2, window)
    got = gq.gqa_ragged_attention_kernel(
        jnp.asarray(q), jnp.asarray(pool), pt, st, ln, kv, 0.25, 2, window,
        interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    rows = np.arange(q.shape[0])[None, :]
    claimed = ((rows >= st[:, None]) & (rows < (st + ln)[:, None])).any(0)
    assert not np.asarray(got)[~claimed].any()


def test_the_jnp_form_is_dense_windowed_attention():
    rng = np.random.default_rng(3)
    q, pool, pt, st, ln, kv = _batch(rng, [30, 11], [1, 4])
    for window in (None, WINDOW):
        got = np.asarray(decode_attention.gqa_ragged_attention_reference(
            q, pool, pt, st, ln, kv, 0.25, 2, window))
        for s in range(2):
            keys = pool[pt[s]].reshape(-1, 128)
            for i in range(ln[s]):
                pos = kv[s] - ln[s] + i
                lo = 0 if window is None else max(0, pos - window + 1)
                for h in range(4):
                    k = keys[lo:pos + 1, (h // 2) * 16:(h // 2 + 1) * 16]
                    v = keys[lo:pos + 1, (2 + h // 2) * 16:(3 + h // 2) * 16]
                    w = jax.nn.softmax(k @ q[st[s] + i, h] * 0.25)
                    np.testing.assert_allclose(got[st[s] + i, h], w @ v,
                                               atol=1e-5)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_pages_behind_the_window_never_reach_an_output(form, small_cells):
    """Every page wholly behind the lowest row's window is poisoned with
    NaN and its table entry pointed at another sequence's page: the
    output is what it was."""
    rng = np.random.default_rng(9)
    q, pool, pt, st, ln, kv = _batch(rng, [37, 22, 9], [1, 5, 9])

    def run(pool, pt):
        if form == "jnp":
            return np.asarray(decode_attention.gqa_ragged_attention_reference(
                q, pool, pt, st, ln, kv, 0.25, 2, WINDOW))
        return np.asarray(gq.gqa_ragged_attention_kernel(
            jnp.asarray(q), jnp.asarray(pool), pt, st, ln, kv, 0.25, 2,
            WINDOW, interpret=True))

    want = run(pool, pt)
    pool, pt = pool.copy(), pt.copy()
    for s in range(3):
        behind = max(0, (kv[s] - ln[s] - WINDOW + 1) // PAGE)
        pool[pt[s, :behind]] = np.nan
        pt[s, :behind] = pt[(s + 1) % 3, 0]
    assert np.isnan(pool).any()
    np.testing.assert_array_equal(run(pool, pt), want)


def test_gqa_step_with_the_kernel_equals_the_jnp_step(model, small_cells):
    out = []
    for use_kernel in (False, True):
        stepper = _Stepper(model, use_kernel=use_kernel)
        stepper.step([("a", PROMPT[:7]), ("b", PROMPT[7:12])])
        stepper.step([("a", PROMPT[12:19])])
        out.append(stepper.step([("a", PROMPT[19:21]), ("b", [5]),
                                 ("c", PROMPT[21:30])]))
    np.testing.assert_allclose(out[1], out[0], atol=2e-5, rtol=0)


# ---------------------------- the work lists --------------------------
def _cells_of(work, n_seqs, n_pages, t):
    cells, count = (np.asarray(x) for x in work)
    per = gq.gqa_pages_per_cell(PAGE, n_pages)
    _, n_tiles = gq.ragged_query_tiles(t, gq.GQA_Q_BLOCK)
    tile_bits, group_bits = gq._cell_bits(n_seqs, -(-n_pages // per), n_tiles)
    live = cells[:count[0]]
    return [(int(c >> group_bits),
             int((c >> tile_bits) & ((1 << (group_bits - tile_bits)) - 1)),
             int(c & ((1 << tile_bits) - 1))) for c in live]


@pytest.mark.parametrize("window", [None, WINDOW, 13])
def test_work_list_holds_no_group_outside_a_tile_s_horizons(window,
                                                            small_cells):
    """Brute force over random batches: the list is exactly the (desc,
    group, tile) cells holding a key some row of the tile sees, in
    (descriptor, tile, group) order, and the host's mirror counts them."""
    rng = np.random.default_rng(17)
    t, n_pages = 24, 16
    per = gq.gqa_pages_per_cell(PAGE, n_pages)
    qb, _ = gq.ragged_query_tiles(t, gq.GQA_Q_BLOCK)
    for _ in range(12):
        n = int(rng.integers(1, 5))
        ln = rng.integers(0, 7, n)
        ln[rng.integers(0, n)] = max(ln.max(), 1)
        kv = np.where(ln > 0, ln + rng.integers(0, 40, n), 0)
        st = np.concatenate([[0], np.cumsum(ln)[:-1]])
        want, pages = [], 0
        for s in range(n):
            for tile in range(-(-t // qb)):
                rows = [r for r in range(tile * qb, (tile + 1) * qb)
                        if st[s] <= r < st[s] + ln[s]]
                if not rows:
                    continue
                pos = [kv[s] - ln[s] + r - st[s] for r in rows]
                lo = 0 if window is None else max(0, min(pos) - window + 1)
                seen = set(range(lo // PAGE, max(pos) // PAGE + 1))
                pages += len(seen)
                want += [(s, grp, tile)
                         for grp in sorted({p // per for p in seen})]
        work = gq.gqa_work_list(st, ln, kv, PAGE, n_pages, t, window)
        assert _cells_of(work, n, n_pages, t) == want
        assert gq.gqa_score_cells(st, ln, kv, PAGE, n_pages, t,
                                  window) == (pages, len(want))
        assert len(want) <= gq.gqa_grid_cells(n, n_pages, t, PAGE, window)


def test_the_lists_fit_smem_at_the_cell_s_largest_bucket():
    """trinity-mini-d8.mixed-closed: 17 descriptors, 528 packed rows, the
    1,024-page bucket of 64-token pages (33,280 tokens).  One word a
    cell and the flat tables: 12 KiB + 68 KiB for the full list, under a
    KiB of cells for the window list, of the 1 MiB of SMEM
    (`tests/test_chip_compile.py` has Mosaic take them)."""
    full = gq.gqa_grid_cells(17, 1024, 528, 64)
    window = gq.gqa_grid_cells(17, 1024, 528, 64, 2048)
    assert (full, window) == (49 * 64, 49 * 4)
    assert 4 * (full + 17 * 1024) < 128 << 10


# ---------------------------- the allocator ---------------------------
def _pool(pages=32, window_pages=12, chunk=7, layers=3):
    kinds = ("window", "full", "window")[:layers]
    return DeviceKVPool(layers, 4, 16, num_pages=pages, page_size=PAGE,
                        rows=HeadRows(2, 16, np.float32),
                        window=(kinds, WINDOW, window_pages, chunk))


def test_a_window_group_holds_a_window_and_a_chunk_at_any_length():
    cache = _pool(pages=128, window_pages=8)
    wg = cache.window_group
    assert wg.sequence_cap == -(-(WINDOW + 7) // PAGE) + 1 == 5
    assert [p.shape[0] for p in cache.take_pool_state()] == [8, 128, 8]
    cache.allocate("a")
    for step in range(60):
        cache.reserve("a", 7 if step < 50 else 1)
        assert wg.held("a") <= wg.sequence_cap
        length = cache.seq_len("a")
        first = wg.first_live("a")
        cache.release_window_pages()
        # released: exactly the pages no later query can see
        assert wg.first_live("a") == max(0, (length - WINDOW + 1) // PAGE)
        assert wg.first_live("a") >= first
        live = wg.table("a")[wg.first_live("a"):]
        assert len(set(live)) == len(live) and not set(live) & set(wg._free)
        assert len(live) + wg.free_pages == wg.num_pages
    assert len(cache.page_table("a")) == -(-cache.seq_len("a") // PAGE)
    assert wg.pages_released == wg.first_live("a")
    assert wg.take_counters() == (wg.pages_reserved, wg.pages_released)
    assert wg.take_counters() == (0, 0)


def test_free_preemption_and_truncate_return_both_groups():
    cache = _pool()
    wg = cache.window_group
    for seq, n in (("a", 30), ("b", 9)):
        cache.allocate(seq)
        for lo in range(0, n, 7):
            cache.reserve(seq, min(7, n - lo))
            cache.release_window_pages()
    released = wg.pages_released
    assert cache.truncate("b", 5) == 1 and wg.held("b") == 2
    with pytest.raises(ValueError, match="released behind"):
        cache.truncate("a", 12)          # its window pages are gone
    assert cache.truncate("a", 28) == 1 and cache.seq_len("a") == 28
    cache.free("a")                      # what a preemption does
    cache.free("b")
    assert (cache.num_free_pages, wg.free_pages) == (32, 12)
    assert wg.pages_released == released     # a free is not a release
    assert sorted(wg._free) == list(range(12))


def test_a_reservation_takes_pages_in_both_groups_or_in_neither():
    cache = _pool(pages=6, window_pages=4)
    cache.allocate("a")
    cache.reserve("a", 7)
    cache.allocate("b")
    with pytest.raises(OutOfPagesError, match="window-group"):
        cache.reserve("b", 12)           # 3 window pages, 2 free
    assert cache.seq_len("b") == 0 and cache.num_free_pages == 4
    assert cache.window_group.held("b") == 0
    cache.reserve("b", 7)
    with pytest.raises(OutOfPagesError):
        cache.reserve("b", 7)            # the full group is short now
    assert cache.window_group.held("b") == 2


class _Metrics:
    def __getattr__(self, name):
        return lambda *a, **k: None


def test_admission_reckons_both_groups():
    """A long prompt is admitted on its full-group need and a window's
    worth of the other group; what either group cannot hold waits."""
    from paddle_tpu.generation.scheduler import (ContinuousBatchingScheduler,
                                                 GenerationRequest)

    def admitted(pages, window_pages, prompts):
        cache = _pool(pages=pages, window_pages=window_pages)
        sched = ContinuousBatchingScheduler(cache, num_slots=4,
                                            queue_depth=8)
        for n in prompts:
            sched.submit(GenerationRequest(
                list(range(n)), g.GenerationHandle(),
                g.SamplingParams(), 4))
        return len(sched.admit())

    # 100 tokens: 26 full pages, 5 window pages (its cap), not 26
    assert admitted(32, 5, [100]) == 1
    assert admitted(64, 12, [100, 100]) == 2
    assert admitted(64, 9, [100, 100]) == 1      # the window group waits
    assert admitted(30, 12, [100, 20]) == 1      # the full group waits
    assert admitted(64, 9, [100, 10]) == 2       # a short one needs 3


def test_one_group_caches_are_what_they_were():
    from paddle_tpu.generation.kv_cache import LatentRows, PagedKVCache

    for cache in (PagedKVCache(2, 2, 8, num_pages=8, page_size=PAGE),
                  DeviceKVPool(2, 2, 8, num_pages=8, page_size=PAGE),
                  DeviceKVPool(2, 2, 8, num_pages=8, page_size=PAGE,
                               rows=LatentRows(24, 16, np.float32))):
        assert cache.window_group is None and cache.release_window_pages() == 0
        cache.allocate("a")
        cache.reserve("a", 9)
        assert cache.truncate("a", 3) == 2
        cache.free("a")
        assert cache.num_free_pages == 8
    rows = LatentRows(24, 16, np.float32)
    assert (rows.lanes, rows.token_bytes(3), rows.layout) == (128, 288,
                                                              "latent")
    heads = HeadRows(4, 128, jnp.bfloat16)
    assert (heads.width, heads.lanes, heads.token_bytes(8), heads.layout) == (
        1024, 1024, 16384, "kv_rows")


# ------------------------------ the engine ----------------------------
def _engine(model, pages=64, slots=4, chunk=8, **kw):
    from paddle_tpu.profiler.monitor import StatRegistry

    return g.GenerationEngine(
        model, g.GenerationConfig(
            num_pages=pages, page_size=PAGE, max_decode_slots=slots,
            prefill_chunk_tokens=chunk, **kw),
        metrics=g.GenerationMetrics(StatRegistry()), start=False)


def _assert_reference_argmax(model, prompt, got):
    logits = _ref_logits(model, prompt + got[:-1], len(got))
    top2 = np.sort(logits, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 2 * LOGIT_TOL
    assert decided.any()
    np.testing.assert_array_equal(
        np.asarray(got)[decided], logits.argmax(-1)[decided])


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_engine_serves_the_reference_argmax_past_the_window(model,
                                                            use_kernel):
    eng = _engine(model, use_kernel=use_kernel)
    stats = eng.stats()
    assert (eng.step_mode, type(eng.cache).__name__, eng.prefix_cache_enabled,
            stats["generation.kernel_path"], stats["generation.kv_pool_layout"],
            stats["generation.kv_window_tokens"]) == (
                "ragged", "DeviceKVPool", False,
                "ragged:pallas" if use_kernel else "ragged:jnp-reference",
                "kv_rows", WINDOW)
    assert json.loads(stats["generation.kv_layer_groups"]) == {
        "window": 4, "full": 1}
    assert stats["generation.kv_token_bytes"] == 64 * 4 * 5
    wg = eng.cache.window_group
    assert wg.num_pages == 5 * wg.sequence_cap
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, ARGS["vocab_size"], n).tolist()
               for n in (5, WINDOW, 30, 70)]
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for prompt, handle in zip(prompts, handles):
        _assert_reference_argmax(model, prompt,
                                 handle.result(timeout=5).token_ids)
    snap = eng.metrics.snapshot()
    assert wg.peak_held <= wg.sequence_cap
    assert (wg.free_pages, eng.cache.num_free_pages) == (wg.num_pages, 64)
    assert snap["generation.kv_window_pages_reserved"] == wg.pages_reserved
    assert snap["generation.kv_window_pages_released"] == wg.pages_released
    # behind the window of the 70-token prompt alone lie 15 pages
    assert wg.pages_released >= 15
    rows = sum(len(p) + 5 for p in prompts)
    assert snap["generation.moe_assignments_total"] == rows * 2 * 4
    if use_kernel:
        assert 0 < snap["generation.step_score_blocks"] \
            <= snap["generation.step_grid_cells"]
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
    wg = eng.cache.window_group
    assert (eng.cache.num_free_pages, wg.free_pages) == (9, wg.num_pages)
    eng.shutdown()


def test_the_engine_s_grid_counters_mirror_the_lists(model, small_cells):
    """`generation.step_score_blocks` / `step_grid_cells` of a step are
    the two lists' (tile, page) pairs and page slots, weighed by the
    layers that walk each and brought back to one layer."""
    eng = _engine(model, use_kernel=True)
    eng.submit(PROMPT[:20], max_new_tokens=2)
    eng.step()
    eng.step()                                  # rows 8..15 of the prompt
    fixed_t = eng._ragged.max_tokens
    st, ln, kv = [0], [8], [16]
    per = gq.gqa_pages_per_cell(PAGE, eng._ragged.last_pages_bucket)
    blocks = cells = 0
    for kind, layers in (("window", 4), ("full", 1)):
        window = WINDOW if kind == "window" else None
        pages, live = gq.gqa_score_cells(
            st, ln, kv, PAGE, eng._ragged.last_pages_bucket, fixed_t, window)
        count = int(gq.gqa_work_list(
            np.asarray(st), np.asarray(ln), np.asarray(kv), PAGE,
            eng._ragged.last_pages_bucket, fixed_t, window)[1][0])
        assert count == live
        blocks += layers * pages
        cells += layers * per * live
    assert (eng._ragged.last_score_blocks, eng._ragged.last_grid_cells) == (
        blocks // 5, cells // 5)
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
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_paths_not_carried_for_the_model_are_refused_by_name(model, path):
    (option, value), = REFUSED[path].items()
    with pytest.raises(g.UnsupportedModelPathError,
                       match=f"{option}={value!r}"
                       .replace("'int8'", "int8")
                       .replace("'bfloat16'", "bfloat16")):
        g.GenerationEngine(model, g.GenerationConfig(
            num_pages=16, page_size=PAGE, **REFUSED[path]), start=False)


def test_a_mesh_is_refused_for_the_model(model):
    from paddle_tpu.parallel import tp_mesh

    with pytest.raises(g.UnsupportedModelPathError, match="mesh"):
        g.GenerationEngine(model, g.GenerationConfig(
            num_pages=16, page_size=PAGE, mesh=tp_mesh(2)), start=False)


def test_the_latent_model_may_still_have_the_prefix_cache():
    """One refusal for every model the ragged step alone serves; only a
    window group rules the prefix cache out."""
    from tests.test_latent_moe import ARGS as latent_args

    eng = g.GenerationEngine(
        g.LatentMoELM(**latent_args, dtype="float32", seed=1),
        g.GenerationConfig(num_pages=16, page_size=PAGE, prefix_cache=True),
        start=False)
    assert eng.prefix_cache_enabled and eng.cache.window_group is None
    eng.shutdown()


def test_policies_left_to_the_engine_resolve_to_the_ragged_step(model):
    eng = g.GenerationEngine(model, g.GenerationConfig(
        num_pages=16, page_size=PAGE, max_decode_slots=2), start=False)
    assert eng.step_mode == "ragged" and eng.prefill_chunk_tokens > 0
    assert not eng.prefix_cache_enabled
    assert isinstance(eng.cache, g.DeviceKVPool)
    assert (eng.cache.rows.width, eng.cache.layer_kinds) == (
        64, ("window",) * 3 + ("full", "window"))
    eng.shutdown()


def test_weights_are_seeded_and_a_model_without_window_layers_has_one_group():
    a = g.GQAWindowMoELM(**ARGS, seed=2147483900)
    b = g.GQAWindowMoELM(**ARGS, seed=2147483900)
    assert a.params["head"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(a.params["head"], b.params["head"])
    embed = np.asarray(a.params["embed"], np.float32)
    assert 0.8 < (embed * a.embed_scale).std() < 1.2
    full = g.GQAWindowMoELM(**dict(ARGS, layer_types=["full_attention"] * 5),
                            dtype="float32", seed=1)
    eng = _engine(full)
    assert eng.cache.window_group is None and eng._ragged._n_fixed == 8
    prompt = PROMPT[:19]
    handle = eng.submit(prompt, max_new_tokens=3)
    eng.run_until_idle()
    logits = np.asarray(reference.next_token_logits(
        full.decode_params(), prompt, dict(
            ARGS, layer_types=["full_attention"] * 5), 1))
    assert handle.result(timeout=5).token_ids[0] == logits.argmax(-1)[0]
    eng.shutdown()
