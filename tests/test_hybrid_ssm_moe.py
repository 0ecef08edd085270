"""HybridSSMMoELM (state-space layers beside an attention layer, experts
of which a share may be held) behind the ragged step: the served path
against the plain reference (`benchmarks/reference/granitemoehybrid.py`,
a token-by-token recurrence) through chunks that split a scan block,
through decode rows, a reused slot and a preemption; the state a slot
beside the pages; the share of the experts; the refusals; the counters.

Tiny preset, seeded weights: 4 layers (state, full, state, state), 4
query heads over 2 KV heads of 8, 4 state-space heads of 8 with a state
of 16, 4 taps, scan blocks of 8 rows, a router 8 wide top-3.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import granitemoehybrid as reference
from paddle_tpu import generation as g
from paddle_tpu.generation import blocks, moe
from paddle_tpu.generation.kv_cache import (DeviceKVPool, SlotState,
                                            UnsupportedCachePathError)
from paddle_tpu.profiler.monitor import StatRegistry

BLOCK = 8
ARGS = dict(vocab_size=131, hidden_size=32, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=8, moe_intermediate_size=16,
            shared_intermediate_size=24, n_routed_experts=8,
            num_experts_per_tok=3,
            layer_types=["mamba", "attention", "mamba", "mamba"],
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
            mamba_d_conv=4, mamba_chunk_size=BLOCK,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.125, logits_scaling=4.0,
            rms_norm_eps=1e-5)
# the second chip's view of the same layers: experts 4-7 of the 8
SHARE = dict(ARGS, n_routed_experts=4, router_width=8, experts_held=[4, 4])
# float32 everywhere and the same sums in another order (blocks of 8
# rows against one row at a time): logits of order 0.1 agree to 1e-6;
# 5e-5 leaves room for the longest products of decays and still catches
# any wrong term (a tap of the convolution, a block's carried state, a
# slot not started from zero), which moves a logit by 1e-3 and more
LOGIT_TOL = 5e-5
BF16_TOL = 0.03


@pytest.fixture(scope="module")
def model():
    return g.HybridSSMMoELM(**ARGS, dtype="float32", seed=11)


def _ref_logits(model, tokens, last, args=ARGS):
    return np.asarray(reference.next_token_logits(
        model.decode_params(), list(tokens), args, last))


def _ref_states(model, tokens):
    """[(state, tail)] of each state layer after `tokens`, by the
    reference's recurrence: `reference.hidden_states`, its layers'
    states kept."""
    params, out = model.decode_params(), []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32) * 12.0
        for lp, kind in zip(params["layers"], ARGS["layer_types"]):
            h = reference._norm(x, lp["norm1"], 1e-5)
            if kind == "mamba":
                y, z, state, tail = reference.recurrence(
                    lp, h, heads=4, head_dim=8, d_state=16)
                out.append((np.asarray(state), np.asarray(tail)))
                mixed = reference._gated_norm_out(lp, y, z, 1e-5)
            else:
                mixed = reference.attention(lp, h, ARGS)
            x = x + 0.22 * mixed
            x = x + 0.22 * reference.feed_forward(
                lp, reference._norm(x, lp["norm2"], 1e-5), ARGS)
    return out


class _Stepper:
    """The model's ragged step over a `DeviceKVPool` with state layers
    and that cache's own bookkeeping, a sequence a descriptor in the
    slot the test gives it: what `RaggedStep` dispatches, without the
    engine."""

    def __init__(self, model, page=4, pages=64, slots=3, t_pad=24):
        self.model, self.page, self.slots = model, page, slots
        kinds, _ = model.kv_layer_kinds()
        self.cache = DeviceKVPool(
            model.num_layers, model.num_heads, model.head_dim,
            num_pages=pages, page_size=page, rows=model.kv_rows(),
            state=(kinds, model.kv_slot_state(), slots))
        self.fn = jax.jit(model.ragged_step_fn(page, pages))
        self.t_pad, self.max_pages = t_pad, 32

    def step(self, work):
        """work: [(seq, slot, new tokens)] -> logits [len(work), V] at
        each sequence's last new token."""
        cache, page = self.cache, self.page
        tokens, pos, desc, st, ln = [], [], [], [], []
        for j, (seq, _, new) in enumerate(work):
            if not cache.has(seq):
                cache.allocate(seq)
            have = cache.reserve(seq, len(new))
            st.append(len(tokens))
            ln.append(len(new))
            tokens += list(new)
            pos += list(range(have, have + len(new)))
            desc += [j] * len(new)
        pt, kv = cache.gather_block_tables([seq for seq, _, _ in work],
                                           self.max_pages)
        pos, desc = np.asarray(pos), np.asarray(desc)
        pad = self.t_pad - len(tokens)
        s_pad = self.slots + 1

        def padded(values, fill):
            return np.asarray(list(values) + [fill] * pad, np.int32)

        def descs(values, fill=0):
            out = np.full((s_pad,) + np.shape(values)[1:], fill, np.int32)
            out[:len(work)] = values
            return out

        fixed = [padded(tokens, 0), padded(pos, 0),
                 padded(pt[desc, pos // page], cache.num_pages),
                 padded(pos % page, 0), descs(pt), descs(st), descs(ln),
                 descs(kv), descs([slot for _, slot, _ in work], self.slots)]
        state = cache.take_pool_state()
        n = self.model.num_layers
        (_, logits, counters), pools, tails = self.fn(
            self.model.decode_params(), *fixed, state[:n], state[n:])
        cache.put_pool_state(list(pools) + list(tails))
        self.counters = dict(zip(self.model.step_counters,
                                 np.asarray(counters).tolist()))
        return np.asarray(logits)[:len(work)]

    def slot_state(self, slot):
        """[(state, tail)] of `slot`, a state layer each."""
        state = self.cache.take_pool_state()
        kinds = self.cache.layer_kinds
        pools = [state[li] for li, kind in enumerate(kinds)
                 if kind == "state"]
        return [(np.asarray(p[slot]), np.asarray(t[slot], np.float32))
                for p, t in zip(pools, state[len(kinds):])]


RNG = np.random.default_rng(5)
PROMPT = RNG.integers(0, ARGS["vocab_size"], 29).tolist()
OTHER = RNG.integers(0, ARGS["vocab_size"], 11).tolist()
NEXT = RNG.integers(0, ARGS["vocab_size"], 5).tolist()


def _serve(stepper, chunk):
    """PROMPT in chunks of `chunk` rows in slot 2, OTHER beside it in
    slot 0 (one chunk, then decode rows), then NEXT's tokens fed one a
    step.  Returns (logits after each step of PROMPT's sequence, the
    tokens of OTHER that were fed)."""
    got, other_fed = [], 0
    for lo in range(0, len(PROMPT), chunk):
        work = [("a", 2, PROMPT[lo:lo + chunk])]
        if lo == 0:
            work.insert(0, ("b", 0, OTHER[:6]))
            other_fed = 6
        elif other_fed < len(OTHER):
            work.insert(0, ("b", 0, OTHER[other_fed:other_fed + 1]))
            other_fed += 1
        got.append(stepper.step(work)[-1])
    for token in NEXT:
        got.append(stepper.step([("a", 2, [token])])[0])
    return np.stack(got), other_fed


@pytest.mark.parametrize("dtype,tol", [("float32", LOGIT_TOL),
                                       ("bfloat16", BF16_TOL)])
@pytest.mark.parametrize("page", [4, 16])
@pytest.mark.parametrize("chunk", [5, BLOCK, 13])
def test_chunks_then_decode_rows_give_the_reference_logits(chunk, page,
                                                           dtype, tol):
    """(a): chunks that end inside a scan block (5, 13) and on its edge
    (8), each carrying the state on from its slot; then the one-token
    update; a second sequence in another slot beside them all the while.
    Every step's logits are the full pass's at that position."""
    model = g.HybridSSMMoELM(**ARGS, dtype=dtype, seed=11)
    got, _ = _serve(_Stepper(model, page=page), chunk)
    tokens = PROMPT + NEXT
    want = _ref_logits(model, tokens, len(tokens))
    ends = [min(lo + chunk, len(PROMPT)) - 1
            for lo in range(0, len(PROMPT), chunk)]
    ends += [len(PROMPT) + i for i in range(len(NEXT))]
    assert want.std() > 0.01
    np.testing.assert_allclose(got, want[ends], atol=tol, rtol=0)


@pytest.mark.parametrize("chunk", [5, 13])
def test_the_scan_and_the_update_leave_the_recurrence_s_state(model, chunk):
    """(b): after chunks and after decode rows a slot holds, in every
    state layer, the recurrence's state and the last three rows of the
    convolution's input; the other sequence's slot holds its own, and
    the slot no sequence used is untouched."""
    stepper = _Stepper(model)
    _, other_fed = _serve(stepper, chunk)
    for slot, tokens in ((2, PROMPT + NEXT), (0, OTHER[:other_fed])):
        for (state, tail), (want_state, want_tail) in zip(
                stepper.slot_state(slot), _ref_states(model, tokens)):
            assert np.abs(want_state).max() > 0.01
            np.testing.assert_allclose(state, want_state, atol=2e-5)
            np.testing.assert_allclose(tail, want_tail, atol=2e-5)
    for state, tail in stepper.slot_state(1) + stepper.slot_state(3):
        assert not state.any() and not tail.any()


def test_a_reused_slot_starts_from_zero_inside_the_step(model):
    """A sequence whose first row is position 0 in a slot that held
    another's state: the logits are a fresh cache's."""
    stepper = _Stepper(model)
    stepper.step([("old", 1, PROMPT[:13])])
    stepper.step([("old", 1, PROMPT[13:14])])
    stepper.cache.free("old")
    got = [stepper.step([("new", 1, OTHER[:1])])[0],      # one row
           stepper.step([("new", 1, OTHER[1:7])])[0]]     # then a chunk
    want = _ref_logits(model, OTHER[:7], 7)[[0, 6]]
    np.testing.assert_allclose(np.stack(got), want, atol=LOGIT_TOL, rtol=0)
    assert stepper.counters["generation.ssm_state_starts"] == 0
    stepper.step([("third", 0, OTHER[:4])])
    assert stepper.counters["generation.ssm_state_starts"] == 1


def test_the_share_adds_up(model):
    """(c): the same rows through the layer holding experts 0-3 and
    through the one holding experts 4-7, the shared expert counted once,
    sum to the layer that holds all eight; every pick is computed on one
    side and counted as gone elsewhere on the other."""
    lp = model.decode_params()["layers"][0]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((13, 32)), jnp.float32)
    valid = jnp.arange(13) < 11

    def run(held):
        part = lp if held is None else dict(
            lp, experts_gate_up=lp["experts_gate_up"][held[0]:sum(held)],
            experts_down=lp["experts_down"][held[0]:sum(held)])
        y, stats = blocks.feed_forward(part, x, valid, 3, None,
                                       "softmax_topk", held)
        return np.asarray(y), np.asarray(stats).tolist()

    whole, whole_stats = run(None)
    low, low_stats = run((0, 4))
    high, high_stats = run((4, 4))
    shared = np.asarray(blocks.gated_mlp(x, lp["shared_gate_up"],
                                         lp["shared_down"]))
    np.testing.assert_allclose(low + high - shared, whole, atol=1e-5)
    assert np.abs(low - shared)[:11].max() > 1e-3
    assert not whole[11:].any() or np.allclose(whole[11:], shared[11:])
    assert len(whole_stats) == 3 and whole_stats[0] == 11 * 3
    assert low_stats[0] + high_stats[0] == 11 * 3
    assert (low_stats[3], high_stats[3]) == (high_stats[0], low_stats[0])
    # the reference's share is the served one
    want = np.asarray(reference.feed_forward(
        dict(lp, experts_gate_up=lp["experts_gate_up"][4:],
             experts_down=lp["experts_down"][4:]), x[:11],
        dict(ARGS, experts_held=(4, 4))))
    np.testing.assert_allclose(high[:11], want, atol=1e-5)


def test_softmax_topk_is_the_reference_s_router(model):
    """(f): the k largest logits, a softmax over those k; no sigmoid,
    no bias, no scale."""
    w_router = model.decode_params()["layers"][2]["w_router"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 32)),
                    jnp.float32)
    experts, weights = moe.route(x, w_router, None, 3, None, "softmax_topk")
    want_experts, want_weights = reference.route(x, w_router, top_k=3)
    np.testing.assert_array_equal(experts, want_experts)
    np.testing.assert_allclose(weights, want_weights, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, w_router, None, 3, None, "softmax")


# ------------------------------ the engine ---------------------------
def _engine(model, pages=64, page=4, slots=3, chunk=12, **kw):
    return g.GenerationEngine(
        model, g.GenerationConfig(
            num_pages=pages, page_size=page, max_decode_slots=slots,
            prefill_chunk_tokens=chunk, **kw),
        metrics=g.GenerationMetrics(StatRegistry()), start=False)


def _assert_reference_argmax(model, prompt, got, args=ARGS):
    logits = _ref_logits(model, prompt + got[:-1], len(got), args)
    top2 = np.sort(logits, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 2 * LOGIT_TOL
    assert decided.sum() >= len(got) - 1
    np.testing.assert_array_equal(
        np.asarray(got)[decided], logits.argmax(-1)[decided])


def _prompts(lengths, seed=8):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, ARGS["vocab_size"], n).tolist() for n in lengths]


def test_engine_serves_the_reference_argmax_and_counts(model):
    """Continuous batching with chunked prefill over three slots, five
    requests (two wait for a slot and reuse one): the reference's
    tokens; (g) the gauges, and the counters of that schedule."""
    eng = _engine(model)
    stats = eng.stats()
    assert (eng.step_mode, type(eng.cache).__name__, eng.prefix_cache_enabled,
            stats["generation.kernel_path"],
            stats["generation.kv_pool_layout"]) == (
                "ragged", "DeviceKVPool", False, "ragged:jnp-reference",
                "kv_rows")
    assert json.loads(stats["generation.kv_layer_groups"]) == {
        "full": 1, "state": 3}
    # an attention layer's row: K and V of 2 heads of 8 in float32; a
    # slot: 3 layers x (3 rows x 64 lanes + 4 x 8 x 16 state) x 4 B
    assert stats["generation.kv_token_bytes"] == 2 * 2 * 8 * 4
    assert stats["generation.kv_state_bytes_a_slot"] == 3 * (
        3 * 64 + 4 * 8 * 16) * 4
    assert (stats["generation.moe_experts_held"],
            stats["generation.moe_router_width"]) == (8, 8)
    assert eng._ragged._n_fixed == 9
    assert [tuple(a.shape) for a in eng.cache.take_pool_state()] == [
        (4, 4, 8, 16), (64, 4, 128), (4, 4, 8, 16), (4, 4, 8, 16),
        (4, 3, 64), (4, 3, 64), (4, 3, 64)]
    prompts = _prompts((5, 23, 40, 9, 17))
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for prompt, handle in zip(prompts, handles):
        _assert_reference_argmax(model, prompt,
                                 handle.result(timeout=5).token_ids)
    snap = eng.metrics.snapshot()
    rows = sum(len(p) + 5 for p in prompts)
    assert snap["generation.moe_assignments_total"] == rows * 3 * 4
    assert snap["generation.moe_assignments_elsewhere"] == 0
    assert snap["generation.ssm_state_starts"] == len(prompts)
    # a row is a decode row (or a one-row chunk) or a chunk's token
    assert (snap["generation.ssm_rows_updated"]
            + snap["generation.ssm_tokens_scanned"]) == rows * 3
    assert snap["generation.ssm_rows_updated"] >= 5 * len(prompts) * 3
    assert eng.cache.num_free_pages == 64
    eng.shutdown()


def test_a_reused_slot_and_a_preempted_sequence_serve_a_fresh_engine_s_tokens(
        model):
    """(d): few pages, so that a sequence is preempted and recomputed,
    and one slot, so that every request reuses it: each request's tokens
    are those of an engine that served it alone."""
    prompts = _prompts((9, 11, 10))
    alone = []
    for prompt in prompts:
        eng = _engine(model, slots=1)
        handle = eng.submit(prompt, max_new_tokens=12)
        eng.run_until_idle()
        alone.append(handle.result(timeout=5).token_ids)
        eng.shutdown()
    eng = _engine(model, pages=9, chunk=4)
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    results = [h.result(timeout=5) for h in handles]
    assert sum(r.preemptions for r in results) > 0
    assert [r.token_ids for r in results] == alone
    snap = eng.metrics.snapshot()
    assert snap["generation.ssm_state_starts"] == len(prompts) + sum(
        r.preemptions for r in results)
    eng.shutdown()
    eng = _engine(model, slots=1)
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    assert [h.result(timeout=5).token_ids for h in handles] == alone
    for prompt, got in zip(prompts, alone):
        _assert_reference_argmax(model, prompt, got)
    eng.shutdown()


def test_a_held_share_serves_the_reference_s_share():
    """The engine over a model that holds experts 4-7 of a router 8
    wide: the reference's tokens under the same share, and the picks
    that went to the absent chip counted."""
    model = g.HybridSSMMoELM(**SHARE, dtype="float32", seed=11)
    eng = _engine(model)
    prompts = _prompts((7, 30))
    handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    for prompt, handle in zip(prompts, handles):
        _assert_reference_argmax(model, prompt,
                                 handle.result(timeout=5).token_ids, SHARE)
    snap = eng.metrics.snapshot()
    rows = sum(len(p) + 4 for p in prompts)
    assert (snap["generation.moe_experts_held"],
            snap["generation.moe_router_width"]) == (4, 8)
    assert (snap["generation.moe_assignments_total"]
            + snap["generation.moe_assignments_elsewhere"]) == rows * 3 * 4
    assert 0 < snap["generation.moe_assignments_elsewhere"] < rows * 3 * 4
    eng.shutdown()


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "ngram_speculation": dict(spec_mode="ngram"),
    "looped_step": dict(loop_steps=4),
    "host_pools": dict(kv_backend="host"),
    "fused_decode": dict(decode="fused"),
    "legacy_step": dict(step_mode="legacy"),
    "one_shot_prefill": dict(prefill_chunk_tokens=0),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_paths_a_recurrence_cannot_hold_for_are_refused_by_name(model, path):
    """(e): by the option's name, and saying why: the state layers."""
    (option, value), = REFUSED[path].items()
    with pytest.raises(g.UnsupportedModelPathError,
                       match=f"state layers.*{option}={value!r}"):
        g.GenerationEngine(model, g.GenerationConfig(
            num_pages=16, page_size=4, **REFUSED[path]), start=False)


def test_pages_of_a_cache_with_state_layers_are_not_exported_or_shared(
        model):
    eng = _engine(model)
    for ask in (lambda: eng.cache.export_pages([0]),
                lambda: eng.cache.import_pages(np.zeros((4, 1, 4, 4, 8)),
                                               np.zeros((4, 1, 4, 4, 8))),
                lambda: eng.cache._copy_page_storage(0, 1)):
        with pytest.raises(UnsupportedCachePathError, match="state layers"):
            ask()
    eng.shutdown()


def test_the_pool_s_state_arrays_and_the_model_s_arguments():
    state = SlotState((3, 64), "bfloat16", (4, 8, 16), "float32")
    assert state.bytes_a_slot == 3 * 64 * 2 + 4 * 8 * 16 * 4
    with pytest.raises(UnsupportedCachePathError, match="row pools"):
        DeviceKVPool(2, 4, 8, state=(("state", "full"), state, 2))
    with pytest.raises(ValueError, match="'state'"):
        DeviceKVPool(2, 4, 8, rows=g.HeadRows(2, 8, "float32"),
                     state=(("state", "dense"), state, 2))
    with pytest.raises(ValueError, match="experts_held"):
        g.HybridSSMMoELM(**dict(SHARE, experts_held=[6, 4]))
    with pytest.raises(ValueError, match="no state-space layer"):
        g.HybridSSMMoELM(**dict(ARGS, layer_types=["attention"] * 4))
    with pytest.raises(ValueError, match="sliding_window"):
        g.HybridSSMMoELM(**ARGS, sliding_window=64)
    a = g.HybridSSMMoELM(**ARGS, seed=2147483900)
    b = g.HybridSSMMoELM(**ARGS, seed=2147483900)
    assert a.params["embed"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(a.params["embed"], b.params["embed"])
    lp = a.params["layers"][0]
    decay = np.exp(-np.asarray(jax.nn.softplus(lp["dt_bias"]))
                   * np.exp(np.asarray(lp["A_log"])))
    assert 0.2 <= decay.min() and decay.max() <= 0.9991
    assert a.kv_slot_state().tail_dtype == np.dtype(jnp.bfloat16)
