"""The ragged step keeps one step in flight (engine._step_ragged).

Step N+1 is planned, packed and enqueued before step N's tokens are
read; its decode rows take their token from step N's ids on the device
(fused.hand_over_tokens).  All CPU, the kernel off: what is asserted is
tokens, order and counts, never a time.

1. Greedy streams are the eager oracle's token for token over every way
   a sequence leaves or joins while a step is in flight: by length (the
   host knows it a step ahead), by a stop token or sequence, a deadline
   or a cancel (the row rode in vain: dropped, counted), a chunk whose
   last row samples, a preemption, a migration.
2. The pipeline drains, a step at a time, where the host cannot run
   ahead (a stochastic sampler, speculation, a plan that would preempt,
   a call that needs the engine settled) and then runs the sequence it
   always had: the same tokens.
3. One dispatch and at most one host sync a step, one executable a
   pages bucket, and `dispatch` of step N+1 before `fetch` of step N.
"""
import time

import numpy as np
import pytest

from paddle_tpu import generation as gen
from paddle_tpu import profiler
from paddle_tpu.generation import metrics as gmetrics
from paddle_tpu.profiler.monitor import StatRegistry
from paddle_tpu.serving.admission import (DeadlineExceededError,
                                          ServingError)

from gen_oracle import greedy_oracle as _ref  # noqa: E402 cross-module memo

PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 4, 2], [11]]
LONG = [9, 9, 9, 4, 2, 6, 1, 8, 3, 3, 5]


@pytest.fixture(scope="module")
def model():
    return gen.TinyCausalLM(vocab_size=48, num_layers=2, num_heads=2,
                            head_dim=8, seed=3)


def _engine(model, *, slots=4, pages=64, page_size=4, chunk=3, **kw):
    kw.setdefault("step_mode", "ragged")
    cfg = gen.GenerationConfig(max_decode_slots=slots, num_pages=pages,
                               page_size=page_size,
                               prefill_chunk_tokens=chunk,
                               kv_backend="device", use_kernel=False, **kw)
    return gen.GenerationEngine(
        model, cfg, metrics=gen.GenerationMetrics(StatRegistry()),
        start=False)


def _stat(eng, name):
    return eng.metrics.snapshot().get(gmetrics.PREFIX + name, 0)


def _step_until(eng, ready, limit=200):
    for _ in range(limit):
        eng.step()
        if ready():
            return
    raise AssertionError("the engine never got there")


def _in_flight_decoding(eng, n=1):
    """A step is in flight that holds the next token of `n` sequences."""
    return lambda: (eng._inflight is not None
                    and len(eng._inflight.samplers) >= n)


# ------------------------ tokens, every way out --------------------------


def test_a_mixed_run_is_the_eager_oracle_token_for_token(model):
    """In one run: finishes by length, by a stop token and by a stop
    sequence with a step in flight, and a prompt whose last chunk
    samples and which decodes from that id, left on the device."""
    eng = _engine(model, prefix_cache=True)
    free = {i: _ref(model, p, 10) for i, p in enumerate(PROMPTS)}
    stop_at, seq_at = 4, 5
    stop_seq = tuple(free[2][seq_at - 1:seq_at + 1])
    # only the token that completes the sequence, the first time it is
    # completed, is withheld
    cut = next(i for i in range(1, 10)
               if tuple(free[2][i - 1:i + 1]) == stop_seq)
    seen = []
    pad = eng._ragged.pad

    def recording_pad(*args, **kw):
        fixed = pad(*args, **kw)
        seen.append((fixed[6].copy(), fixed[-1].copy()))
        return fixed

    eng._ragged.pad = recording_pad
    handles = [
        eng.submit(PROMPTS[0], max_new_tokens=10),
        eng.submit(PROMPTS[1], max_new_tokens=10,
                   stop_tokens=(free[1][stop_at],)),
        eng.submit(PROMPTS[2], max_new_tokens=10,
                   sampling=gen.SamplingParams(stop_sequences=[stop_seq])),
        eng.submit(LONG, max_new_tokens=6),
    ]
    eng.run_until_idle()
    got = [h.result(timeout=5) for h in handles]
    assert got[0].token_ids == free[0] and got[0].finish_reason == "length"
    first = free[1].index(free[1][stop_at])
    assert got[1].token_ids == free[1][:first]
    assert got[2].token_ids == free[2][:cut]
    assert [g.finish_reason for g in got[1:3]] == ["stop", "stop"]
    assert got[3].token_ids == _ref(model, LONG, 6)
    # both stops rode the step behind theirs in vain
    assert _stat(eng, "overlap_rows_discarded") == 2
    assert _stat(eng, "steps_overlapped") > 5
    assert _stat(eng, "pipeline_drains") == 0
    assert eng._inflight is None and eng.cache.utilization() == 0.0
    # some decode row took its token from a CHUNK's descriptor of the
    # step before (more than one row long: no decode row's)
    handed = [(src[src >= 0], before[0])
              for before, (_, src) in zip(seen, seen[1:])]
    assert any((lens[src] > 1).any() for src, lens in handed if src.size)
    assert all((src < 0).all() for _, src in seen[:1])
    # the pages a stopped sequence left in the prefix index hold the
    # tokens it kept and nothing of the row that rode in vain: a prompt
    # running through them and on is served warm, and right
    again = PROMPTS[1] + got[1].token_ids + [5, 6, 7]
    h = eng.submit(again, max_new_tokens=6)
    eng.run_until_idle()
    assert h.prefix_hit_tokens >= 4
    assert h.result(timeout=5).token_ids == _ref(model, again, 6)
    eng.shutdown()


def test_a_plan_that_would_preempt_drains_first(model):
    eng = _engine(model, pages=9, chunk=2)
    handles = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    eng.run_until_idle()
    results = [h.result(timeout=5) for h in handles]
    for res, p in zip(results, PROMPTS):
        assert res.token_ids == _ref(model, p, 12)
    assert sum(r.preemptions for r in results) > 0
    assert _stat(eng, "pipeline_drains.preempt") > 0
    assert _stat(eng, "steps_overlapped") > 0
    assert eng.cache.utilization() == 0.0
    eng.shutdown()


def test_a_stochastic_request_beside_greedy_ones_drains(model):
    """Its token is drawn on the host: the steps that sample it run at
    depth 0, and every stream is what the legacy path gives under the
    same seeds."""
    def run(mode, chunk):
        eng = _engine(model, chunk=chunk, step_mode=mode)
        hs = [eng.submit(p, max_new_tokens=10, sampling=(
            gen.SamplingParams(temperature=0.9, top_k=10, top_p=0.9,
                               seed=41) if i == 1 else None))
            for i, p in enumerate(PROMPTS)]
        hs.append(eng.submit(LONG, max_new_tokens=14))
        eng.run_until_idle()
        out = [h.result(timeout=5).token_ids for h in hs]
        drains = (_stat(eng, "pipeline_drains.stochastic"),
                  _stat(eng, "steps_overlapped"))
        eng.shutdown()
        return out, drains

    ragged, (stochastic, overlapped) = run("ragged", 3)
    assert ragged == run("legacy", 0)[0]
    # the long greedy request outlives the stochastic one: both depths
    assert stochastic > 0 and overlapped > 0


def test_cancel_with_a_step_in_flight(model):
    eng = _engine(model)
    h1 = eng.submit(PROMPTS[0], max_new_tokens=12)
    h2 = eng.submit(PROMPTS[2], max_new_tokens=12)
    _step_until(eng, _in_flight_decoding(eng, 2))
    streamed = h1.n_streamed
    assert eng.cancel(h1)
    # the step in flight was read first: its token reached the stream
    assert eng._inflight is None and h1.n_streamed == streamed + 1
    assert _stat(eng, "pipeline_drains.api") == 1
    res = h1.result(timeout=5)
    assert res.finish_reason == "cancelled"
    assert res.token_ids == _ref(model, PROMPTS[0], 12)[:streamed + 1]
    eng.run_until_idle()
    assert h2.result(timeout=5).token_ids == _ref(model, PROMPTS[2], 12)
    assert eng.cache.utilization() == 0.0
    eng.shutdown()


def test_a_deadline_with_a_step_in_flight(model):
    eng = _engine(model)
    h1 = eng.submit(PROMPTS[0], max_new_tokens=40, timeout_ms=600000)
    h2 = eng.submit(PROMPTS[2], max_new_tokens=12)
    _step_until(eng, _in_flight_decoding(eng, 2))
    (late,) = [s for s in eng.scheduler.active() if s.handle is h1]
    late.request.deadline = time.monotonic() - 1.0
    eng.run_until_idle()
    with pytest.raises(DeadlineExceededError):
        h1.result(timeout=5)
    # reaped while planning, its row of the step in flight was dropped
    assert _stat(eng, "overlap_rows_discarded") == 1
    assert h2.result(timeout=5).token_ids == _ref(model, PROMPTS[2], 12)
    assert eng.cache.utilization() == 0.0
    eng.shutdown()


def test_shutdown_delivers_the_step_in_flight(model):
    eng = _engine(model)
    h = eng.submit(PROMPTS[0], max_new_tokens=30)
    _step_until(eng, _in_flight_decoding(eng))
    streamed = h.n_streamed
    eng.shutdown()
    assert eng._inflight is None and h.n_streamed == streamed + 1
    with pytest.raises(ServingError):
        h.result(timeout=5)
    got = []
    with pytest.raises(ServingError):
        for token in h.tokens(timeout=1):
            got.append(token)
    assert got == _ref(model, PROMPTS[0], 30)[:streamed + 1]


def test_run_until_idle_leaves_nothing_in_flight(model):
    eng = _engine(model)
    hs = [eng.submit(p, max_new_tokens=5) for p in PROMPTS]
    steps = eng.run_until_idle()
    assert eng._inflight is None and all(h.done() for h in hs)
    # depth one: a token costs a call, plus the one that fills the pipe
    assert steps <= 5 + 2 + 1
    assert eng.step() == 0
    eng.shutdown()


def test_a_migration_round_trip_with_a_step_in_flight(model):
    src, dst = _engine(model), _engine(model)
    h = src.submit(PROMPTS[2], max_new_tokens=14)
    _step_until(src, lambda: _in_flight_decoding(src)() and h.n_streamed > 3)
    cold, live = src.evacuate_for_migration()
    assert not cold and len(live) == 1 and src._inflight is None
    assert _stat(src, "pipeline_drains.api") == 1
    # the snapshot holds the token that was in flight, applied
    assert live[0]["tokens"][len(PROMPTS[2]):] == \
        _ref(model, PROMPTS[2], 14)[:live[0]["n_generated"]]
    assert live[0]["cache_len"] == len(live[0]["tokens"]) - 1
    assert dst.import_sequence(live[0])
    dst.run_until_idle()
    assert h.result(timeout=5).token_ids == _ref(model, PROMPTS[2], 14)
    src.shutdown()
    dst.shutdown()


def test_callers_and_cancels_beside_the_worker(model):
    """The background worker keeps a step in flight while four callers
    submit in closed loops and a fifth cancels what it can (switch
    interval shortened): every stream is the oracle's, or a prefix of it
    where it was cancelled, and nothing is left in flight or in pages."""
    import sys
    import threading

    eng = _engine(model, slots=3)
    results, errors = {}, []

    def caller(i):
        try:
            for j in range(3):
                prompt = PROMPTS[(i + j) % len(PROMPTS)]
                h = eng.submit(prompt, max_new_tokens=9)
                results[i, j] = (prompt, h, h.result(timeout=60))
        except Exception as e:   # noqa: BLE001 — reported below
            errors.append(e)

    def canceller():
        for _ in range(200):
            for _, h, *_ in list(results.values())[-2:]:
                eng.cancel(h)
            for state in eng.scheduler.active()[:1]:
                eng.cancel(state.handle)
            time.sleep(0.002)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.start()
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(4)] + [threading.Thread(target=canceller)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(interval)
        eng.shutdown()
    assert len(results) == 12 and eng._inflight is None
    for prompt, _, res in results.values():
        want = _ref(model, prompt, 9)
        if res.finish_reason == "cancelled":
            assert res.token_ids == want[:len(res.token_ids)]
        else:
            assert res.token_ids == want
    assert {r.finish_reason for _, _, r in results.values()} >= {"length"}
    assert eng.cache.utilization() == 0.0


# --------------------------- order and counts ----------------------------


def _events_of(eng, prompts, n):
    profiler.start_profiler()
    try:
        hs = [eng.submit(p, max_new_tokens=n) for p in prompts]
        calls = []
        while eng.scheduler.active() or eng.scheduler.pending_count():
            before = len(profiler._events)
            eng.step()
            calls.append([e[0].split("::")[1]
                          for e in profiler._events[before:]])
    finally:
        profiler.stop_profiler()
    return hs, calls


def test_dispatch_of_the_next_step_precedes_fetch_of_this_one(model):
    eng = _engine(model, chunk=8)
    hs, calls = _events_of(eng, PROMPTS, 8)
    for h, p in zip(hs, PROMPTS):
        assert h.result(timeout=5).token_ids == _ref(model, p, 8)
    order = [n for call in calls for n in call if n in ("dispatch", "fetch")]
    # the pipe fills with two dispatches, and empties with two fetches
    assert order[:3] == ["dispatch", "dispatch", "fetch"]
    assert order[-2:] == ["fetch", "fetch"]
    for call in calls:
        # one dispatch a step and at most one host sync, as ever
        assert call.count("dispatch") <= 1 and call.count("fetch") <= 1
        if "dispatch" in call and "fetch" in call:
            assert call.index("dispatch") < call.index("fetch")
    dispatched = sum(call.count("dispatch") for call in calls)
    assert _stat(eng, "steps_overlapped") == dispatched - 1
    assert _stat(eng, "decode_host_syncs_per_step") <= 1
    assert _stat(eng, "decode_dispatches_per_step") == 1
    # one executable a pages bucket, as before the hand-over
    assert eng._ragged.compile_count == len(eng._ragged.cached_buckets())
    assert eng._ragged.compile_count <= 3
    eng.shutdown()


def test_speculation_keeps_the_pipeline_at_depth_zero(model):
    eng = _engine(model, chunk=8, spec_mode="ngram")
    repeats = [[5, 6, 7, 5, 6, 7, 5, 6], [4, 8, 4, 8, 4, 8, 4]]
    hs, calls = _events_of(eng, repeats, 8)
    for h, p in zip(hs, repeats):
        assert h.result(timeout=5).token_ids == _ref(model, p, 8)
    order = [n for call in calls for n in call if n in ("dispatch", "fetch")]
    assert order == ["dispatch", "fetch"] * (len(order) // 2)
    assert _stat(eng, "steps_overlapped") == 0
    assert _stat(eng, "pipeline_drains.speculation") == len(order) // 2
    assert all(eng._inflight is None for _ in calls)
    eng.shutdown()


# ------------------- the three models with a row cache -------------------


def _latent():
    from tests.test_latent_moe import ARGS

    return gen.LatentMoELM(**ARGS, dtype="float32", seed=11), {
        "prefix_cache": True}


def _gqa_window():
    from tests.test_gqa_window_moe import ARGS

    return gen.GQAWindowMoELM(**ARGS, dtype="float32", seed=11), {}


def _hybrid():
    from tests.test_hybrid_ssm_moe import ARGS

    return gen.HybridSSMMoELM(**ARGS, dtype="float32", seed=11), {}


@pytest.mark.parametrize("build", [_latent, _gqa_window, _hybrid])
def test_a_row_cache_model_serves_the_same_at_depth_one(build):
    """The same engine with the pipeline held at depth 0 (today's
    sequence, steered here and by no option) is the oracle: the same
    tokens, and the same counts out of the step's counter blocks, each
    read when its own step is retired and never a later step's."""
    lm, extra = build()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, lm.vocab_size, n).tolist()
               for n in (9, 23, 5, 14, 11)]

    def run(depth_one):
        eng = gen.GenerationEngine(lm, gen.GenerationConfig(
            num_pages=64, page_size=4, max_decode_slots=2,
            prefill_chunk_tokens=8, use_kernel=False, **extra),
            metrics=gen.GenerationMetrics(StatRegistry()), start=False)
        if not depth_one:
            eng._drain_reason = lambda step: "stochastic"
        read = []
        count = eng.metrics.count_model_step
        eng.metrics.count_model_step = lambda names, values: (
            read.append(eng._inflight), count(names, values))
        hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        out = [h.result(timeout=5).token_ids for h in hs]
        snap = eng.metrics.snapshot()
        assert eng._inflight is None and eng.cache.utilization() == 0.0
        wg = eng.cache.window_group
        if wg is not None:
            assert wg.free_pages == wg.num_pages
            assert snap["generation.kv_window_pages_released"] > 0
        eng.shutdown()
        return out, snap, read

    out, snap, read = run(True)
    ref_out, ref_snap, _ = run(False)
    assert out == ref_out
    assert snap["generation.steps_overlapped"] > 10
    assert "generation.steps_overlapped" not in ref_snap
    # what is counted a row is the same; what is counted a step (the
    # busiest expert, the experts touched) follows the batches, which a
    # slot freed one step later makes up differently
    counted = [name for name in lm.step_counters
               if not name.endswith(("_max_expert", "_experts_touched"))]
    assert all(snap[n] == ref_snap[n] for n in counted)
    assert snap[counted[0]] > 0
    # a block was read a step: mostly with the next step in flight, whose
    # own block that read did not touch
    assert sum(step is not None for step in read) > 10
    if "generation.ssm_state_starts" in snap:
        # five sequences through two slots: every slot reused, every
        # start from zero inside a step behind the one before
        assert snap["generation.ssm_state_starts"] >= 5
