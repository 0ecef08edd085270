"""Inside the serving engine's step and request (docs/GENERATION.md
"Reading a trace"): the phase spans of one ragged step, the
`ragged_step` span's attributes, the request's own stamps on every
handle type the engine drives, and the kernel grid's denominator.

All CPU; what is asserted is structure and counts, never a time: which
spans a step opens, in which order and inside which, that nothing is
recorded with the profiler off, that the stamps are ordered, and that
the grid counter is the steps the kernel's compacted grid walks
(ops/pallas ragged_grid_cells).
"""
import math
import statistics

import pytest

from paddle_tpu import generation as gen
from paddle_tpu import profiler
from paddle_tpu.generation import metrics as gmetrics
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import ragged_grid_cells
from paddle_tpu.profiler.monitor import StatRegistry
from paddle_tpu.serving import fleet as fleet_mod
from paddle_tpu.serving.disagg.worker import _StreamHandle
from paddle_tpu.serving.fleet import (FleetConfig, FleetRouter,
                                      ReplicaSpec, _MigrationRelay)

PHASES = ["pack", "dispatch", "post_dispatch", "fetch", "emit", "account"]
PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 4, 2, 6, 1, 8, 3, 3, 5], [11]]


@pytest.fixture(autouse=True)
def _fresh_stats_and_profiler_off():
    reg = StatRegistry.instance()
    for name in list(reg.stats()):
        if name.startswith((gmetrics.PREFIX, fleet_mod.PREFIX)):
            reg.get_stat(name).reset()
    yield
    if profiler._enabled[0]:
        profiler.stop_profiler()


@pytest.fixture(scope="module")
def model():
    return gen.TinyCausalLM(vocab_size=48, num_layers=2, num_heads=2,
                            head_dim=8, seed=3)


def _config(*, slots=4, pages=64, page_size=4, chunk=3, **kw):
    return gen.GenerationConfig(max_decode_slots=slots, num_pages=pages,
                                page_size=page_size,
                                prefill_chunk_tokens=chunk,
                                kv_backend="device", step_mode="ragged",
                                **kw)


def _engine(model, **kw):
    return gen.GenerationEngine(model, _config(**kw), start=False)


def _serve(eng, prompts, n=6, **submit_kw):
    handles = [eng.submit(p, max_new_tokens=n, **submit_kw)
               for p in prompts]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=5)
    return handles


def _traced(eng, prompts, **submit_kw):
    """Serve `prompts` with the profiler on; the recorded spans as
    [(short name, start_s, end_s, args)] in the order they opened."""
    profiler.start_profiler()
    try:
        handles = _serve(eng, prompts, **submit_kw)
        events = list(profiler._events)
    finally:
        profiler.stop_profiler()
    spans = sorted(((name.split("::")[1], start, start + dur, args)
                    for name, _, start, dur, args in events
                    if name.startswith("generation::")),
                   key=lambda e: (e[1], -e[2]))
    return handles, spans


# --------------------------- phase spans ---------------------------------

TRAFFIC = {
    "greedy": {},
    "stochastic": {"sampling": gen.SamplingParams(
        temperature=0.9, top_k=10, top_p=0.9, seed=7)},
    "speculative": {"spec_mode": "ngram"},
}


@pytest.fixture(scope="module", params=sorted(TRAFFIC))
def traced_run(request, model):
    kw = dict(TRAFFIC[request.param])
    engine_kw = {k: kw.pop(k) for k in ("spec_mode",) if k in kw}
    eng = _engine(model, **engine_kw)
    handles, spans = _traced(eng, PROMPTS, **kw)
    eng.shutdown()
    return handles, spans


def _steps(spans):
    """[(ragged_step span, its direct children)]; `sample` rides inside
    `emit` and is no phase."""
    out = []
    for step in (s for s in spans if s[0] == "ragged_step"):
        inside = [s for s in spans if s is not step and s[0] != "sample"
                  and step[1] <= s[1] and s[2] <= step[2]]
        out.append((step, inside))
    return out


def _closings(names):
    """`names` cut into the closings of retired steps: (fetch,) emit,
    account each; None where they are anything else."""
    out, names = [], list(names)
    while names:
        n = 3 if names[0] == "fetch" else 2
        if names[:n] != PHASES[6 - n:]:
            return None
        out.append(names[:n])
        names = names[n:]
    return out


def test_every_step_holds_its_phases_in_order(traced_run):
    """A step's span holds the opening phases of the step it enqueues,
    then the closing phases of the step it retires: none (nothing was in
    flight), one (the step in flight, or at depth 0 its own) or two (the
    step in flight and then its own, which cannot stay in flight)."""
    _, spans = traced_run
    steps = _steps(spans)
    assert len(steps) > 5
    fetches = closed = 0
    for _, inside in steps:
        names = [s[0] for s in inside]
        assert names[:3] == PHASES[:3]
        closings = _closings(names[3:])
        assert closings is not None and len(closings) <= 2, names
        closed += len(closings)
        fetches += sum("fetch" in c for c in closings)
        for before, after in zip(inside, inside[1:]):
            assert before[2] <= after[1], (before, after)
    # every step is closed once: inside a later step's span, or, when
    # nothing is left to plan, at the top level
    top = [s[0] for s in spans if s[0] in PHASES[3:] and not any(
        p[0] == "ragged_step" and p[1] <= s[1] and s[2] <= p[2]
        for p in spans)]
    top = [n for i, n in enumerate(top)        # `_account_step`'s own
           if n != "account" or (i and top[i - 1] == "emit")]
    assert closed + len(_closings(top)) == len(steps)
    # a mid-prompt chunk-only step fetches nothing; every other does
    assert 0 < fetches <= len(steps)


def test_a_schedule_precedes_and_an_account_follows_each_step(traced_run):
    _, spans = traced_run
    top = [s for s in spans if s[0] in ("schedule", "ragged_step")
           or (s[0] == "account"
               and not any(p[0] == "ragged_step" and p[1] <= s[1]
                           and s[2] <= p[2] for p in spans))]
    names = [s[0] for s in top]
    for i, name in enumerate(names):
        if name == "ragged_step":
            assert names[i - 1] == "schedule" and names[i + 1] == "account"
    for before, after in zip(top, top[1:]):
        assert before[2] <= after[1], (before, after)


def test_the_phases_cover_the_step(traced_run):
    _, spans = traced_run
    uncovered = [step[2] - step[1] - sum(s[2] - s[1] for s in inside)
                 for step, inside in _steps(spans)]
    # between two `with` blocks nothing runs: microseconds a step (the
    # median, so that one collector pause on a loaded CPU is no failure)
    assert 0 <= statistics.median(uncovered) < 2e-4


def test_ragged_step_attributes_name_its_requests(traced_run):
    handles, spans = traced_run
    known = {str(h.seq_id) for h in handles}
    assert None not in {h.seq_id for h in handles}
    steps = [s[3] for s in spans if s[0] == "ragged_step"]
    for args in steps:
        assert sorted(args) == ["chunk", "decode", "pages", "seqs", "step"]
        seqs = args["seqs"].split("/")
        assert set(seqs) <= known and len(seqs) == len(set(seqs))
        assert len(seqs) == args["decode"] + args["chunk"]
        # a power-of-two pages bucket, as the executables are cut
        assert args["pages"] >= 1 and not args["pages"] & (args["pages"] - 1)
    assert [a["step"] for a in steps] == sorted({a["step"] for a in steps})
    # no other span carries attributes
    assert all(s[3] is None for s in spans if s[0] != "ragged_step")


def test_step_seqs_match_the_scheduler(model):
    """Step by step: the `seqs` of a step are the sequences the
    scheduler held in slots when it ran (two short prompts: nothing
    finishes or is preempted between the plan and the span's close
    before the last step)."""
    eng = _engine(model)
    profiler.start_profiler()
    handles = [eng.submit(p, max_new_tokens=5) for p in PROMPTS[:2]]
    seen = 0
    while eng.scheduler.active() or eng.scheduler.pending_count():
        eng.step()
        active = {str(s.seq_id) for s in eng.scheduler.active()}
        steps = [e for e in profiler._events
                 if e[0] == "generation::ragged_step"]
        if len(steps) > seen and active:
            seen = len(steps)
            assert active <= set(steps[-1][4]["seqs"].split("/"))
    profiler.stop_profiler()
    assert seen > 3 and all(h.done() for h in handles)
    eng.shutdown()


def test_chrome_trace_export_carries_the_attributes(model, tmp_path):
    import json

    eng = _engine(model)
    profiler.start_profiler()
    _serve(eng, PROMPTS[:1])
    profiler.stop_profiler(profile_path=str(tmp_path / "trace.json"))
    eng.shutdown()
    events = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    steps = [e for e in events if e["name"] == "generation::ragged_step"]
    assert steps and all("seqs" in e["args"] for e in steps)
    assert all("args" not in e for e in events
               if e["name"] == "generation::pack")


def test_profiler_off_records_nothing(model):
    assert not profiler._enabled[0]
    profiler._events.clear()
    profiler._records.clear()
    eng = _engine(model)
    _serve(eng, PROMPTS)
    eng.shutdown()
    assert profiler._events == [] and not profiler._records


@pytest.mark.parametrize("on", [False, True])
def test_attribute_callables_run_only_when_on(on):
    calls = []

    def attr():
        calls.append(1)
        return len(calls)

    if on:
        profiler.start_profiler()
    with profiler.RecordEvent("probe::attrs", plain=3, lazy=attr):
        assert calls == []      # read as the span closes, not before
    if on:
        events = list(profiler._events)
        profiler.stop_profiler()
        assert calls == [1]
        assert events[-1][0] == "probe::attrs"
        assert events[-1][4] == {"plain": 3, "lazy": 1}
    else:
        assert calls == []


# --------------------------- request stamps ------------------------------


def test_stamps_are_ordered_on_a_served_request(traced_run):
    handles, _ = traced_run
    for h in handles:
        assert (h.submitted_s <= h.admitted_s <= h.first_token_s
                <= h.finished_s)
        assert h.prefill_chunks >= 1


@pytest.mark.parametrize("prompt_len,chunk,warm", [
    (3, 3, False), (4, 3, False), (11, 3, False), (11, 16, False),
    (1, 2, False), (14, 3, True), (13, 2, True)])
def test_prefill_chunks_of_a_lone_request(model, prompt_len, chunk, warm):
    eng = _engine(model, chunk=chunk, prefix_cache=warm)
    prompt = [(5 * i + 1) % 48 for i in range(prompt_len)]
    if warm:
        # an earlier request leaves the prompt's first two pages cached
        _serve(eng, [prompt[:9]], n=2)
    (h,) = _serve(eng, [prompt], n=3)
    hit = h.prefix_hit_tokens
    assert (hit >= 8) if warm else (hit == 0)
    assert h.prefill_chunks == math.ceil((prompt_len - hit) / chunk)
    eng.shutdown()


def test_a_readmitted_request_keeps_its_first_admission(model):
    eng = _engine(model, pages=9, chunk=2)
    handles = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    first = {}
    while eng.scheduler.active() or eng.scheduler.pending_count():
        eng.step()
        for h in handles:
            if h.admitted_s is not None:
                first.setdefault(id(h), h.admitted_s)
    results = [h.result(timeout=5) for h in handles]
    assert sum(r.preemptions for r in results) > 0
    for h, r, p in zip(handles, results, PROMPTS):
        assert h.admitted_s == first[id(h)]
        assert h.admitted_s <= h.first_token_s <= h.finished_s
        if r.preemptions:
            # its prompt (and what it had generated) was chunked again
            assert h.prefill_chunks > math.ceil(len(p) / 2)
    eng.shutdown()


def test_a_failed_request_has_no_finish_stamp(model):
    eng = _engine(model)
    h = eng.submit(PROMPTS[0], max_new_tokens=50)
    for _ in range(3):
        eng.step()
    eng.shutdown()
    assert h.exception(timeout=5) is not None
    assert h.admitted_s is not None and h.finished_s is None


class _BareHandle:
    """The least a caller's handle holds (engine.submit's docstring):
    no __slots__, none of the engine's later stamps."""

    def __init__(self):
        self.submitted_s = None
        self.first_token_s = None
        self.prefix_hit_tokens = None
        self.tokens = []
        self.result = None

    def _push_token(self, token):
        self.tokens.append(int(token))

    def _finish(self, result):
        self.result = result

    def set_exception(self, exc):
        self.result = exc

    def done(self):
        return self.result is not None


def test_a_duck_typed_handle_acquires_the_stamps(model):
    eng = _engine(model)
    h = _BareHandle()
    assert not hasattr(h, "admitted_s")
    eng.submit(PROMPTS[2], max_new_tokens=4, handle=h)
    eng.run_until_idle()
    eng.shutdown()
    assert len(h.tokens) == 4
    assert h.submitted_s <= h.admitted_s <= h.finished_s
    assert h.prefill_chunks == math.ceil(len(PROMPTS[2]) / 3)
    assert h.seq_id == 0


def fleet_stat(name):
    return StatRegistry.instance().get_stat(name).get()


@pytest.mark.parametrize("live", [False, True])
def test_stamps_reach_the_client_through_a_migration(model, live):
    """A request drained mid-stream moves to a sibling: cold behind a
    relay handle (which has __slots__), or live with its pages.  Either
    way the sibling's scheduler and engine stamp the client's handle,
    whose first admission stands."""
    fleet = FleetRouter(
        [ReplicaSpec(f"r{i}", model, _config(prefix_cache=True))
         for i in range(2)],
        FleetConfig(routing="affinity", start=False, seed=0))
    # a request that stays on the sibling, so that the migrated one
    # gets another id there than it had at home
    fleet.submit(PROMPTS[0], max_new_tokens=30, session="other")
    h = fleet.submit(PROMPTS[2], max_new_tokens=10, session="s")
    home = fleet.replica_of("s")
    assert fleet.replica_of("other") != home
    eng = fleet._replicas[home].engine
    while h.n_streamed < 3:
        eng.step()
    fleet._replicas[fleet.replica_of("other")].engine.step()
    admitted, chunks = h.admitted_s, h.prefill_chunks
    assert admitted is not None and h.finished_s is None and h.seq_id == 0
    fleet.drain(home, migrate=True, live=live)
    replayed = fleet_stat(fleet_mod.MIGRATED_REPLAY_TOKENS)
    assert (replayed == 0) if live else (replayed >= 3)
    fleet.run_until_idle()
    assert len(h.result(timeout=5).token_ids) == 10
    assert h.admitted_s == admitted
    assert h.seq_id == 1
    # a cold re-run chunks the prompt again, a live move does not
    assert (h.prefill_chunks == chunks) if live \
        else (h.prefill_chunks > chunks)
    assert h.submitted_s <= h.admitted_s <= h.first_token_s <= h.finished_s
    fleet.shutdown()


@pytest.mark.parametrize("make", [
    gen.GenerationHandle,
    lambda: _MigrationRelay(gen.GenerationHandle(), skip=0),
    lambda: _StreamHandle(7, lambda event: None),
], ids=["engine", "fleet-relay", "subprocess-stream"])
def test_every_handle_type_the_engine_drives_takes_the_stamps(make):
    """Two of them have __slots__: a stamp they do not list would raise
    AttributeError at a request's first admission."""
    h = make()
    assert (h.admitted_s, h.finished_s, h.prefill_chunks, h.seq_id) == (
        None, None, 0, None)
    h.admitted_s, h.finished_s, h.seq_id = 1.0, 2.0, 3
    h.prefill_chunks = getattr(h, "prefill_chunks", 0) + 1
    assert (h.admitted_s, h.finished_s, h.prefill_chunks, h.seq_id) == (
        1.0, 2.0, 1, 3)


def test_the_relay_reads_and_writes_the_clients_stamps():
    client = _BareHandle()
    relay = _MigrationRelay(client, skip=2)
    assert relay.admitted_s is None and relay.prefill_chunks == 0
    relay.admitted_s = 1.5
    relay.prefill_chunks = relay.prefill_chunks + 1
    relay.finished_s = 2.5
    relay.seq_id = 7
    assert (client.admitted_s, client.prefill_chunks, client.finished_s,
            client.seq_id) == (1.5, 1, 2.5, 7)


# ----------------------- the kernel grid's denominator -------------------


@pytest.mark.parametrize("cell_tokens", [8, 128])
def test_grid_cells_per_dispatch_on_the_kernel_path(model, monkeypatch,
                                                    cell_tokens):
    """Groups of two pages, and the module's own 128 keys (here every
    page of a bucket in one group)."""
    monkeypatch.setattr(pa, "RAGGED_CELL_TOKENS", cell_tokens)
    eng = _engine(model, slots=6, chunk=16, use_kernel=True)
    handles = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    step = eng._ragged
    page_size = eng.cache.page_size
    assert pa.ragged_cell_shape(page_size, 64, step.max_tokens)[2] == 8
    cells = dispatches = 0       # chunk 16 + 6 slots, q_block 8
    while eng.scheduler.active() or eng.scheduler.pending_count():
        before = eng.metrics.snapshot()
        eng.step()
        after = eng.metrics.snapshot()
        grew = (after.get(gmetrics.STEP_GRID_CELLS, 0)
                - before.get(gmetrics.STEP_GRID_CELLS, 0))
        if grew:
            dispatches += 1
            cells += grew
            shape = (step.max_seqs, step.last_pages_bucket, step.max_tokens)
            per = pa.ragged_cell_shape(page_size, *shape[1:])[0]
            assert per == min(cell_tokens // page_size, shape[1])
            # page slots: G a cell the grid walked, which hold the
            # visible (tile, page) pairs and padding
            assert grew == step.last_grid_cells and grew % per == 0
            # (3 tiles + 7 descriptors - 1) x the bucket's groups: the list
            assert step.last_score_blocks <= grew <= per * ragged_grid_cells(
                *shape, page_size) == per * 9 * -(-shape[1] // per)
    for h in handles:
        h.result(timeout=5)
    snap = eng.metrics.snapshot()
    assert dispatches > 5 and snap[gmetrics.STEP_GRID_CELLS] == cells
    # the grid is the live cells' page slots: the part of them that
    # computes is what the kernel without query tiles would compute, less
    # the tiles outside a descriptor's rows
    assert 0 < snap[gmetrics.STEP_SCORE_BLOCKS] < cells
    assert (snap[gmetrics.STEP_SCORE_BLOCKS]
            < snap[gmetrics.STEP_SCORE_BLOCKS_UNTILED])
    assert (snap["generation.ragged_pages_per_cell"],
            snap["generation.ragged_heads_per_cell"]) == (
        cell_tokens // page_size, model.num_heads)
    assert snap["generation.latent_pages_per_cell"] == 0
    eng.shutdown()


def test_grid_cells_are_zero_on_the_reference_path(model):
    eng = _engine(model, slots=6, chunk=16)
    _serve(eng, PROMPTS)
    snap = eng.metrics.snapshot()
    assert (snap["generation.ragged_pages_per_cell"],
            snap["generation.ragged_heads_per_cell"]) == (0, 0)
    assert snap.get(gmetrics.STEP_GRID_CELLS, 0) == 0
    assert snap.get(gmetrics.STEP_SCORE_BLOCKS, 0) == 0
    assert snap[gmetrics.STEPS_TOTAL] > 0
    eng.shutdown()


# ------------------------------ what went --------------------------------


def test_tokens_per_s_gauge_is_gone_and_steps_still_count(model):
    eng = _engine(model)
    _serve(eng, PROMPTS)
    snap = eng.stats()
    eng.shutdown()
    assert "generation.tokens_per_s" not in snap
    # every step of this run samples at least one token or is a
    # mid-prompt chunk-only step, which observe_step does not count
    assert 0 < snap["generation.steps_total"] <= eng.step_seq
    assert snap["generation.tokens_total"] == 6 * len(PROMPTS)
    for name in ("StepTimer", "TOKENS_PER_S"):
        assert not hasattr(gmetrics, name)
    assert not hasattr(gmetrics.GenerationMetrics, "_EWMA")


def test_step_programs_are_named_and_read_only_when_asked(model,
                                                          monkeypatch):
    """`profiler.device_op_scopes()` through `CompiledModelCache`: the
    engine's ragged step compiles one program a pages bucket, named
    after it (`ragged_step_p<bucket>`), and keeps a reference to it and
    nothing more; the compiled text is read when the profiler stops,
    once, and the map then holds every bucket's module with the step's
    parts in it and no other part."""
    import jax

    from paddle_tpu.generation.fused import STEP_SCOPES

    reads = []
    as_text = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **kw: reads.append(self)
                        or as_text(self, *a, **kw))
    eng = _engine(model)
    _serve(eng, PROMPTS)
    assert not reads
    buckets = {key[4][0][1] for key in eng._ragged.cached_buckets()}
    assert buckets
    profiler.start_profiler()
    assert not reads
    profiler.stop_profiler()
    assert len(reads) == len(buckets)
    scopes = profiler.device_op_scopes()
    assert len(reads) == len(buckets)         # read once, kept as strings
    for bucket in buckets:
        parts = {path.split("/")[0]
                 for path in scopes[f"jit_ragged_step_p{bucket}"].values()}
        # (XLA:CPU fuses the hand-over's gather into the embedding's)
        assert {"embed", "attention", "mlp", "head"} <= parts
        assert parts <= set(STEP_SCOPES) | {""}
