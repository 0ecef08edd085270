"""Runner for cells that serve a model some of whose layers keep a
recurrent state a decode slot and no pages (`builder.model` by import
path, as `serve_model.py`), through `GenerationEngine`, with NO shared
prefix: the engine refuses the prefix cache for such a model (a hit
would need the state at the matched prefix's end, which nobody kept).

The load generator, the stamped handle, the window and the page-bucket
warm-up are `serve_engine.py`'s, the model loader and the reference
readings `serve_model.py`'s, by import: a cell of this runner is offered
and measured exactly as the cells of those.

The reference check (outside the window, on the engine the window then
uses, through the same executables): greedy requests of
`check.prompt_tokens` seeded tokens each, live together (the first of
them is the one whose slot is reused: a sequence takes the lowest free
slot, so it sits in slot 0), `check.new_tokens` served tokens each; when
the first has finished, one more of `check.reuse_prompt_tokens` tokens
is sent and takes the slot it left, whose state arrays still hold the
first's.  One pass of the plain float32 reference (a token-by-token
recurrence) over prompt + served tokens gives, at each served position,
the reference's logits and its routers' closest call.  A served token
AGREES when its reference logit is within `check.logit_margin` of the
reference's top.  `verdict()` holds the run to all of:

- of all served tokens at least `check.min_agreeing_share` agree;
- in each request at least `check.min_agreeing_tokens_a_request` agree
  (a request served by a wrong computation agrees nowhere);
- the states did what the configuration says: the last request sat in
  the slot the first had left, every sequence started its slot from zero
  once and only a preemption started one again
  (`generation.ssm_state_starts`), after the check no page of the
  attention layers' pool is held, and the state the LONGEST request left
  in its slot, in the first layer, is the reference's within
  `check.state_relative_error` (`state_error()`: what tokens cannot
  show: a state kept in bfloat16 serves the same tokens and misses its
  float32 state by its rounding's walk over a slow head's memory, a
  thousand tokens; behind a short prompt the walk is no longer than the
  bf16 activations' own mark on the state).

Why a share and not every token: `serve_model.py`'s docstring and the
configuration's `check.why`.
"""
import json
import time

import numpy as np

from benchmarks.runners import serve_engine, serve_model

_load = serve_model._load     # tools/precision_control.py builds the model
# what `tools/precision_control.py` may ask of this runner's reference:
# every matrix in float8, or the recurrent state rounded to bfloat16
# after every token (the configuration states float32)
CONTROLS = ("float8", "state_bf16")


def check_lengths(check, traffic):
    """The check's prompt lengths, for `tools/precision_control.py`."""
    del traffic
    return [int(n) for n in check["prompt_tokens"]] + [
        int(check["reuse_prompt_tokens"])]


def verdict(check, requests, states_ok):
    """(correct, the largest shortfall, one line a limit) of the
    readings of the check's requests.  A pure function of the readings:
    the precision control passes a reference's own (and True for the
    states, which a reference has not)."""
    margin = float(check["logit_margin"])
    floor = int(check["min_agreeing_tokens_a_request"])
    short = np.concatenate([r["short"] for r in requests])
    share = float(np.mean(short <= margin))
    ok = bool(states_ok) and share >= float(check["min_agreeing_share"])
    lines = [f"{share:.3f} of {len(short)} served tokens within {margin} of "
             f"the reference's top (at least {check['min_agreeing_share']}), "
             f"largest shortfall {short.max():.4g}; slots and pages "
             f"{'as expected' if states_ok else 'NOT as expected'}"]
    for r in requests:
        agree = int(np.sum(np.asarray(r["short"]) <= margin))
        ok = ok and agree >= floor
        lines.append(f"{r['what']}: {agree}/{len(r['short'])} agree (at "
                     f"least {floor}): {'ok' if agree >= floor else 'NOT ok'}")
    return ok, float(short.max()), lines


def state_error(want, state):
    """How far `state` [heads, P, N] (what a system holds in the first
    layer after some tokens) lies from `want` (the float32 reference's
    `first_layer_state` after the same), relative, in the Frobenius
    norm."""
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(state, np.float64) - want)
                 / np.linalg.norm(want))


def _slot_of(engine, prompt, timeout_s=30.0):
    """The decode slot the request with `prompt` sits in (None if it was
    not seen in one: it finished sooner than this looked)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for slot, state in enumerate(list(engine.scheduler.slots)):
            if state is not None and state.request.prompt == prompt:
                return slot
        time.sleep(0.001)
    return None


def check_against_reference(ctx, engine, model, check):
    """Returns (ok, worst shortfall)."""
    reference = ctx.module("reference", ctx.config["reference"])
    rng = np.random.default_rng([int(ctx.seed), 0xC0DE])
    n_new = int(check["new_tokens"])
    timeout = float(check["timeout_s"])
    before = engine.stats()
    prompts = [rng.integers(0, model.vocab_size, n).tolist()
               for n in check_lengths(check, None)]
    *together, reuser = prompts
    handles = [engine.submit(p, max_new_tokens=n_new) for p in together]
    left = _slot_of(engine, together[0])
    longest = max(range(len(together)), key=lambda i: len(together[i]))
    kept = _slot_of(engine, together[longest])
    first = handles[0].result(timeout=timeout)
    handles.append(engine.submit(reuser, max_new_tokens=n_new))
    taken = _slot_of(engine, reuser)
    results = [first] + [h.result(timeout=timeout) for h in handles[1:]]
    served = [r.token_ids for r in results]
    for got in served:
        if len(got) != n_new:
            raise RuntimeError(f"check request returned {len(got)} "
                               f"tokens, not {n_new}")
    after = engine.stats()
    preempted = sum(r.preemptions for r in results)
    starts = (after.get("generation.ssm_state_starts", 0)
              - before.get("generation.ssm_state_starts", 0))
    held = engine.cache.num_pages - engine.cache.num_free_pages
    # the engine is idle and no later request sat in the longest one's
    # slot (the last took the lowest free one): it holds what that
    # request left, the state after its prompt and all served tokens
    # but the last
    want = reference.first_layer_state(
        model.decode_params(), together[longest] + served[longest][:-1],
        ctx.builder["model_args"])
    error = None if kept in (None, taken) or want is None else state_error(
        want, engine.cache.latent_pool(0)[kept])
    limit = float(check["state_relative_error"])
    states_ok = (left is not None and taken == left
                 and starts == len(prompts) + preempted and held == 0
                 and error is not None and error <= limit)
    ctx.note(f"slots in the check: the first request sat in slot {left} "
             f"and the last took slot {taken}; {starts} states started "
             f"from zero for {len(prompts)} requests and {preempted} "
             f"preemptions; {held} pages held after it; the state the "
             f"longest request left in slot {kept}, first layer, lies "
             f"{error} from the reference's, relative (at most {limit})")
    requests = [serve_model.reference_readings(
        ctx, reference, model, prompt, got, f"prompt of {len(prompt)} tokens")
        for prompt, got in zip(prompts, served)]
    requests[-1]["what"] += ", in a slot another request left"
    ok, worst, lines = verdict(check, requests, states_ok)
    for line in lines:
        ctx.note("reference check: " + line)
    margin = float(check["logit_margin"])
    agree = [int(np.sum(np.asarray(r["short"]) <= margin)) for r in requests]
    ctx.checks.update({
        "agreeing_share": (
            sum(agree) / sum(len(r["short"]) for r in requests),
            check["min_agreeing_share"]),
        "least_agreeing_tokens_a_request": (
            min(agree), check["min_agreeing_tokens_a_request"]),
        "slot_of_the_last_request": (-1 if taken is None else taken,
                                     -2 if left is None else left),
        "states_started_from_zero": (starts, len(prompts) + preempted),
        "state_relative_error": (float("inf") if error is None else error,
                                 limit),
        "pages_held_after_the_check": (held, 0)})
    return ok, worst


def build(ctx):
    import jax

    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry

    b = ctx.builder
    t0 = time.monotonic()
    model = _load(b["model"])(**b["model_args"], seed=ctx.seed)
    jax.block_until_ready(model.decode_params())
    ctx.clock["weights_s"] = time.monotonic() - t0
    metrics = g.GenerationMetrics(StatRegistry())
    engine = g.GenerationEngine(model, g.GenerationConfig(**b["engine"]),
                                metrics=metrics)
    try:
        stats = engine.stats()
        picked = {"step_mode": engine.step_mode,
                  "kernel_path": stats["generation.kernel_path"],
                  "pools": type(engine.cache).__name__,
                  "pool_layout": stats["generation.kv_pool_layout"],
                  "layer_groups": json.loads(
                      stats["generation.kv_layer_groups"]),
                  "chunked": engine.prefill_chunk_tokens > 0,
                  "prefix_cache": bool(engine.prefix_cache_enabled)}
        ctx.note(f"engine picked {picked}, chunk "
                 f"{engine.prefill_chunk_tokens}, {engine.cache.num_pages} "
                 f"pages of {stats['generation.kv_token_bytes']} B a token, "
                 f"{stats['generation.kv_state_bytes_a_slot']} B of state "
                 f"a slot over {b['engine']['max_decode_slots']} slots, "
                 f"{stats['generation.moe_experts_held']} experts held of a "
                 f"router {stats['generation.moe_router_width']} wide")
        if picked != b["expect"]:
            raise RuntimeError(f"the engine picked {picked}, the "
                               f"configuration expects {b['expect']}")
        contexts = [serve_engine._max_context(ctx.traffic),
                    max(check_lengths(ctx.config["check"], None))
                    + int(ctx.config["check"]["new_tokens"])]
        for pages in serve_engine._pages_buckets(engine, max(contexts)):
            engine.prewarm_decode(1, pages)
        ok, worst = check_against_reference(ctx, engine, model,
                                            ctx.config["check"])
    except BaseException:
        engine.shutdown(timeout=30.0)
        raise
    return engine, metrics, ok, worst


def run(ctx):
    engine, metrics, ok, worst = build(ctx)
    try:
        out = serve_engine.offer(ctx, engine, metrics, ctx.traffic,
                                 ctx.seconds, ctx.open_window)
    finally:
        engine.shutdown(timeout=30.0)
    counters = out["counters"]
    ctx.note(f"states over the window: "
             f"{counters.get('generation.ssm_state_starts', 0)} started "
             f"from zero, {counters.get('generation.ssm_rows_updated', 0)} "
             f"row updates and "
             f"{counters.get('generation.ssm_tokens_scanned', 0)} scanned "
             f"tokens (x layers); picks of experts held elsewhere "
             f"{counters.get('generation.moe_assignments_elsewhere', 0)} "
             f"beside {counters.get('generation.moe_assignments_total', 0)} "
             f"computed; preempted "
             f"{counters.get('generation.preempted_total', 0)}")
    out["correct"] = bool(ok)
    out["check_worst_shortfall"] = worst
    return out
