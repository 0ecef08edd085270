"""Runner for cells that serve a model whose window layers give cache
pages back (`builder.model` by import path, as `serve_model.py`), through
`GenerationEngine`, with NO shared prefix: the engine refuses the prefix
cache for such a model, so `serve_model.py`'s check (a document asked
twice, a miss and a hit) has nothing to ask here.

The load generator, the stamped handle, the window and the page-bucket
warm-up are `serve_engine.py`'s, the model loader and the reference
readings `serve_model.py`'s, by import: a cell of this runner is offered
and measured exactly as the cells of those.

The reference check (outside the window, on the engine the window then
uses, through the same executables): greedy requests of
`check.prompt_tokens` seeded tokens each (one inside the window, one of
several chunks whose window pages go back during its own prefill, one of
many windows), live together, `check.new_tokens` served tokens each.
One pass of the plain float32 reference over prompt + served tokens
gives, at each served position, the reference's logits and its routers'
closest call.  A served token AGREES when its reference logit is within
`check.logit_margin` of the reference's top.  `verdict()` holds the run
to all of:

- of all served tokens at least `check.min_agreeing_share` agree;
- in each request at least `check.min_agreeing_tokens_a_request` agree
  (a request served by a wrong computation agrees nowhere);
- the cache did what the configuration says: after the check the window
  group holds no page of a finished request, no request ever held more
  window-group pages than a window and a chunk take (`(window + chunk) /
  page + 2`), and pages did go back behind the longest request's window.

Why a share and not every token: `serve_model.py`'s docstring and the
configuration's `check.why`.
"""
import json
import time

import numpy as np

from benchmarks.runners import serve_engine, serve_model

_load = serve_model._load     # tools/precision_control.py builds the model


def check_lengths(check, traffic):
    """The check's prompt lengths, for `tools/precision_control.py`."""
    del traffic
    return [int(n) for n in check["prompt_tokens"]]


def verdict(check, requests, cache_ok):
    """(correct, the largest shortfall, one line a limit) of the
    readings of the check's requests.  A pure function of the readings:
    the precision control passes a reference's own (and True for the
    cache, which a reference has not)."""
    margin = float(check["logit_margin"])
    floor = int(check["min_agreeing_tokens_a_request"])
    short = np.concatenate([r["short"] for r in requests])
    share = float(np.mean(short <= margin))
    ok = bool(cache_ok) and share >= float(check["min_agreeing_share"])
    lines = [f"{share:.3f} of {len(short)} served tokens within {margin} of "
             f"the reference's top (at least {check['min_agreeing_share']}), "
             f"largest shortfall {short.max():.4g}; window group "
             f"{'as expected' if cache_ok else 'NOT as expected'}"]
    for r in requests:
        agree = int(np.sum(np.asarray(r["short"]) <= margin))
        ok = ok and agree >= floor
        lines.append(f"{r['what']}: {agree}/{len(r['short'])} agree (at "
                     f"least {floor}): {'ok' if agree >= floor else 'NOT ok'}")
    return ok, float(short.max()), lines


def check_against_reference(ctx, engine, model, check):
    """Returns (ok, worst shortfall)."""
    reference = ctx.module("reference", ctx.config["reference"])
    rng = np.random.default_rng([int(ctx.seed), 0xC0DE])
    n_new = int(check["new_tokens"])
    prompts = [rng.integers(0, model.vocab_size, int(n)).tolist()
               for n in check["prompt_tokens"]]
    handles = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
    served = [h.result(timeout=float(check["timeout_s"])).token_ids
              for h in handles]
    for got in served:
        if len(got) != n_new:
            raise RuntimeError(f"check request returned {len(got)} "
                               f"tokens, not {n_new}")
    group = engine.cache.window_group
    stats = engine.stats()
    released = stats.get("generation.kv_window_pages_released", 0)
    # behind the longest request's window lie this many whole pages
    behind = max(0, (max(len(p) for p in prompts) - group.window)
                 // group.page_size)
    limit = (group.window + engine.prefill_chunk_tokens) \
        // group.page_size + 2
    cache_ok = (group.free_pages == group.num_pages
                and group.peak_held <= limit and released >= behind)
    ctx.note(f"window group after the check: {group.free_pages} of "
             f"{group.num_pages} pages free, the most a request held "
             f"{group.peak_held} (limit {limit}), {released} pages given "
             f"back behind a window (at least {behind})")
    requests = [serve_model.reference_readings(
        ctx, reference, model, prompt, got, f"prompt of {len(prompt)} tokens")
        for prompt, got in zip(prompts, served)]
    ok, worst, lines = verdict(check, requests, cache_ok)
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
        "window_pages_held_by_finished_requests": (
            group.num_pages - group.free_pages, 0),
        "most_window_pages_a_request_held": (group.peak_held, limit),
        "window_pages_released_in_the_check": (released, behind)})
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
                 f"{engine.prefill_chunk_tokens}, window "
                 f"{stats['generation.kv_window_tokens']} tokens, "
                 f"{engine.cache.num_pages} + "
                 f"{engine.cache.window_group.num_pages} pages, "
                 f"{stats['generation.kv_token_bytes']} B a token")
        if picked != b["expect"]:
            raise RuntimeError(f"the engine picked {picked}, the "
                               f"configuration expects {b['expect']}")
        contexts = [serve_engine._max_context(ctx.traffic),
                    max(ctx.config["check"]["prompt_tokens"])
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
    group = engine.cache.window_group
    preempted = out["counters"].get("generation.preempted_total", 0)
    ctx.note(f"window group over the run: the most a request held "
             f"{group.peak_held} pages of {group.num_pages}; preempted in "
             f"the window {preempted}")
    out["correct"] = bool(ok)
    out["check_worst_shortfall"] = worst
    return out
