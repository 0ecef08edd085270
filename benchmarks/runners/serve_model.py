"""Runner for cells that serve a model named by its import path through
`GenerationEngine`.

`builder.model` in the configuration file is the dotted path of the
model's class (`paddle_tpu.generation.LatentMoELM`): everything up to the
last dot is imported as a module, the rest is looked up in it, and the
class is called with `builder.model_args` and the run's seed.
`builder.engine` gives `GenerationConfig`'s arguments; every policy the
file leaves out stays `None`, so the engine takes its own path, and
`builder.expect` names what that path has to be.

The load generator, the stamped handle, the window and the page-bucket
warm-up are `serve_engine.py`'s, by import: a cell of this runner is
offered and measured exactly as the cells of that one.

The reference check (outside the window, on the engine the window then
uses, through the same executables): greedy requests of fixed lengths
with seeded tokens — `check.prompt_tokens` (one short, one of several
chunks) and a `check.document_question_tokens`-token question behind
one of the traffic's own documents, asked twice.  The plain prompts and
the first asking (a miss: it prefills the document) are live together,
so the check sees steps that carry several sequences and a chunk; the
second asking finds the document in the prefix cache (a hit).  One pass
of the plain float32 reference over prompt + served tokens gives, at
each served position, the reference's logits and its routers' closest
call (`router_margin`: how far the last chosen expert stands above the
first one left out, the least over the expert layers).  A served token
AGREES when its reference logit is within `check.logit_margin` of the
reference's top.  `verdict()` holds the run to all of:

- the second asking hit the cache and the first did not;
- of all served tokens at least `check.min_agreeing_share` agree;
- in each request at least `check.min_agreeing_tokens_a_request` agree:
  a request served by a wrong computation agrees nowhere (154,880
  logits), however right the other requests are.

Not every token can agree: in bf16 a router's near-tie falls the other
way for some tokens, and such a token's logits are another expert's.
The closest calls are printed beside the shortfalls and limit nothing:
a token whose own routers are far from a tie can still fall short in a
short context, where a neighbour's flipped choice weighs (the
configuration's `check.why` has the readings and the arithmetic).

Where the traffic shares documents (`prefix`), each of them is asked
once, one at a time, before the load starts: the documents' cache is
built in set-up, as a service that answers questions about a standing
set of documents has it, and the ramp and the window see hits.
"""
import importlib
import time

import numpy as np


def _load(path):
    module, _, name = path.rpartition(".")
    return getattr(importlib.import_module(module), name)


def reference_readings(ctx, reference, model, prompt, got, what):
    """What the reference says of the tokens `got` served after
    `prompt`: each one's shortfall below the reference's top logit and
    its position's `router_margin` (inf for a model without experts)."""
    n_new, margins = len(got), []
    logits = np.asarray(reference.next_token_logits(
        model.decode_params(), prompt + got[:-1],
        ctx.builder["model_args"], n_new, margins))
    short = logits.max(axis=-1) - logits[np.arange(n_new), got]
    tie = np.min([np.asarray(m)[-n_new:] for m in margins], axis=0) \
        if margins else np.full(n_new, np.inf)
    short, tie = short.astype(float).tolist(), tie.astype(float).tolist()
    ctx.note(f"reference check, {what}: shortfalls of the served tokens' "
             f"reference logits below the reference's top "
             f"{[round(x, 4) for x in short]}, the routers' closest calls "
             f"there {[round(x, 5) for x in tie]} (logit std "
             f"{logits.std():.3g})")
    return {"what": what, "short": short, "router_margin": tie}


def verdict(check, requests, cached):
    """(correct, the largest shortfall, one line a limit) of the
    readings of the check's requests, by the module docstring's limits.
    A pure function of the readings: the precision control
    (`tools/precision_control.py`) passes a reference's own."""
    margin = float(check["logit_margin"])
    floor = int(check["min_agreeing_tokens_a_request"])
    short = np.concatenate([r["short"] for r in requests])
    share = float(np.mean(short <= margin))
    ok = bool(cached) and share >= float(check["min_agreeing_share"])
    lines = [f"{share:.3f} of {len(short)} served tokens within {margin} of "
             f"the reference's top (at least {check['min_agreeing_share']}), "
             f"largest shortfall {short.max():.4g}; prefix cache "
             f"{'as expected' if cached else 'NOT as expected'}"]
    for r in requests:
        agree = int(np.sum(np.asarray(r["short"]) <= margin))
        ok = ok and agree >= floor
        lines.append(f"{r['what']}: {agree}/{len(r['short'])} agree (at "
                     f"least {floor}): {'ok' if agree >= floor else 'NOT ok'}")
    return ok, float(short.max()), lines


def traffic_documents(ctx, model):
    """``{prefix id: tokens}`` of the documents `ctx.traffic` shares, as
    the schedule draws them for this seed.  The schedule's draws depend
    on its length, so it is made whole (as `offer` will make it again),
    once, for the check and the warm-up alike."""
    from benchmarks.harness import loadgen

    prefix = ctx.traffic.get("prefix")
    documents = {}
    if prefix:
        for request in loadgen.schedule(ctx.traffic, ctx.seed,
                                        model.vocab_size, 0.0):
            documents.setdefault(request.prefix_id,
                                 request.prompt[:int(prefix["tokens"])])
            if len(documents) == int(prefix["count"]):
                break
    return documents


def check_against_reference(ctx, engine, model, check, document):
    """Returns (ok, worst shortfall); `document` is one of the traffic's
    own."""
    reference = ctx.module("reference", ctx.config["reference"])
    rng = np.random.default_rng([int(ctx.seed), 0xC0DE])
    n_new = int(check["new_tokens"])
    timeout = float(check["timeout_s"])

    def serve(*prompts):
        """The prompts live together; [(tokens, prefix-hit tokens)]."""
        handles = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
        out = []
        for handle in handles:
            got = handle.result(timeout=timeout).token_ids
            if len(got) != n_new:
                raise RuntimeError(f"check request returned {len(got)} "
                                   f"tokens, not {n_new}")
            out.append((got, handle.prefix_hit_tokens or 0))
        return out

    plain = [rng.integers(0, model.vocab_size, int(n)).tolist()
             for n in check["prompt_tokens"]]
    asked = document + rng.integers(
        0, model.vocab_size, int(check["document_question_tokens"])).tolist()
    *served, (miss, hit_of_miss) = serve(*plain, asked)
    (hit, hit_of_hit), = serve(asked)
    ctx.note(f"document question served twice: {hit_of_miss} and then "
             f"{hit_of_hit} of its {len(asked)} prompt tokens came from "
             f"the prefix cache")
    requests = [reference_readings(ctx, reference, model, prompt, got,
                                   f"prompt of {len(prompt)} tokens")
                for prompt, (got, _) in zip(plain, served)]
    question = f"question behind a document, {len(asked)} tokens, "
    requests.append(reference_readings(ctx, reference, model, asked, miss,
                                       question + "miss"))
    # the same tokens have the same reference readings
    requests.append(
        dict(requests[-1], what=question + "hit") if hit == miss
        else reference_readings(ctx, reference, model, asked, hit,
                                question + "hit"))
    ok, worst, lines = verdict(
        check, requests, hit_of_miss == 0 and hit_of_hit >= len(document))
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
        "prefix_cache_tokens_second_asking": (hit_of_hit, len(document)),
        "prefix_cache_tokens_first_asking": (hit_of_miss, 0)})
    return ok, worst


def warm_documents(ctx, engine, documents):
    """Ask each of the traffic's shared documents once, one at a time,
    so that the prefix cache holds them all before the load starts."""
    t0 = time.monotonic()
    for document in documents.values():
        engine.submit(document + [0], max_new_tokens=1).result(
            timeout=float(ctx.config["check"]["timeout_s"]))
    ctx.note(f"{len(documents)} documents of "
             f"{ctx.traffic['prefix']['tokens']} tokens in the prefix "
             f"cache after {time.monotonic() - t0:.1f}s")


def build(ctx, serve_engine):
    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry

    import jax

    b = ctx.builder
    t0 = time.monotonic()
    model = _load(b["model"])(**b["model_args"], seed=ctx.seed)
    jax.block_until_ready(model.decode_params())
    ctx.clock["weights_s"] = time.monotonic() - t0
    metrics = g.GenerationMetrics(StatRegistry())
    engine = g.GenerationEngine(model, g.GenerationConfig(**b["engine"]),
                                metrics=metrics)
    try:
        picked = {"step_mode": engine.step_mode,
                  "kernel_path": engine.stats()["generation.kernel_path"],
                  "pools": type(engine.cache).__name__,
                  "latent_pool": engine.cache.rows is not None,
                  "chunked": engine.prefill_chunk_tokens > 0,
                  "prefix_cache": bool(engine.prefix_cache_enabled)}
        ctx.note(f"engine picked {picked}, chunk "
                 f"{engine.prefill_chunk_tokens}")
        if picked != b["expect"]:
            raise RuntimeError(f"the engine picked {picked}, the "
                               f"configuration expects {b['expect']}")
        for pages in serve_engine._pages_buckets(
                engine, serve_engine._max_context(ctx.traffic)):
            engine.prewarm_decode(1, pages)
        documents = traffic_documents(ctx, model)
        ok, worst = check_against_reference(
            ctx, engine, model, ctx.config["check"],
            next(iter(documents.values())))
        warm_documents(ctx, engine, documents)
    except BaseException:
        engine.shutdown(timeout=30.0)
        raise
    return engine, metrics, ok, worst


def run(ctx):
    serve_engine = ctx.module("runners", "serve_engine")
    engine, metrics, ok, worst = build(ctx, serve_engine)
    try:
        out = serve_engine.offer(ctx, engine, metrics, ctx.traffic,
                                 ctx.seconds, ctx.open_window)
    finally:
        engine.shutdown(timeout=30.0)
    out["correct"] = bool(ok)
    out["check_worst_shortfall"] = worst
    return out
