"""Runner for cells that serve a model named by its import path through
`GenerationEngine` over shared documents longer than the plain
reference can read whole in a run's set-up.

Everything is `serve_model.py`'s, by import, but for one length: the
check's question stands behind the first `check.document_tokens` tokens
(whole pages) of one of the traffic's own documents, not behind the
whole document.  The plain float32 reference's pass over a 65,536-token
document takes minutes on the chip, several times the rest of a run's
set-up; behind a prefix of the document the check's rows still attend
over contexts far past the selection's `index_topk`, so they drop most
of their context, and the second asking is still served from the
prefix cache.  The prefix is the document's own: the warm-up that
follows finds its pages in the prefix cache and prefills the rest.

Set-up's parts are noted as they end (the page buckets' programs, the
check, the documents), each with the compile clock so far, so a run's
output says where its set-up went.
"""
import time

from benchmarks.runners import serve_engine, serve_model

_load = serve_model._load     # tools/precision_control.py builds the model
verdict = serve_model.verdict


def _noted(ctx, what, t0):
    """Note that `what` ended, the seconds since `t0` and the compile
    clock's totals so far."""
    c = ctx.compile_clock.snapshot()
    ctx.note(f"{what} after {time.monotonic() - t0:.1f}s (so far "
             f"{c['programs']} programs: tracing {c['trace_s']:.1f}s, "
             f"compiling or reading the cache {c['backend_s']:.1f}s)")


def check_lengths(check, traffic):
    """The check's prompt lengths, for `tools/precision_control.py`."""
    del traffic
    return [int(n) for n in check["prompt_tokens"]] + [
        int(check["document_tokens"])
        + int(check["document_question_tokens"])]


def build(ctx):
    import jax

    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry

    b, check = ctx.builder, ctx.config["check"]
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
        t0 = time.monotonic()
        buckets = serve_engine._pages_buckets(
            engine, serve_engine._max_context(ctx.traffic))
        for pages in buckets:
            engine.prewarm_decode(1, pages)
        _noted(ctx, f"programs of {len(buckets)} page buckets", t0)
        documents = serve_model.traffic_documents(ctx, model)
        length = int(check["document_tokens"])
        if length % engine.cache.page_size:
            raise ValueError(f"check.document_tokens {length} is no whole "
                             f"number of {engine.cache.page_size}-token "
                             f"pages")
        t0 = time.monotonic()
        ok, worst = serve_model.check_against_reference(
            ctx, engine, model, check, next(iter(documents.values()))[:length])
        _noted(ctx, f"reference check over the first {length} tokens of a "
                    f"document", t0)
        t0 = time.monotonic()
        serve_model.warm_documents(ctx, engine, documents)
        _noted(ctx, "documents", t0)
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
    out["correct"] = bool(ok)
    out["check_worst_shortfall"] = worst
    return out
