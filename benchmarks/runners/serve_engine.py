"""Runner for cells that serve through `GenerationEngine`.

The configuration's `builder` gives the arguments of `TinyCausalLM` and of
`GenerationConfig`; every policy the file leaves out stays `None`, so on
a TPU the engine takes its own path (device pools, the ragged Pallas step,
chunked prefill, the prefix cache).  `builder.expect` names what that path
has to be, and a run in which the engine picked another fails.

The load generator is one thread (`harness/loadgen.py`).  Each request
rides a handle of this file's own, handed to `engine.submit(handle=...)`:
the engine's worker calls `_push_token` as it samples, and the handle
stamps the time.  So the gaps are the engine's delivery times and no
thread per request exists.
"""
import math
import time

import numpy as np

from benchmarks.harness import stats


class StampedHandle:
    """The engine-side surface of `GenerationHandle` (engine.submit's
    docstring lists it), stamping every token on the tracked request."""

    def __init__(self, tracked, driver):
        self._tracked = tracked
        self._driver = driver
        self._done = False
        self.submitted_s = None
        self.first_token_s = None
        self.prefix_hit_tokens = None
        self.n_streamed = 0
        self.tokens = []

    def _push_token(self, token):
        now = time.monotonic()
        if self.first_token_s is None:
            self.first_token_s = now
        self.n_streamed += 1
        self.tokens.append(int(token))
        self._tracked.token_s.append(now)

    def _finish(self, result):
        self._done = True
        self._driver.finished(self._tracked)

    def set_exception(self, exc):
        if self._done:
            return
        self._done = True
        self._tracked.error = exc
        self._driver.finished(self._tracked)

    def done(self):
        return self._done


def _ms(seconds):
    return None if seconds is None else seconds * 1e3


def _pages_buckets(engine, max_context):
    """The page-table widths the ragged step compiles for, up to the one
    that holds `max_context` tokens: a geometric menu from 1."""
    pages = math.ceil((max_context + 1) / engine.cache.page_size)
    top = 1 << max(0, math.ceil(math.log2(pages)))
    return [1 << i for i in range(int(math.log2(top)) + 1)]


def _max_context(traffic):
    def top(spec):
        return int(spec["value"] if spec["dist"] == "const" else spec["hi"])

    prefix = int(traffic["prefix"]["tokens"]) if traffic.get("prefix") else 0
    return (prefix + top(traffic["prompt_tokens"])
            + top(traffic["output_tokens"]))


def check_against_reference(ctx, engine, model, check):
    """Greedy requests of fixed lengths (so their programs are the same
    in every run) with seeded tokens, served before the window.  One dense
    float32 pass of the plain reference over prompt + generated tokens
    gives, for each generated token, the reference's logits at its
    position: the engine's token has to be within `logit_margin` of the
    reference's top logit.  Returns (ok, worst shortfall)."""
    reference = ctx.module("reference", ctx.config["reference"])
    rng = np.random.default_rng([int(ctx.seed), 0xC0DE])
    n_new = int(check["new_tokens"])
    prompts = [rng.integers(0, model.vocab_size, int(n)).tolist()
               for n in check["prompt_tokens"]]
    handles = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
    worst = 0.0
    for prompt, handle in zip(prompts, handles):
        got = handle.result(timeout=float(check["timeout_s"])).token_ids
        if len(got) != n_new:
            ctx.note(f"check request returned {len(got)} tokens, not "
                     f"{n_new}")
            ctx.checks["served_tokens_a_check_request"] = (len(got), n_new)
            return False, float("inf")
        logits = np.asarray(reference.next_token_logits(
            model.decode_params(), prompt + got[:-1], model.num_heads,
            n_new))
        short = logits.max(axis=-1) - logits[np.arange(n_new), got]
        agree = int(np.sum(np.argmax(logits, -1) == np.asarray(got)))
        ctx.note(f"reference check, prompt of {len(prompt)} tokens: "
                 f"{agree}/{n_new} tokens are the reference's argmax; "
                 f"largest shortfall of a served token's reference logit "
                 f"below the reference's top logit {short.max():.4g} "
                 f"(logit std {logits.std():.3g})")
        worst = max(worst, float(short.max()))
    ctx.checks["served_logit_shortfall_max"] = (worst, check["logit_margin"])
    return worst <= float(check["logit_margin"]), worst


def build(ctx):
    """(engine, model, metrics): the model from the seed, the engine as
    the configuration's `builder` says, one executable per pages bucket
    `ctx.traffic` can reach, and the reference check.  All of it set-up."""
    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry

    b = ctx.builder
    t0 = time.monotonic()
    model = g.TinyCausalLM(**b["model_args"], seed=ctx.seed)
    ctx.clock["weights_s"] = time.monotonic() - t0
    # a registry of its own: the process-wide one adds engines together
    metrics = g.GenerationMetrics(StatRegistry())
    engine = g.GenerationEngine(model, g.GenerationConfig(**b["engine"]),
                                metrics=metrics)
    try:
        picked = {"step_mode": engine.step_mode,
                  "kernel_path": engine.stats()["generation.kernel_path"],
                  "pools": type(engine.cache).__name__,
                  "chunked": engine.prefill_chunk_tokens > 0,
                  "prefix_cache": bool(engine.prefix_cache_enabled)}
        ctx.note(f"engine picked {picked}, chunk "
                 f"{engine.prefill_chunk_tokens}")
        if picked != b["expect"]:
            raise RuntimeError(f"the engine picked {picked}, the "
                               f"configuration expects {b['expect']}")
        for pages in _pages_buckets(engine, _max_context(ctx.traffic)):
            engine.prewarm_decode(1, pages)
        # the check requests also run every host-side path once
        ok, worst = check_against_reference(ctx, engine, model,
                                            ctx.config["check"])
    except BaseException:
        engine.shutdown(timeout=30.0)
        raise
    return engine, model, metrics, ok, worst


def offer(ctx, engine, metrics, traffic, seconds, open_window):
    """Offer `traffic` for its ramp and then `seconds` more; returns what
    that window saw.  `open_window()` is called as the window opens and
    gives an object with `poll(now)`, `closed(now)` and `finish()`."""
    from benchmarks.harness import loadgen

    horizon = float(traffic["ramp_s"]) + seconds + 5.0
    requests = loadgen.schedule(traffic, ctx.seed, engine.model.vocab_size,
                                horizon)
    timeout_ms = traffic.get("timeout_ms")

    def submit(tracked):
        tracked.handle = StampedHandle(tracked, driver)
        with ctx.span("bench::submit"):
            engine.submit(tracked.request.prompt,
                          max_new_tokens=tracked.request.max_new_tokens,
                          timeout_ms=timeout_ms, handle=tracked.handle)

    driver = loadgen.LoadDriver(traffic, requests, submit)
    driver.start()
    # the ramp: the same traffic, offered until the slots and the queue
    # are as a long-running server's; it counts as set-up
    time.sleep(float(traffic["ramp_s"]))
    before = metrics.snapshot()
    compiles_before = ctx.compile_clock.snapshot()["programs"]
    window = open_window()
    lo = time.monotonic()
    while True:
        now = time.monotonic()
        window.poll(now)
        if window.closed(now):
            break
        time.sleep(0.02)
    hi = time.monotonic()
    after = metrics.snapshot()
    compiles_after = ctx.compile_clock.snapshot()["programs"]
    window.finish()
    driver.stop()

    timeout_s = (float(timeout_ms) / 1e3 if timeout_ms else float("inf"))
    view = loadgen.window_view(driver.tracked, lo, hi, timeout_s)
    if driver.exhausted_abs is not None and driver.exhausted_abs < hi:
        raise RuntimeError("the schedule ran out inside the window: raise "
                           "`pool` or the horizon")
    counters = {k: after[k] - before.get(k, 0) for k in after
                if isinstance(after[k], (int, float))
                and not isinstance(after[k], bool)}
    out = {
        "attempted": view["attempted"],
        "failed": view["failed"],
        "finished": view["finished"],
        "window_s": hi - lo,
        "end_to_end": {
            "serve_out_tokens_per_s": view["tokens"] / (hi - lo),
            "serve_gap_ms_p95": _ms(stats.percentile(view["gap_s"], 95)),
        },
        "ttft_s": view["ttft_s"],
        "gap_s": view["gap_s"],
        "counters": counters,
        "jax_compiles_in_window": compiles_after - compiles_before,
        "lateness_s": driver.lateness_s(lo, hi),
        "tracked": driver.tracked,
        "in_flight_at_close": sum(
            1 for t in driver.tracked
            if t.due_abs < hi and (t.done_abs is None or t.done_abs > hi)),
    }
    ctx.note(f"window {hi - lo:.2f}s: {view['attempted']} requests due, "
             f"{view['finished']} finished, {view['failed']} failed, "
             f"{out['in_flight_at_close']} in flight at its close, "
             f"{view['tokens']} tokens, {len(view['ttft_s'])} first tokens, "
             f"{len(view['gap_s'])} gaps; jax compiles in window "
             f"{out['jax_compiles_in_window']}")
    ttft, gap = view["ttft_s"], view["gap_s"]
    ctx.note("time to first token, ms: "
             + ", ".join(f"p{q} {_ms(stats.percentile(ttft, q))}"
                         for q in (50, 95))
             + f", mean {_ms(sum(ttft) / len(ttft)) if ttft else None}; gap "
             "between tokens, ms: "
             + ", ".join(f"p{q} {_ms(stats.percentile(gap, q))}"
                         for q in (50, 95, 99)))
    hit = counters.get("generation.prefix_cache_hit_tokens", 0)
    filled = counters.get("generation.prefill_tokens_total", 0)
    ctx.note(f"prompt tokens in window: {hit} from the prefix cache, "
             f"{filled} prefilled; engine steps "
             f"{counters.get('generation.steps_total', 0)}; preempted "
             f"{counters.get('generation.preempted_total', 0)}")
    return out


def run(ctx):
    engine, _, metrics, ok, worst = build(ctx)
    try:
        out = offer(ctx, engine, metrics, ctx.traffic, ctx.seconds,
                    ctx.open_window)
    finally:
        engine.shutdown(timeout=30.0)
    out["correct"] = bool(ok)
    out["check_worst_shortfall"] = worst
    return out
