#!/usr/bin/env python
"""Decode microbench: tokens/s across batch x context for the
paddle_tpu.generation engine (BENCH-style JSON to stdout).

Measures the paged-KV continuous-batching decode loop end to end —
prefill, paged decode attention (Pallas kernel on TPU, jnp reference on
CPU), sampling, scheduling — with the `generation.*` StatRegistry
snapshot embedded in the artifact (the stats_snapshot() export).

Usage:
    python tools/gen_bench.py                    # default grid
    python tools/gen_bench.py --batches 1,4,8 --contexts 32,128 \
        --new-tokens 32 --out BENCH_GEN.json
    python tools/gen_bench.py --pool device --decode both
        # eager vs fused single-dispatch decode A/B: steady-state
        # steps/s + tokens/s per cell with per-step dispatch/sync
        # counts; compile/warmup wall time in the separate warmup_s
        # column, never folded into the rate
    python tools/gen_bench.py --prefill both --chunk-tokens 32
        # full vs CHUNKED prefill A/B: every series gains an
        # "interleave" cell — batch-1 short requests decode while one
        # long prompt streams in — reporting time-to-first-token per
        # request, decode tokens/s DURING the long prefill, and the
        # prefill compile count (chunked: O(1) in prompt length)
    python tools/gen_bench.py --step both
        # legacy vs RAGGED mixed-batch step A/B: the FusedDecodeStep +
        # ChunkedPrefillStep pair (dummy-padded decode buckets, two
        # dispatches per interleaved step) vs ONE ragged dispatch
        # packing decode rows and the prefill chunk into a fixed token
        # axis — steady-state tokens/s, dispatches/step, measured
        # row_utilization, padded_token_waste (ragged: 0), and a ragged
        # TTFT-under-interleave cell
    python tools/gen_bench.py --prefix both
        # prefix-cache A/B: a shared-system-prompt workload (N users,
        # one long system prefix, distinct short suffixes) run with
        # the cache off and on — per-cell prefix hit tokens, cold vs
        # warm TTFT, prefill tokens computed, live shared_pages and
        # COW copies; warm cells pay prefill only for the divergent
        # suffix
    python tools/gen_bench.py --replicas both
        # fleet-tier A/B: a shared-system-prompt multi-turn session
        # workload through serving.FleetRouter at 1 and N replicas,
        # with the affinity routing ladder (session -> prefix ->
        # least-loaded) against a random-routing baseline — per-replica
        # prefix hit rate, shed rate, TTFT p50/p95, and the
        # prefix-routing confirmation split per cell
    python tools/gen_bench.py --replicas N --fleet-transport both
        # DISAGGREGATED fleet A/B: the same fleet cells behind the
        # in-process transport vs one-OS-process-per-replica
        # (SubprocTransport pickled RPC), plus a drain-migration probe
        # pair per transport — a mid-decode stream's replica drains
        # and the cell reports stream-gap p95 across the drain,
        # migrated_replay_tokens (LIVE migration must report 0 vs the
        # cold-resubmit baseline's full replay), and the page-service
        # adoption counters
    python tools/gen_bench.py --page-transfer both --page-codec both
        # cross-host DATA-PLANE A/B: one warm-prefix adoption cell per
        # (relay vs p2p) x (raw vs compressed) combo — wire bytes,
        # router relay bytes (p2p cells must report 0: pages dial the
        # holder's data port, the router only books the index), raw
        # bytes + measured compression ratio (bitwise-lossless delta+
        # zlib; the synthetic model's KV is near-incompressible, so
        # the ratio is honest, not a marketing 2x), the async transfer
        # wall, and the importer's warm TTFT after adoption
    python tools/gen_bench.py --mesh both
        # single-chip vs TENSOR-PARALLEL sharded decode A/B: the same
        # grid run unsharded (tp_degree 1) and over a head-sharded
        # mesh of every visible device (GenerationConfig.mesh, fused
        # decode only) — tokens/s and dispatches/step vs tp_degree,
        # plus generation.collective_bytes_per_step, mesh_devices and
        # kernel_path in each cell; GSPMD compile wall stays in
        # warmup_s.  Every SHARDED combo runs twice — use_kernel False
        # (jnp reference, GSPMD-partitioned) vs True (the shard_map'd
        # Pallas kernel: per-shard program over num_heads/tp heads) —
        # the kernel-vs-reference A/B under the mesh.  On CPU
        # an --xla_force_host_platform_device_count=8 mesh is forced
        # automatically when XLA_FLAGS doesn't already carry one
        # (collectives over loopback: a semantics/dispatch A/B, not a
        # speedup).  --mesh also takes an explicit tp_degree integer.

Steady-state accounting: every cell pre-warms its decode buckets (and
pays its prefill/chunk compiles in a full warmup pass) BEFORE the
measured window; compile wall time lands in `warmup_s`, and the cell's
`measured_compiles` field records any executable built inside the timed
region (0 in the steady state — a nonzero value means the bucket menu
was exercised mid-run and the rate is polluted).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python tools/gen_bench.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _prewarm_decode_buckets(eng, batch, context, new_tokens, page_size):
    """Pre-compile every fused-decode bucket the run can touch (all
    batch buckets <= batch x all pages buckets up to the final context)
    OUTSIDE the measured window — a new bucket appearing mid-run (batch
    decay on finishes, pages growth as sequences lengthen) otherwise
    lands its compile wall time in the timed region.  No-op on the
    eager path.  Returns elapsed seconds (reported under warmup_s)."""
    t0 = time.perf_counter()
    max_pages = -(-(context + new_tokens + 1) // page_size)
    pages = 1
    while True:
        for b in range(1, batch + 1):
            eng.prewarm_decode(b, pages, greedy=True)
        if pages >= max_pages:
            break
        pages *= 2
    return time.perf_counter() - t0


def _pool_byte_facts(model, num_pages, page_size, context, new_tokens,
                     kv_dtype):
    """Pool-capacity arithmetic for the kv-quant A/B: bytes per page at
    this dtype (scales included for int8), and the resident-sequence
    capacity a FIXED byte budget (the bf16 pool at this page count)
    buys — the "~2x resident sequences per pool byte" headline."""
    import numpy as np

    ll, h, d = model.num_layers, model.num_heads, model.head_dim

    def page_bytes(dt):
        b = 2 * ll * page_size * h * d * np.dtype(dt).itemsize
        if np.dtype(dt) == np.dtype(np.int8):
            b += 2 * ll * h * 4            # [P, H] f32 scales per pool
        return b

    budget = page_bytes("bfloat16") * num_pages
    pages_at_budget = budget // page_bytes(kv_dtype)
    pages_per_seq = -(-(context + new_tokens) // page_size)
    return {
        "kv_page_bytes": int(page_bytes(kv_dtype)),
        "kv_pool_bytes": int(page_bytes(kv_dtype) * num_pages),
        "pool_byte_budget": int(budget),
        "pages_at_fixed_budget": int(pages_at_budget),
        "resident_seqs_at_fixed_budget": int(pages_at_budget
                                             // pages_per_seq),
    }


def bench_cell(model, batch, context, new_tokens, num_pages, page_size,
               pool, decode, prefill="full", chunk_tokens=0, tp=1,
               step="legacy", use_kernel=None, kv_dtype=None,
               quant_collectives=False):
    from paddle_tpu import generation as g
    from paddle_tpu.generation import metrics as gmetrics
    from paddle_tpu.parallel import tp_mesh
    from paddle_tpu.profiler.monitor import StatRegistry

    mesh = tp_mesh(tp) if tp > 1 else None
    kv_kwargs = {}
    if kv_dtype is not None:
        kv_kwargs["kv_dtype"] = kv_dtype
    eng = g.GenerationEngine(
        model,
        g.GenerationConfig(max_decode_slots=batch, num_pages=num_pages,
                           page_size=page_size, queue_depth=batch * 2,
                           kv_backend=pool, mesh=mesh,
                           # the kernel-vs-reference A/B under the mesh:
                           # None = auto (pallas on TPU), False = jnp
                           # reference, True = the shard_map'd kernel
                           use_kernel=use_kernel,
                           # the ragged step replaces the decode path:
                           # one mixed-batch executable per pages bucket
                           decode=(None if step == "ragged" else decode),
                           step_mode=step,
                           quantized_collectives=quant_collectives,
                           prefill_chunk_tokens=(chunk_tokens
                                                 if prefill == "chunked"
                                                 else 0),
                           **kv_kwargs),
        start=False)
    rng = np.random.default_rng(batch * 1000 + context)
    prompts = [rng.integers(0, model.vocab_size, context).tolist()
               for _ in range(batch)]

    def run_once():
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        return dt, [h.result(timeout=1) for h in handles]

    # warmup pass: same shapes as the measured pass, so it pays every
    # trace/compile (fused decode buckets, jit_prefill buckets) exactly
    # once — compile time is REPORTED, never folded into the
    # steady-state rate below.  The explicit bucket pre-warm then covers
    # signatures the warmup pass may have missed (scheduling jitter can
    # shift which buckets a pass touches).
    warmup_s, _ = run_once()
    warmup_s += _prewarm_decode_buckets(eng, batch, context, new_tokens,
                                        page_size)
    reg = StatRegistry.instance()
    kv_stat = reg.get_stat(gmetrics.KV_BYTES_MOVED)
    pf_stat = reg.get_stat(gmetrics.PREFILL_TOKENS_TOTAL)
    steps_stat = reg.get_stat(gmetrics.STEPS_TOTAL)
    pfc_stat = reg.get_stat(gmetrics.PREFILL_COMPILES_TOTAL)
    dcc_stat = reg.get_stat(gmetrics.DECODE_COMPILES_TOTAL)
    sb_stat = reg.get_stat(gmetrics.STEP_SCORE_BLOCKS)
    sbu_stat = reg.get_stat(gmetrics.STEP_SCORE_BLOCKS_UNTILED)
    kv_before, pf_before = kv_stat.get(), pf_stat.get()
    steps_before = steps_stat.get()
    compiles_before = pfc_stat.get() + dcc_stat.get()
    sb_before, sbu_before = sb_stat.get(), sbu_stat.get()
    dt, results = run_once()
    measured_compiles = int(pfc_stat.get() + dcc_stat.get()
                            - compiles_before)
    generated = sum(len(r.token_ids) for r in results)
    steps = int(steps_stat.get() - steps_before)
    kv_bytes = int(kv_stat.get() - kv_before)
    # prefill writes (incl. preemption re-prefills) are exactly the
    # prefill token count x K+V payload at the POOL itemsize (the cache
    # counts writes at storage precision — int8 cells write 1-byte
    # payloads); subtracting them leaves the decode-side traffic the
    # O(pool)-vs-O(tokens) A/B is about
    prefill_bytes = (int(pf_stat.get() - pf_before) * 2 * model.num_layers
                     * model.num_heads * model.head_dim
                     * np.dtype(kv_dtype or np.float32).itemsize)
    snap = eng.metrics.snapshot()
    eng.shutdown()
    return {
        "pool": pool,
        "decode": decode,
        "prefill": prefill,
        # legacy vs ragged step A/B: the one-dispatch mixed-batch path
        # reports its measured packed-axis utilization and the ZERO of
        # padded_token_waste; legacy cells report their dummy-row bill.
        # Utilization is the CUMULATIVE useful/dispatched ratio over the
        # cell (the per-step gauge would report whatever the drain-tail
        # step happened to pack).
        "step": step,
        "row_utilization": round(
            snap.get("generation.step_rows_useful", 0)
            / max(snap.get("generation.step_rows_dispatched", 0), 1), 3),
        "padded_token_waste": snap.get(
            "generation.padded_token_waste", 0),
        # tensor-parallel degree of the cell's mesh (1 = unsharded) and
        # the per-dispatch allreduce estimate — the tokens/s-vs-tp A/B
        # plus the collective-cost baseline the EQuARX-style quantized
        # allreduce follow-on is measured against
        "tp_degree": tp,
        "collective_bytes_per_step": snap.get(
            "generation.collective_bytes_per_step", 0),
        # which attention implementation actually dispatched — the
        # silent-fallback tripwire (a mesh cell reporting jnp-reference
        # when pallas was requested is a bug, not a detail)
        "kernel_path": snap.get("generation.kernel_path", ""),
        # precision facts: the pool storage dtype this cell measured,
        # the split-out scale traffic (int8: scales are bytes in flight
        # too, already folded into kv_bytes_moved), and whether the
        # EQuARX-style quantized ring actually carried the allreduces
        # (a silent fp32 fallback is a stats fact, like kernel_path)
        "kv_quant_dtype": snap.get("generation.kv_quant_dtype", ""),
        "kv_scale_bytes": snap.get("generation.kv_scale_bytes", 0),
        "collective_quantized": snap.get(
            "generation.collective_quantized", 0),
        # fixed-pool-byte capacity arithmetic (the int8 headline:
        # ~2x resident sequences vs bf16 at the same byte budget)
        **_pool_byte_facts(model, num_pages, page_size, context,
                           new_tokens,
                           kv_dtype if kv_dtype is not None
                           else "float32"),
        # the query-tiling FLOP proxy (ragged KERNEL cells; 0 when the
        # jnp reference dispatched — the /ref-vs-/kernel tripwire):
        # score blocks the tiled kernel computed vs the untiled bill,
        # DELTAS over the measured pass (the counters are cumulative
        # per series, like kv_bytes)
        "score_blocks": int(sb_stat.get() - sb_before),
        "score_blocks_untiled": int(sbu_stat.get() - sbu_before),
        "batch": batch,
        "context": context,
        "new_tokens": new_tokens,
        "generated": generated,
        "wall_s": round(dt, 4),
        "warmup_s": round(warmup_s, 4),      # compile+trace+prewarm
        # executables built INSIDE the timed region (steady state: 0 —
        # pre-warm moved every bucket compile into warmup_s)
        "measured_compiles": measured_compiles,
        "tokens_per_s": round(generated / dt, 2) if dt > 0 else 0.0,
        "steps": steps,
        "steps_per_s": round(steps / dt, 2) if dt > 0 else 0.0,
        # per-step gauges from the steady-state pass: the fused-vs-eager
        # dispatch-collapse A/B per cell (fused: 1 and 1)
        "dispatches_per_step": snap.get(
            "generation.decode_dispatches_per_step", 0),
        "host_syncs_per_step": snap.get(
            "generation.decode_host_syncs_per_step", 0),
        "decode_compiles": snap.get("generation.decode_compiles_total", 0),
        "preemptions": sum(r.preemptions for r in results),
        "kv_bytes_moved": kv_bytes,          # total, prefill included
        "kv_prefill_bytes": prefill_bytes,
        # decode-side bytes per generated token: O(pool) for host pools,
        # O(batch x layers x heads x head_dim) for DeviceKVPool —
        # context-independent by construction for the device backend
        "kv_decode_bytes_per_token": round(
            (kv_bytes - prefill_bytes) / max(generated, 1), 1),
    }


def bench_interleave(model, batch, context, long_context, new_tokens,
                     page_size, pool, decode, prefill, chunk_tokens,
                     step="legacy", pack=True):
    """The chunked-prefill A/B scenario: `batch - 1` short requests
    decode while ONE long prompt streams in.  Reports time-to-first-
    token per request and the decode tokens/s the short requests
    sustained DURING the long prompt's prefill window — the
    head-of-line stall full prefill causes and chunking removes.

    Measured on the second pass (the first pays every compile); the
    prefill window is [long submit, long first token], probed via the
    GenerationHandle submitted_s/first_token_s monotonic stamps."""
    from paddle_tpu import generation as g
    from paddle_tpu.generation import metrics as gmetrics
    from paddle_tpu.profiler.monitor import StatRegistry

    # one slot past the decode batch: reserved for the LATE short
    # request the packing probe submits behind the long prompt
    pages = (-(-(long_context + new_tokens) // page_size) + 2) * (batch + 1)
    eng = g.GenerationEngine(
        model,
        g.GenerationConfig(max_decode_slots=batch + 1, num_pages=pages,
                           page_size=page_size, queue_depth=batch * 2 + 2,
                           kv_backend=pool, prefill_pack=pack,
                           decode=(None if step == "ragged" else decode),
                           step_mode=step,
                           prefill_chunk_tokens=(chunk_tokens
                                                 if prefill == "chunked"
                                                 else 0)),
        start=False)
    rng = np.random.default_rng(batch * 7 + context)
    shorts = [rng.integers(0, model.vocab_size, context).tolist()
              for _ in range(batch - 1)]
    late_short = rng.integers(0, model.vocab_size, context).tolist()
    long_prompt = rng.integers(0, model.vocab_size, long_context).tolist()
    reg = StatRegistry.instance()
    tok_stat = reg.get_stat(gmetrics.TOKENS_TOTAL)
    chunk_stat = reg.get_stat(gmetrics.PREFILL_CHUNKS_TOTAL)

    def run_once():
        hs = [eng.submit(p, max_new_tokens=new_tokens) for p in shorts]
        # get every short request decoding before the long prompt lands;
        # chunked mode streams ONE chunk per step FIFO, so the cap must
        # cover every short's whole prefill or the measured window would
        # silently include leftover short-prefill chunks
        warm_cap = 64 + len(shorts) * (
            -(-context // max(chunk_tokens, 1))
            if prefill == "chunked" else 1)
        for _ in range(warm_cap):
            eng.step()
            if all(h.first_token_s is not None for h in hs):
                break
        if not all(h.first_token_s is not None for h in hs):
            raise RuntimeError(
                "interleave warm-up did not finish the short requests' "
                "prefills; the window metrics would be mis-scoped")
        tokens_before = tok_stat.get()
        chunks_before = chunk_stat.get()
        h_long = eng.submit(long_prompt, max_new_tokens=new_tokens)
        # the multi-prompt PACKING probe: a short prompt admitted
        # BEHIND the long one.  With chunked prefill its first chunk
        # rides the very next step's leftover token-axis room
        # (plan_pack), so its TTFT is a couple of steps; with full
        # prefill it pays the long prompt's whole forward pass first —
        # the head-of-line number packing removes
        h_late = eng.submit(late_short, max_new_tokens=new_tokens)
        # count short-request tokens from steps that finished BEFORE the
        # long prompt's first token: the snapshot taken before the step
        # that produced it excludes that step's own decode output, which
        # lands after the window closes in both prefill modes
        before_step = tok_stat.get()
        # capped like the warm-up loop: if the long prompt can never
        # yield a first token (page exhaustion resolves its handle with
        # an exception, pathological config), fail THIS cell instead of
        # spinning until the harness timeout kills the whole artifact
        window_cap = 256 + 4 * (
            -(-long_context // max(chunk_tokens, 1))
            if prefill == "chunked" else 1)
        for _ in range(window_cap):
            if h_long.first_token_s is not None:
                break
            before_step = tok_stat.get()
            eng.step()
        if h_long.first_token_s is None:
            raise RuntimeError(
                "interleave cell: the long prompt produced no first "
                "token within the step cap (config cannot fit it?)")
        decode_tokens = int(before_step - tokens_before)
        # chunks dispatched inside the window: the long prompt's plus
        # the late short's (its pack rides the same steps when chunked)
        window_chunks = int(chunk_stat.get() - chunks_before)
        eng.run_until_idle()
        for h in hs:
            h.result(timeout=1)
        h_long.result(timeout=1)
        h_late.result(timeout=1)
        window = h_long.first_token_s - h_long.submitted_s
        return {
            "ttft_long_s": round(window, 4),
            "ttft_short_avg_s": round(
                sum(h.first_token_s - h.submitted_s for h in hs)
                / max(len(hs), 1), 4),
            # the packing headline: TTFT of the short admitted BEHIND
            # the long prompt (chunked+packed strictly below full
            # prefill's head-of-line wait)
            "ttft_short_behind_long_s": round(
                h_late.first_token_s - h_late.submitted_s, 4),
            "decode_tokens_during_prefill": decode_tokens,
            "decode_tps_during_prefill": round(
                decode_tokens / window, 2) if window > 0 else 0.0,
            "prefill_chunks": window_chunks,
        }

    run_once()                                   # compile/trace pass
    warm_t0 = time.perf_counter()
    # batch + 1: the late packing probe can decode alongside the full
    # short batch + the long prompt, one slot past the nominal batch
    _prewarm_decode_buckets(eng, batch + 1, long_context, new_tokens,
                            page_size)
    warmup_s = time.perf_counter() - warm_t0
    pfc = reg.get_stat(gmetrics.PREFILL_COMPILES_TOTAL)
    pfc_before = pfc.get()
    cell = run_once()                            # measured pass
    snap = eng.metrics.snapshot()
    cell.update({
        "scenario": "interleave",
        "pool": pool,
        "decode": decode,
        "prefill": prefill,
        # multi-prompt chunk packing on (default) or the one-chunk-
        # per-step ablation baseline — the packing TTFT A/B pairs a
        # pack=True cell with a pack=False one on the same traffic
        "pack": pack,
        # the TTFT-under-interleave A/B rung for the ragged step, with
        # its measured mixed-batch row utilization (decode rows + chunk
        # rows share the packed axis, cumulative over the cell) and
        # dummy-row bill (ragged: 0)
        "step": step,
        "row_utilization": round(
            snap.get("generation.step_rows_useful", 0)
            / max(snap.get("generation.step_rows_dispatched", 0), 1), 3),
        "padded_token_waste": snap.get(
            "generation.padded_token_waste", 0),
        "kernel_path": snap.get("generation.kernel_path", ""),
        "dispatches_per_step": snap.get(
            "generation.decode_dispatches_per_step", 0),
        "batch": batch,
        "context": context,
        "long_context": long_context,
        "new_tokens": new_tokens,
        "warmup_s": round(warmup_s, 4),
        # compile reuse across passes: 0 new prefill executables in the
        # measured pass for BOTH modes; the absolute count per series
        # is in the stats snapshot (chunked: O(1) in prompt length)
        "measured_prefill_compiles": int(pfc.get() - pfc_before),
    })
    eng.shutdown()
    return cell


def bench_prefix(model, users, sys_tokens, user_tokens, new_tokens,
                 page_size, pool, prefix_on, chunk_tokens):
    """The prefix-cache A/B scenario: `users` requests share one
    `sys_tokens`-token system prompt with distinct `user_tokens`-token
    suffixes — the production shape (system prompts, few-shot
    templates, multi-turn history re-sent per request).  Reports the
    cold TTFT (the request that seeds the cache), the warm-wave TTFT
    average, prefill tokens computed for the warm wave (warm: suffix
    only), per-request hit tokens, and the LIVE shared-page count
    while every user holds its slot — the one-physical-copy proof.

    Compile/trace cost is paid by a throwaway request with the same
    shapes but disjoint tokens (it can never warm the measured
    prompts), so cold-vs-warm TTFT is prefill work, not compile
    wall."""
    from paddle_tpu import generation as g
    from paddle_tpu.generation import metrics as gmetrics
    from paddle_tpu.profiler.monitor import StatRegistry

    total = sys_tokens + user_tokens + new_tokens
    pages = (-(-total // page_size) + 2) * (users + 1)
    eng = g.GenerationEngine(
        model,
        g.GenerationConfig(max_decode_slots=users, num_pages=pages,
                           page_size=page_size, queue_depth=users * 2,
                           kv_backend=pool, prefix_cache=prefix_on,
                           prefill_chunk_tokens=chunk_tokens),
        start=False)
    rng = np.random.default_rng(sys_tokens * 31 + users)
    half = model.vocab_size // 2
    system = rng.integers(0, half, sys_tokens).tolist()
    suffixes = [rng.integers(0, half, user_tokens).tolist()
                for _ in range(users)]
    # throwaway: same shapes, tokens from the other half of the vocab
    # (disjoint from `system`, so it cannot pre-warm the measured wave)
    throwaway = rng.integers(half, model.vocab_size, total
                             - new_tokens).tolist()
    eng.submit(throwaway, max_new_tokens=new_tokens)
    eng.run_until_idle()
    reg = StatRegistry.instance()
    pf_stat = reg.get_stat(gmetrics.PREFILL_TOKENS_TOTAL)
    # cold request: seeds the cache (when on) and is the cold baseline
    pf_before = pf_stat.get()
    h_cold = eng.submit(system + suffixes[0], max_new_tokens=new_tokens)
    eng.run_until_idle()
    h_cold.result(timeout=5)
    cold_prefill = int(pf_stat.get() - pf_before)
    # warm wave: every user shares the system prompt
    pf_before = pf_stat.get()
    hs = [eng.submit(system + sfx, max_new_tokens=new_tokens)
          for sfx in suffixes[1:]]
    shared_live = 0
    for _ in range(64 + users * (-(-total // max(chunk_tokens, 1)))):
        eng.step()
        shared_live = max(shared_live, eng.cache.shared_pages)
        if all(h.first_token_s is not None for h in hs):
            break
    eng.run_until_idle()
    for h in hs:
        h.result(timeout=5)
    warm_prefill = int(pf_stat.get() - pf_before)
    snap = eng.metrics.snapshot()
    eng.shutdown()
    return {
        "scenario": "prefix",
        "prefix": "on" if prefix_on else "off",
        "pool": pool,
        "users": users,
        "sys_tokens": sys_tokens,
        "user_tokens": user_tokens,
        "new_tokens": new_tokens,
        "ttft_cold_s": round(h_cold.first_token_s - h_cold.submitted_s, 4),
        "ttft_warm_avg_s": round(
            sum(h.first_token_s - h.submitted_s for h in hs)
            / max(len(hs), 1), 4),
        # prefill tokens computed: cold pays the whole prompt; a warm
        # hit pays only the divergent suffix
        "cold_prefill_tokens": cold_prefill,
        "warm_prefill_tokens": warm_prefill,
        "warm_prefill_tokens_per_user": round(
            warm_prefill / max(len(hs), 1), 1),
        "hit_tokens": sum(h.prefix_hit_tokens or 0 for h in hs),
        "hit_rate": snap.get("generation.prefix_cache_hit_rate", 0.0),
        # one physical copy: peak pages aliased by >1 sequence while
        # the whole wave held slots
        "shared_pages_live": shared_live,
        "cow_copies": snap.get("generation.cow_copies", 0),
        "prefix_evictions": snap.get("generation.prefix_evictions", 0),
    }


def bench_fleet(model, n_replicas, sessions, sys_tokens, user_tokens,
                new_tokens, page_size, routing, chunk_tokens, turns=2,
                transport="inproc"):
    """The fleet-tier A/B scenario: `sessions` multi-turn sessions share
    one system prompt; each session's turn 2 re-sends turn 1's prompt
    PLUS the streamed answer (the production multi-turn shape that
    decode-tail indexing warm-hits).  Run once per routing mode —
    'affinity' (session -> prefix -> least-loaded ladder) vs 'random'
    (uniform baseline) — reporting per-replica prefix hit rate, shed
    rate, and TTFT p50/p95: affinity keeps a session's warm pages and a
    prompt's prefix index on ONE replica, random splits them and pays
    cold prefills per replica."""
    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry
    from paddle_tpu.serving import fleet as fleet_mod
    from paddle_tpu.serving.fleet import (FleetConfig, FleetRouter,
                                          ReplicaSpec)

    # reset fleet.* so each cell's routing counters stand alone (the
    # per-replica generation.* registries are fresh per FleetRouter)
    reg = StatRegistry.instance()

    def reset_fleet_stats():
        for name in list(reg.stats()):
            if name.startswith(fleet_mod.PREFIX):
                reg.get_stat(name).reset()

    reset_fleet_stats()
    total = sys_tokens + turns * (user_tokens + new_tokens)
    pages = (-(-total // page_size) + 2) * (sessions + 1)
    specs = [
        ReplicaSpec(
            f"r{i}", model,
            g.GenerationConfig(max_decode_slots=4, num_pages=pages,
                               page_size=page_size,
                               queue_depth=sessions * turns + 4,
                               prefix_cache=True,
                               prefill_chunk_tokens=chunk_tokens),
            transport=transport)
        for i in range(n_replicas)]
    fl = FleetRouter(specs, FleetConfig(routing=routing,
                                        start=(transport == "proc"),
                                        seed=7))
    rng = np.random.default_rng(sys_tokens * 17 + sessions)
    half = model.vocab_size // 2

    def run_waves(system, tag, lo, hi):
        """`turns` waves of `sessions` multi-turn requests.  Each wave
        submits CONCURRENTLY (queues build, the least-loaded rung sees
        real depths, TTFT includes queueing) and drains once per turn —
        the barrier only exists because turn t+1 needs turn t's
        answers."""
        handles, history = [], {}
        for turn in range(turns):
            wave = []
            for sess in range(sessions):
                sfx = rng.integers(lo, hi, user_tokens).tolist()
                prompt = history.get(sess, list(system)) + sfx
                h = fl.submit(prompt, max_new_tokens=new_tokens,
                              session=f"{tag}{sess}")
                wave.append((sess, prompt, h))
            fl.run_until_idle()
            for sess, prompt, h in wave:
                history[sess] = prompt + h.result(timeout=10).token_ids
                handles.append(h)
        return handles

    # warmup: the EXACT measured structure (same wave shapes, batched
    # prefill buckets included) with tokens from the other half of the
    # vocab, so every per-shape op warm-up is paid before the timed
    # waves and nothing it registers can warm the measured prompts.
    # Then flush the residue and reset the counters: measured waves
    # start cold with clean books.
    run_waves(rng.integers(half, model.vocab_size, sys_tokens).tolist(),
              "w", half, model.vocab_size)
    for name, rep in fl._replicas.items():
        rep.transport.flush_prefix()
        rep.transport.reset_stats()
        rep.transport.take_prefix_deltas()   # the flush's drop deltas
        fl._page_index.drop_replica(name)    # warmup residue forgotten
    reset_fleet_stats()
    system = rng.integers(0, half, sys_tokens).tolist()
    handles = run_waves(system, "s", 0, half)
    ttfts = sorted(h.first_token_s - h.submitted_s for h in handles)
    snap = fl.stats_snapshot()
    per_replica = {}
    for name, rep in snap["replicas"].items():
        gstats = rep.get("generation", {})
        per_replica[name] = {
            "requests": gstats.get("generation.requests_total", 0),
            "hit_tokens":
                gstats.get("generation.prefix_cache_hit_tokens", 0),
            "hit_rate":
                gstats.get("generation.prefix_cache_hit_rate", 0.0),
            "prefill_tokens":
                gstats.get("generation.prefill_tokens_total", 0),
        }
    fl.shutdown()
    fsnap = snap["fleet"]
    n_requests = len(handles)
    return {
        "scenario": "fleet",
        "replicas": n_replicas,
        "routing": routing,
        "transport": transport,
        "page_adoptions": fsnap.get("fleet.page_adoptions", 0),
        "pages_adopted": fsnap.get("fleet.pages_adopted", 0),
        "sessions": sessions,
        "turns": turns,
        "sys_tokens": sys_tokens,
        "user_tokens": user_tokens,
        "new_tokens": new_tokens,
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
        "ttft_p95_s": round(float(np.percentile(ttfts, 95)), 4),
        "hit_tokens": sum(h.prefix_hit_tokens or 0 for h in handles),
        "shed_total": fsnap.get("fleet.shed_total", 0),
        "shed_rate": round(fsnap.get("fleet.shed_total", 0)
                           / max(n_requests, 1), 3),
        "routed_affinity": fsnap.get("fleet.routed_affinity", 0),
        "routed_prefix": fsnap.get("fleet.routed_prefix", 0),
        "routed_spill": fsnap.get("fleet.routed_spill", 0),
        "prefix_routed_confirmed":
            fsnap.get("fleet.prefix_routed_confirmed", 0),
        "prefix_routed_missed":
            fsnap.get("fleet.prefix_routed_missed", 0),
        "per_replica": per_replica,
    }


def bench_drain_migration(model, transport, live, sys_tokens, new_tokens,
                          page_size, chunk_tokens):
    """The drain-migration probe: one long stream is mid-decode when
    its replica drains; a consumer thread stamps every token arrival
    so the cell reports STREAM-GAP p95 (time-to-next-token across the
    drain) alongside `migrated_replay_tokens` — live migration must
    report 0 (the sibling RESUMES the decode) vs the cold-resubmit
    baseline's full replay of every already-streamed token — and the
    page-service adoption counters.  Runs with started workers so the
    gap measures real wall time, per transport."""
    import threading

    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry
    from paddle_tpu.serving import fleet as fleet_mod
    from paddle_tpu.serving.fleet import (FleetConfig, FleetRouter,
                                          ReplicaSpec)

    reg = StatRegistry.instance()
    for name in list(reg.stats()):
        if name.startswith(fleet_mod.PREFIX):
            reg.get_stat(name).reset()
    total = sys_tokens + new_tokens
    pages = (-(-total // page_size) + 2) * 3
    specs = [
        ReplicaSpec(
            f"r{i}", model,
            g.GenerationConfig(max_decode_slots=4, num_pages=pages,
                               page_size=page_size, prefix_cache=True,
                               prefill_chunk_tokens=chunk_tokens),
            transport=transport)
        for i in range(2)]
    fl = FleetRouter(specs, FleetConfig(start=True, seed=7,
                                        live_migration=live))
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, model.vocab_size, sys_tokens).tolist()
    h = fl.submit(prompt, max_new_tokens=new_tokens, session="probe")
    arrivals = []

    def consume():
        for _ in h.tokens(timeout=60):
            arrivals.append(time.monotonic())

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()
    # let the stream establish, then pull the replica out mid-decode
    deadline = time.monotonic() + 60
    while len(arrivals) < max(4, new_tokens // 8) \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    drained_at = len(arrivals)
    t0 = time.monotonic()
    fl.drain(fl.replica_of("probe"), migrate=True)
    drain_s = time.monotonic() - t0
    consumer.join(timeout=120)
    result = h.result(timeout=10)
    gaps = np.diff(np.asarray(arrivals))
    snap = fl.stats_snapshot()["fleet"]
    fl.shutdown()

    def pct(q):
        # a starved cell (fewer than 2 arrivals before the deadline)
        # reports null gaps instead of crashing the whole artifact
        return (None if gaps.size == 0
                else round(float(np.percentile(gaps, q)), 4))

    return {
        "scenario": "fleet_drain",
        "transport": transport,
        "migration": "live" if live else "cold-resubmit",
        "tokens_streamed": len(result.token_ids),
        "tokens_before_drain": drained_at,
        "drain_wall_s": round(drain_s, 4),
        "stream_gap_p50_s": pct(50),
        "stream_gap_p95_s": pct(95),
        "stream_gap_max_s": (None if gaps.size == 0
                             else round(float(np.max(gaps)), 4)),
        "migrated_total": snap.get("fleet.migrated_total", 0),
        "live_migrated_total":
            snap.get("fleet.live_migrated_total", 0),
        "migrated_replay_tokens":
            snap.get("fleet.migrated_replay_tokens", 0),
        "page_adoptions": snap.get("fleet.page_adoptions", 0),
        "pages_adopted": snap.get("fleet.pages_adopted", 0),
    }


def bench_page_transfer(model, transfer, codec, sys_tokens, new_tokens,
                        page_size, chunk_tokens):
    """One DATA-PLANE A/B cell: a 2-replica fleet seeds a warm prefix
    on the holder, then a request lands on the importer and the page
    transfer ships it over — once per (page_transfer, page_codec)
    combo.  Reports the wire bytes the transfer actually moved, the
    ROUTER-RELAY bytes (the p2p acceptance number: must be 0 — pages
    dial the holder's data port directly, the router only books the
    index), the raw-byte baseline and the measured compression ratio
    (raw / wire; the synthetic bench model's int8-grid KV is
    near-incompressible, so this cell reports the honest ratio for
    THIS data — the codec's >= 2x capacity on low-entropy pages is
    pinned by tests/test_data_plane.py), the async transfer wall, and
    the warm TTFT the importer serves after adoption."""
    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry
    from paddle_tpu.serving import fleet as fleet_mod
    from paddle_tpu.serving.fleet import (FleetConfig, FleetRouter,
                                          ReplicaSpec)

    reg = StatRegistry.instance()
    for name in list(reg.stats()):
        if name.startswith(fleet_mod.PREFIX):
            reg.get_stat(name).reset()
    total = sys_tokens + new_tokens
    pages = (-(-total // page_size) + 2) * 4
    specs = [
        ReplicaSpec(
            f"r{i}", model,
            g.GenerationConfig(max_decode_slots=4, num_pages=pages,
                               page_size=page_size, prefix_cache=True,
                               prefill_chunk_tokens=chunk_tokens))
        for i in range(2)]
    fl = FleetRouter(specs, FleetConfig(start=False, seed=7,
                                        page_transfer=transfer,
                                        page_codec=codec))
    rng = np.random.default_rng(sys_tokens * 11 + 3)
    system = rng.integers(0, model.vocab_size, sys_tokens).tolist()
    sfx = rng.integers(0, model.vocab_size, (2, 4)).tolist()
    # seed the warm prefix on the holder (cold TTFT baseline) — the
    # first pass also pays every per-shape compile on both replicas
    fl._sessions["seed"] = "r0"
    h_cold = fl.submit(system + sfx[0], max_new_tokens=new_tokens,
                       session="seed")
    fl.run_until_idle()
    h_cold.result(timeout=60)
    fl.stats_snapshot()            # flush prefix deltas into the index
    # the adoption: a shared-prefix request lands on the importer;
    # routing returns immediately, the transfer ships asynchronously
    fl._sessions["imp"] = "r1"
    t0 = time.perf_counter()
    h_warm = fl.submit(system + sfx[1], max_new_tokens=new_tokens,
                       session="imp")
    transferred = fl.wait_transfers(timeout=60)
    transfer_wall = time.perf_counter() - t0
    fl.run_until_idle()
    h_warm.result(timeout=60)
    snap = fl.stats_snapshot()["fleet"]
    fl.shutdown()
    wire = (snap.get("fleet.page_p2p_bytes", 0)
            + snap.get("fleet.page_relay_bytes", 0))
    # the relay path ships the un-encoded payload, so its raw
    # baseline IS its wire bill (the codec only rides the p2p port)
    raw = snap.get("fleet.page_raw_bytes", 0) or wire
    return {
        "scenario": "page_transfer",
        "page_transfer": transfer,
        "page_codec": codec,
        "sys_tokens": sys_tokens,
        "new_tokens": new_tokens,
        "transfer_drained": bool(transferred),
        "page_adoptions": snap.get("fleet.page_adoptions", 0),
        "pages_adopted": snap.get("fleet.pages_adopted", 0),
        "wire_bytes": wire,
        # the p2p acceptance counter: page payload bytes that crossed
        # the ROUTER socket (p2p cells must report 0)
        "router_relay_bytes": snap.get("fleet.page_relay_bytes", 0),
        "raw_bytes": raw,
        "compression_ratio": (round(raw / wire, 3) if wire else None),
        "transfer_wall_s": round(transfer_wall, 4),
        "cold_ttft_s": round(
            h_cold.first_token_s - h_cold.submitted_s, 4),
        "warm_ttft_after_adoption_s": round(
            h_warm.first_token_s - h_warm.submitted_s, 4),
        "warm_hit_tokens": h_warm.prefix_hit_tokens or 0,
        "transfers_failed": snap.get("fleet.page_transfers_failed", 0),
        "transfers_cancelled":
            snap.get("fleet.page_transfers_cancelled", 0),
    }


def bench_spec(model, batch, context, new_tokens, page_size, spec_mode,
               spec_tokens, workload):
    """One SPECULATIVE-decoding A/B cell: the ragged engine with
    spec_mode off vs "ngram" (prompt-lookup proposer, k-token verify
    in the one ragged dispatch, on-device accept).

    Two workload shapes bound the story from both sides:

    - "repeat": code/RAG-shaped prompts — a short random pattern tiled
      to the context length, so the token history is dense with n-gram
      recurrences and prompt lookup HITS (the free-win cell);
    - "random": the plain rng workload of the main grid, where lookup
      mostly misses — the overhead-bound cell (the spec axis is wider
      and every miss is a proposer scan; the acceptance criterion is
      "no regression worse than ~10%", not a win).

    Reports steady-state tokens/s, acceptance rate, mean accepted
    drafts per verify row (accepted / spec_draft_rows), rewind tokens,
    and dispatches/step (must stay 1 — speculation may never add a
    dispatch)."""
    from paddle_tpu import generation as g
    from paddle_tpu.generation import metrics as gmetrics
    from paddle_tpu.profiler.monitor import StatRegistry

    rng = np.random.default_rng(7000 + batch)
    if workload == "repeat":
        prompts = []
        for _ in range(batch):
            base = rng.integers(0, model.vocab_size, 8).tolist()
            reps = -(-context // len(base))
            prompts.append((base * reps)[:context])
    else:
        prompts = [rng.integers(0, model.vocab_size, context).tolist()
                   for _ in range(batch)]
    pages = ((context + new_tokens + spec_tokens)
             // page_size + 2) * batch
    eng = g.GenerationEngine(
        model,
        g.GenerationConfig(max_decode_slots=batch, num_pages=pages,
                           page_size=page_size, queue_depth=batch * 2,
                           kv_backend="device", step_mode="ragged",
                           spec_mode=spec_mode,
                           spec_tokens=spec_tokens),
        start=False)

    def run_once():
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        return dt, [h.result(timeout=1) for h in handles]

    warmup_s, _ = run_once()
    reg = StatRegistry.instance()
    stats = {name: reg.get_stat(name) for name in (
        gmetrics.STEPS_TOTAL, gmetrics.SPEC_PROPOSED_TOKENS,
        gmetrics.SPEC_ACCEPTED_TOKENS, gmetrics.SPEC_REWIND_TOKENS,
        gmetrics.SPEC_DRAFT_ROWS,
        gmetrics.DECODE_COMPILES_TOTAL, gmetrics.PREFILL_COMPILES_TOTAL)}
    before = {name: s.get() for name, s in stats.items()}
    dt, results = run_once()
    delta = {name: int(s.get() - before[name])
             for name, s in stats.items()}
    generated = sum(len(r.token_ids) for r in results)
    steps = delta[gmetrics.STEPS_TOTAL]
    proposed = delta[gmetrics.SPEC_PROPOSED_TOKENS]
    accepted = delta[gmetrics.SPEC_ACCEPTED_TOKENS]
    snap = eng.metrics.snapshot()
    cell = {
        "cell": "spec",
        "workload": workload,
        "spec_mode": spec_mode or "off",
        "spec_tokens": spec_tokens,
        "batch": batch,
        "context": context,
        "new_tokens": new_tokens,
        "warmup_s": round(warmup_s, 4),
        "elapsed_s": round(dt, 4),
        "generated": int(generated),
        "tokens_per_s": round(generated / dt, 1) if dt > 0 else None,
        "steps": steps,
        "tokens_per_step": round(generated / steps, 3) if steps else None,
        "spec_proposed": proposed,
        "spec_accepted": accepted,
        "spec_rewind": delta[gmetrics.SPEC_REWIND_TOKENS],
        "acceptance_rate": (round(accepted / proposed, 3)
                            if proposed else None),
        # mean accepted drafts per VERIFY ROW (one row per drafting
        # sequence per step — the true mean accepted length; the
        # per-dispatch bonus token is excluded)
        "mean_accepted_len": (
            round(accepted / delta[gmetrics.SPEC_DRAFT_ROWS], 3)
            if delta[gmetrics.SPEC_DRAFT_ROWS] else None),
        "dispatches_per_step":
            snap["generation.decode_dispatches_per_step"],
        "host_syncs_per_step":
            snap["generation.decode_host_syncs_per_step"],
        "measured_compiles": delta[gmetrics.DECODE_COMPILES_TOTAL]
            + delta[gmetrics.PREFILL_COMPILES_TOTAL],
    }
    eng.shutdown()
    return cell


def bench_loop(model, batch, context, new_tokens, page_size, loop_steps,
               spec_tokens=0, stochastic=False, ttft_probe=False):
    """One HOST-FREE DECODE LOOP A/B cell: the ragged engine at
    loop_steps=N (N ragged iterations fused into ONE dispatch, ONE
    host fetch per N tokens per row) vs the per-step N=1 baseline.

    The decode-bound cell the loop exists for: short prompts, long
    generations, so nearly every engine boundary is decode-only and
    takes the fused loop.  Reports steady-state tokens/s, host
    fetches per token (<= 1/N is the acceptance floor), dispatches
    per boundary (must stay 1), early exits and wasted iterations
    (rows finishing mid-loop), and — with `ttft_probe` — the TTFT of
    a prompt submitted mid-stream, which can only join at a loop
    boundary: the join-latency cost the N knob trades against
    throughput (docs/GENERATION.md "Host-free decode loop")."""
    from paddle_tpu import generation as g
    from paddle_tpu.generation import metrics as gmetrics
    from paddle_tpu.profiler.monitor import StatRegistry

    rng = np.random.default_rng(9000 + batch)
    prompts = [rng.integers(0, model.vocab_size, context).tolist()
               for _ in range(batch)]
    horizon = new_tokens + loop_steps + spec_tokens
    pages = ((context + horizon) // page_size + 2) * (batch + 1)
    kw = {}
    if spec_tokens:
        kw.update(spec_mode="ngram", spec_tokens=spec_tokens)
    eng = g.GenerationEngine(
        model,
        g.GenerationConfig(max_decode_slots=batch, num_pages=pages,
                           page_size=page_size, queue_depth=batch * 2,
                           kv_backend="device", step_mode="ragged",
                           loop_steps=loop_steps, **kw),
        start=False)
    samp = (g.SamplingParams(temperature=0.9, top_k=16, seed=5)
            if stochastic else None)

    def run_once():
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=new_tokens,
                              sampling=samp or g.SamplingParams())
                   for p in prompts]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        return dt, [h.result(timeout=1) for h in handles]

    warmup_s, _ = run_once()
    reg = StatRegistry.instance()
    counters = {name: reg.get_stat(name) for name in (
        gmetrics.STEPS_TOTAL, gmetrics.LOOP_EARLY_EXITS,
        gmetrics.LOOP_WASTED_STEPS,
        gmetrics.DECODE_COMPILES_TOTAL, gmetrics.PREFILL_COMPILES_TOTAL)}
    before = {name: s.get() for name, s in counters.items()}
    dt, results = run_once()
    delta = {name: int(s.get() - before[name])
             for name, s in counters.items()}
    ttft_join_s = None
    if ttft_probe:
        # a prompt submitted while the batch decodes joins at the next
        # loop boundary: its TTFT carries up to N-1 steps of wait
        bg = [eng.submit(p, max_new_tokens=new_tokens)
              for p in prompts[:max(1, batch - 1)]]
        while not eng.scheduler.decode_ready():
            eng.step()
        probe = eng.submit(prompts[-1][:4], max_new_tokens=4)
        eng.run_until_idle()
        for h in bg + [probe]:
            h.result(timeout=1)
        ttft_join_s = probe.first_token_s - probe.submitted_s
    generated = sum(len(r.token_ids) for r in results)
    steps = delta[gmetrics.STEPS_TOTAL]
    snap = eng.metrics.snapshot()
    cell = {
        "cell": "loop",
        "loop_steps": loop_steps,
        "spec_tokens": spec_tokens,
        "stochastic": bool(stochastic),
        "batch": batch,
        "context": context,
        "new_tokens": new_tokens,
        "warmup_s": round(warmup_s, 4),
        "elapsed_s": round(dt, 4),
        "generated": int(generated),
        "tokens_per_s": round(generated / dt, 1) if dt > 0 else None,
        "steps": steps,
        "tokens_per_step": round(generated / steps, 3) if steps else None,
        # the acceptance ratio: cumulative host fetches over decode
        # tokens for THIS engine (stamped 0.0 at build, so the N=1
        # baseline reports 0.0 — it never takes the loop path)
        "host_fetches_per_token":
            snap["generation.decode_host_fetches_per_token"],
        "loop_early_exits": delta[gmetrics.LOOP_EARLY_EXITS],
        "loop_wasted_steps": delta[gmetrics.LOOP_WASTED_STEPS],
        "dispatches_per_step":
            snap["generation.decode_dispatches_per_step"],
        "host_syncs_per_step":
            snap["generation.decode_host_syncs_per_step"],
        "ttft_join_s": (round(ttft_join_s, 4)
                        if ttft_join_s is not None else None),
        "measured_compiles": delta[gmetrics.DECODE_COMPILES_TOTAL]
            + delta[gmetrics.PREFILL_COMPILES_TOTAL],
    }
    eng.shutdown()
    return cell


def bench_chaos(model, seed, n_replicas, requests, new_tokens):
    """The chaos-soak bench cell: a seeded KILL + STALL schedule over
    a subprocess fleet under concurrent streams (serving/disagg/
    chaos.py drill) — stream-gap p50/p95 across the faults, recovery
    wall, breaker trips, wedge kills, replay tokens, and the no-hang/
    no-leak/token-identity invariants as cell facts.  Environments
    without fd-inheriting subprocesses emit a skipped cell instead of
    sinking the whole artifact."""
    from paddle_tpu.serving.disagg.chaos import (chaos_drill,
                                                 kill_stall_plans)

    names = [f"c{i}" for i in range(n_replicas)]
    try:
        report = chaos_drill(
            model, seed=seed, n_replicas=n_replicas,
            n_requests=requests, new_tokens=new_tokens,
            plans=kill_stall_plans(seed, names), watchdog_s=120.0,
            restart_dead=True)
    except AssertionError as e:
        return {"cell": "chaos", "invariant_broken": str(e)}
    except Exception as e:   # noqa: BLE001 — a sandbox without
        # subprocess replicas must not sink the artifact
        return {"cell": "chaos", "skipped": f"{type(e).__name__}: {e}"}
    return {"cell": "chaos", "schedule": "kill+stall", **report}


def bench_pd(model, mode, sessions, long_tokens, new_tokens, page_size,
             chunk_tokens):
    """The P/D-disaggregation A/B cell: a LONG-prompt prefill wave
    arriving concurrently with SHORT interactive requests, run once
    per fleet shape — 'split' (one prefill-class + one decode-class
    replica: longs prefill on one side, hand off, and decode next to
    the shorts) vs 'mixed' (two role-less replicas, the ablation
    baseline where shorts queue behind whatever prefill landed on
    their replica).  The headline number is the SHORT-request
    (decode-class) TTFT p95: split keeps the interactive path clear of
    prefill head-of-line blocking, and the handoff books must show
    pd_handoffs > 0 with migrated_replay_tokens == 0 (the import
    resumes at base, never replays)."""
    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry
    from paddle_tpu.serving import fleet as fleet_mod
    from paddle_tpu.serving.fleet import (FleetConfig, FleetRouter,
                                          ReplicaSpec)

    reg = StatRegistry.instance()
    for name in list(reg.stats()):
        if name.startswith(fleet_mod.PREFIX):
            reg.get_stat(name).reset()
    short_tokens = 4
    total = long_tokens + new_tokens
    pages = (-(-total // page_size) + 2) * (2 * sessions + 2)
    roles = (("prefill", "decode") if mode == "split"
             else ("mixed", "mixed"))
    specs = [
        ReplicaSpec(
            f"{role[:2]}{i}", model,
            g.GenerationConfig(max_decode_slots=4, num_pages=pages,
                               page_size=page_size,
                               queue_depth=2 * sessions + 4,
                               prefix_cache=True,
                               prefill_chunk_tokens=chunk_tokens),
            role=role)
        for i, role in enumerate(roles)]
    fl = FleetRouter(specs, FleetConfig(
        start=True, seed=7,
        pd_prefill_threshold_tokens=max(16, long_tokens // 4)))
    rng = np.random.default_rng(long_tokens * 13 + sessions)
    half = model.vocab_size // 2

    def run_wave(lo, hi):
        longs = [fl.submit(rng.integers(lo, hi, long_tokens).tolist(),
                           max_new_tokens=new_tokens)
                 for _ in range(sessions)]
        shorts = [fl.submit(rng.integers(lo, hi,
                                         short_tokens).tolist(),
                            max_new_tokens=new_tokens)
                  for _ in range(sessions)]
        for h in longs + shorts:
            h.result(timeout=300)
        return longs, shorts

    # warmup from the other vocab half: every per-shape jit is paid
    # before the timed wave, nothing it prefilled warms the real one
    run_wave(half, model.vocab_size)
    for name, rep in fl._replicas.items():
        rep.transport.flush_prefix()
        rep.transport.reset_stats()
        rep.transport.take_prefix_deltas()
        fl._page_index.drop_replica(name)
    for name in list(reg.stats()):
        if name.startswith(fleet_mod.PREFIX):
            reg.get_stat(name).reset()
    longs, shorts = run_wave(0, half)
    snap = fl.stats_snapshot()["fleet"]
    fl.shutdown()

    def ttft(handles):
        gaps = sorted(h.first_token_s - h.submitted_s for h in handles)
        return (round(float(np.percentile(gaps, 50)), 4),
                round(float(np.percentile(gaps, 95)), 4))

    s50, s95 = ttft(shorts)
    l50, l95 = ttft(longs)
    return {
        "scenario": "pd_disagg",
        "mode": mode,
        "replicas": 2,
        "long_prompts": sessions,
        "short_prompts": sessions,
        "long_tokens": long_tokens,
        "short_tokens": short_tokens,
        "new_tokens": new_tokens,
        "decode_class_ttft_p50_s": s50,
        "decode_class_ttft_p95_s": s95,
        "long_ttft_p50_s": l50,
        "long_ttft_p95_s": l95,
        "pd_handoffs": snap.get("fleet.pd_handoffs", 0),
        "pd_handoff_tokens": snap.get("fleet.pd_handoff_tokens", 0),
        "routed_role": snap.get("fleet.routed_role", 0),
        "replay_tokens": snap.get("fleet.migrated_replay_tokens", 0),
        "shed_total": snap.get("fleet.shed_total", 0),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,4,8")
    ap.add_argument("--contexts", default="32,128")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool", choices=("host", "device", "both"),
                    default="both",
                    help="KV backend A/B: host numpy pools vs "
                         "device-resident DeviceKVPool (donated "
                         "scatter appends); 'both' emits one tokens/s "
                         "series per backend")
    ap.add_argument("--decode", choices=("eager", "fused", "both"),
                    default="eager",
                    help="decode-path A/B: eager per-layer attend "
                         "callbacks vs the fused single-dispatch "
                         "FusedDecodeStep (device pools only — "
                         "host-pool fused cells are skipped); steps/s "
                         "is steady-state with compile/warmup time in "
                         "the separate warmup_s column")
    ap.add_argument("--prefill", choices=("full", "chunked", "both"),
                    default="full",
                    help="prefill-path A/B: one monolithic bucketed "
                         "prefill per prompt vs CHUNKED prefill "
                         "(fixed-size chunks interleaved with decode "
                         "under the step token budget); each series "
                         "adds an 'interleave' cell measuring TTFT and "
                         "decode tokens/s while a long prompt streams "
                         "in")
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="chunk size for --prefill chunked/both")
    ap.add_argument("--step", choices=("legacy", "ragged", "both"),
                    default="legacy",
                    help="step-executable A/B: the legacy pair "
                         "(FusedDecodeStep / ChunkedPrefillStep per "
                         "--decode/--prefill) vs the RAGGED mixed-batch "
                         "step (decode rows + the prefill chunk packed "
                         "into ONE dispatch, one executable per pages "
                         "bucket TOTAL, zero dummy rows); ragged cells "
                         "run device pools, report steady-state "
                         "tokens/s, dispatches/step, measured "
                         "row_utilization and padded_token_waste, and "
                         "add their own TTFT-under-interleave cell")
    ap.add_argument("--prefix", choices=("off", "on", "both"),
                    default="off",
                    help="prefix-cache A/B: a shared-system-prompt "
                         "workload (one long system prefix, distinct "
                         "short user suffixes) per pool backend — warm "
                         "vs cold TTFT, prefill tokens computed, hit "
                         "tokens, live shared_pages, COW copies; "
                         "'both' emits an off and an on cell")
    ap.add_argument("--prefix-users", type=int, default=8,
                    help="concurrent users sharing the system prompt "
                         "in the --prefix scenario")
    ap.add_argument("--replicas", default="0",
                    help="fleet-tier A/B: '1' (single-replica "
                         "baseline), 'N' (a 2-replica fleet), 'both', "
                         "or an explicit replica count; '0' (default) "
                         "skips the scenario.  Multi-replica cells run "
                         "TWICE — affinity routing (session -> prefix "
                         "-> least-loaded) vs random — over a "
                         "shared-system-prompt multi-turn session "
                         "workload, reporting per-replica hit rate, "
                         "shed rate, and TTFT p50/p95")
    ap.add_argument("--fleet-sessions", type=int, default=8,
                    help="concurrent sessions in the --replicas "
                         "scenario (each runs 2 turns)")
    ap.add_argument("--fleet-transport",
                    choices=("inproc", "proc", "tcp", "both"),
                    default="inproc",
                    help="replica process boundary A/B for the fleet "
                         "cells: 'inproc' (direct-object engines), "
                         "'proc' (one OS process per replica behind "
                         "the SubprocTransport RPC boundary), 'tcp' "
                         "(the same worker dialing back over a real "
                         "TCP socket — the cross-host rung), or "
                         "'both' (inproc + proc).  Each transport "
                         "also emits a "
                         "DRAIN-MIGRATION probe cell pair — live "
                         "migration vs cold resubmit — reporting "
                         "stream-gap p95 across the drain, "
                         "migrated_replay_tokens (live must report 0) "
                         "and page-service adoption counters")
    ap.add_argument("--page-transfer",
                    choices=("off", "relay", "p2p", "both"),
                    default="off",
                    help="data-plane A/B: a 2-replica fleet ships one "
                         "warm prefix to the importer per cell — "
                         "'relay' (page payloads ride the router "
                         "socket) vs 'p2p' (the importer dials the "
                         "holder's data port; router_relay_bytes must "
                         "report 0), or 'both'.  Each cell reports "
                         "wire bytes, raw bytes, compression ratio, "
                         "async transfer wall, and the warm TTFT the "
                         "importer serves after adoption")
    ap.add_argument("--page-codec",
                    choices=("raw", "compressed", "both"),
                    default="compressed",
                    help="page payload codec for the --page-transfer "
                         "cells: 'raw' (byte-exact baseline, wire == "
                         "raw) vs 'compressed' (per-page delta filter "
                         "+ zlib, bitwise-lossless, raw fallback per "
                         "array), or 'both' for the codec A/B pair "
                         "per transfer mode")
    ap.add_argument("--mesh", default="1",
                    help="tensor-parallel A/B: '1' (unsharded), 'N' "
                         "(head-sharded over every visible device), "
                         "'both', or an explicit tp_degree.  Sharded "
                         "cells run device pools + fused decode "
                         "(GenerationConfig.mesh — ONE GSPMD dispatch "
                         "per step) and report tp_degree + "
                         "collective_bytes_per_step + kernel_path per "
                         "cell; every sharded combo runs TWICE — jnp "
                         "reference vs the shard_map'd Pallas kernel "
                         "(the kernel-vs-reference A/B under the mesh)")
    ap.add_argument("--kv-quant", choices=("off", "bf16", "int8", "both"),
                    default="off",
                    help="KV storage precision A/B on device pools: "
                         "bf16 vs INT8 pools (per-page per-head abs-max "
                         "scales, in-kernel dequant) — per-cell "
                         "tokens/s, kv_bytes_moved (+ split-out "
                         "kv_scale_bytes), and resident-sequence "
                         "capacity at a FIXED pool byte budget "
                         "(resident_seqs_at_fixed_budget: int8 ~2x "
                         "bf16).  'both' runs the pair; int8 also "
                         "emits a kv_quality cell (max-logit drift + "
                         "greedy-token agreement vs the fp32 oracle — "
                         "the quality gate the lossy path ships under)")
    ap.add_argument("--spec", choices=("off", "ngram", "both"),
                    default="off",
                    help="speculative-decoding A/B on the ragged step: "
                         "spec_mode off vs 'ngram' (prompt-lookup "
                         "proposer, k-token verify in ONE dispatch, "
                         "on-device accept) over a repetition-heavy "
                         "workload (tiled code-like prompts, where "
                         "lookup hits) AND the plain rng workload (the "
                         "overhead-bound cell) — tokens/s, acceptance "
                         "rate, mean tokens/step, rewind tokens, "
                         "dispatches/step (still 1) per cell")
    ap.add_argument("--spec-tokens", type=int, default=3,
                    help="draft cap per speculating row for --spec "
                         "(3 measured best on CPU, where the packed "
                         "axis is real FLOPs; sweep upward on TPU)")
    ap.add_argument("--loop-steps", default="0",
                    help="host-free decode loop A/B on the ragged "
                         "step: comma list of N values (each one cell "
                         "at loop_steps=N; 1 = the per-step baseline) "
                         "or 'both' for the 1,4,8 ladder — decode-"
                         "bound cells reporting tokens/s, host "
                         "fetches/token (<= 1/N), dispatches/step "
                         "(still 1), early exits, wasted iterations, "
                         "and the TTFT of a mid-stream join (which "
                         "waits for a loop boundary); '0' disables")
    ap.add_argument("--loop-stochastic", action="store_true",
                    help="sample the --loop-steps cells at temperature "
                         "0.9/top-k 16 instead of greedy: the "
                         "on-device sampler's cost inside the loop "
                         "vs the host sampler at N=1")
    ap.add_argument("--quant-collectives", action="store_true",
                    help="EQuARX-style quantized-allreduce A/B: every "
                         "SHARDED (tp > 1) combo runs an extra cell "
                         "with quantized_collectives=True — same grid, "
                         "collective_bytes_per_step ~4x lower, "
                         "collective_quantized=1 stamped — paired "
                         "against its fp32-collective sibling")
    ap.add_argument("--pd", choices=("off", "mixed", "split", "both"),
                    default="off",
                    help="prefill/decode disaggregation A/B: a "
                         "long-prompt prefill wave concurrent with "
                         "short interactive requests over a 2-replica "
                         "fleet — 'split' (prefill-class + "
                         "decode-class, longs hand off at "
                         "prompt-consumed) vs 'mixed' (role-less "
                         "ablation baseline), or 'both'.  Reports "
                         "decode-class (short-request) TTFT p50/p95, "
                         "pd_handoffs / pd_handoff_tokens, and "
                         "replay_tokens (must be 0: the import "
                         "resumes at base)")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos-soak cell: a seeded kill+stall fault "
                         "schedule over a 3-replica subprocess fleet "
                         "under concurrent streams — stream-gap "
                         "p50/p95, recovery wall, breaker trips, "
                         "wedge kills, replay tokens; the cell also "
                         "asserts the no-hang / token-identity / "
                         "zero-leak invariants")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="fault-schedule seed for --chaos")
    ap.add_argument("--long-context", type=int, default=None,
                    help="long-prompt length for the interleave cell "
                         "(default: 8x the largest --contexts entry)")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    args = ap.parse_args()

    # a multi-device CPU mesh needs forced host devices, and the flag
    # must land before the backend initializes (no devices have been
    # touched yet)
    if (args.mesh != "1" and os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    import jax

    from paddle_tpu import generation as g
    from paddle_tpu.profiler.monitor import StatRegistry
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    batches = [int(b) for b in args.batches.split(",")]
    contexts = [int(c) for c in args.contexts.split(",")]
    long_ctx = args.long_context or max(contexts) * 8
    model = g.TinyCausalLM(vocab_size=args.vocab, num_layers=args.layers,
                           num_heads=args.heads, head_dim=args.head_dim,
                           max_positions=(max(max(contexts), long_ctx)
                                          + args.new_tokens + 1),
                           seed=0)
    pools = (("host", "device") if args.pool == "both" else (args.pool,))
    decodes = (("eager", "fused") if args.decode == "both"
               else (args.decode,))
    prefills = (("full", "chunked") if args.prefill == "both"
                else (args.prefill,))
    ndev = len(jax.devices())

    def shardable(n):
        # the head axis is the shard axis: the auto degree is the
        # largest device count that divides --heads (an explicit
        # integer skips this and fails loudly in the engine instead)
        while n > 1 and args.heads % n:
            n -= 1
        return n

    if args.mesh == "both":
        tps = sorted({1, shardable(ndev)})
    elif args.mesh == "N":
        tps = [shardable(ndev)]
    else:
        tps = [int(args.mesh)]
    def mesh_kernel_variants(tp):
        # the kernel-vs-reference A/B under the mesh: every sharded
        # combo runs TWICE — the jnp reference (GSPMD-partitioned) and
        # the shard_map'd Pallas kernel — so the artifact carries the
        # first sharded-kernel numbers instead of inferring them.
        # Unsharded cells keep the auto policy (None).
        return (False, True) if tp > 1 else (None,)

    combos = []
    for pool in pools:
        for decode in decodes:
            if decode == "fused" and pool != "device":
                continue  # fused requires donated device pools
            for prefill in prefills:
                for tp in tps:
                    if tp > 1 and (pool, decode) != ("device", "fused"):
                        continue  # sharded decode IS device + fused
                    combos += [(pool, decode, prefill, tp, "legacy", k)
                               for k in mesh_kernel_variants(tp)]
    if max(tps) > 1 and not any(tp > 1 for *_, tp, _, _ in combos):
        # the mesh A/B must not silently vanish because the requested
        # --pool/--decode combo can't shard: force the one that can
        combos += [("device", "fused", prefill, tp, "legacy", k)
                   for prefill in prefills for tp in tps if tp > 1
                   for k in mesh_kernel_variants(tp)]
    if args.step == "legacy":
        pass
    else:
        # the ragged mixed-batch step: one series per prefill mode on
        # device pools (the ragged step's `decode` label IS 'ragged' —
        # the one executable replaces the eager/fused choice), at every
        # requested tp degree — the shard_map'd kernel made mesh cells
        # real kernel cells, so sharded ragged runs the A/B too
        ragged = [("device", "ragged", prefill, tp, "ragged", k)
                  for prefill in prefills for tp in tps
                  for k in mesh_kernel_variants(tp)]
        combos = ragged if args.step == "ragged" else combos + ragged
    grid = []
    stats_by_series = {}
    reg = StatRegistry.instance()

    def reset_gen_stats():
        for name in list(reg.stats()):
            if name.startswith("generation."):
                reg.get_stat(name).reset()

    for pool, decode, prefill, tp, step, kern in combos:
        # per-series snapshot: reset generation.* so each
        # (pool, decode, prefill, tp, step, kernel) combo's stats land
        # apart
        reset_gen_stats()
        for b in batches:
            for ctx in contexts:
                # pool sized to fit the cell w/o preemption noise
                pages = ((ctx + args.new_tokens)
                         // args.page_size + 2) * b
                grid.append(bench_cell(
                    model, b, ctx, args.new_tokens, pages,
                    args.page_size, pool, decode, prefill,
                    args.chunk_tokens, tp=tp, step=step,
                    use_kernel=kern))
        # the prefill/decode-interleave cell: decode throughput
        # while a long prompt streams in (the chunked-prefill
        # headline number; unsharded — the mesh A/B is the grid's)
        ib = max(batches)
        if ib > 1 and tp == 1:
            grid.append(bench_interleave(
                model, ib, min(contexts), long_ctx,
                args.new_tokens, args.page_size, pool, decode,
                prefill, args.chunk_tokens, step=step))
            if prefill == "chunked":
                # the multi-prompt packing A/B: the same interleave
                # traffic with packing OFF (one chunk per step) — the
                # late short's ttft_short_behind_long_s is the paired
                # number packing strictly improves
                grid.append(bench_interleave(
                    model, ib, min(contexts), long_ctx,
                    args.new_tokens, args.page_size, pool, decode,
                    prefill, args.chunk_tokens, step=step, pack=False))
        series = f"{pool}/{decode}/{prefill}" + (
            f"/tp{tp}" if tp > 1 else "") + (
            "" if kern is None else
            ("/kernel" if kern else "/ref"))
        stats_by_series[series] = reg.stats_snapshot("generation.")

    if args.kv_quant != "off":
        # KV precision A/B on device pools (fused decode — the
        # CPU-forced fast path, so the bytes numbers are the device
        # story): bf16 vs int8 cells at the SAME page count; the
        # capacity headline is the per-cell
        # resident_seqs_at_fixed_budget arithmetic
        kv_menu = {"bf16": ("bfloat16",), "int8": ("int8",),
                   "both": ("bfloat16", "int8")}[args.kv_quant]
        for dt in kv_menu:
            reset_gen_stats()
            for b in batches:
                for ctx in contexts:
                    pages = ((ctx + args.new_tokens)
                             // args.page_size + 2) * b
                    grid.append(bench_cell(
                        model, b, ctx, args.new_tokens, pages,
                        args.page_size, "device", "fused", "full",
                        args.chunk_tokens, kv_dtype=dt))
            stats_by_series[f"device/fused/kvq-{dt}"] = \
                reg.stats_snapshot("generation.")
        if "int8" in kv_menu:
            # the quality gate as a bench artifact: drift + agreement
            # vs the fp32 oracle on the seeded workload — the contract
            # the lossy cells ship under travels WITH their numbers
            from paddle_tpu.generation.quality import kv_quality_report

            ctx0 = min(contexts)
            pages = ((ctx0 + args.new_tokens)
                     // args.page_size + 2) * max(batches)
            mk = lambda **kw: g.GenerationConfig(  # noqa: E731
                max_decode_slots=max(batches), num_pages=pages,
                page_size=args.page_size, kv_backend="device", **kw)
            grid.append({
                "cell": "kv_quality",
                "kv_quant_dtype": "int8",
                **kv_quality_report(model, mk(), mk(kv_dtype="int8"),
                                    max_new_tokens=args.new_tokens),
            })
    if args.quant_collectives:
        # the quantized-allreduce A/B: every sharded degree reruns the
        # grid with quantized_collectives=True — pair each /qcol cell
        # with its fp32-collective sibling from the main grid and read
        # collective_bytes_per_step (~4x lower) + tokens/s
        q_step = "ragged" if args.step in ("ragged", "both") else "legacy"
        q_decode = "ragged" if q_step == "ragged" else "fused"
        for tp in [t for t in tps if t > 1]:
            reset_gen_stats()
            for b in batches:
                for ctx in contexts:
                    pages = ((ctx + args.new_tokens)
                             // args.page_size + 2) * b
                    grid.append(bench_cell(
                        model, b, ctx, args.new_tokens, pages,
                        args.page_size, "device", q_decode, "full",
                        args.chunk_tokens, tp=tp, step=q_step,
                        use_kernel=True, quant_collectives=True,
                        kv_dtype=("int8" if args.kv_quant
                                  in ("int8", "both") else None)))
            stats_by_series[f"device/{q_decode}/tp{tp}/qcol"] = \
                reg.stats_snapshot("generation.")
    if args.spec != "off":
        # the speculative-decoding A/B: ragged engine, off vs ngram,
        # repeat-heavy (prompt lookup hits) and random (overhead-bound)
        spec_modes = ((None, "ngram") if args.spec == "both"
                      else ("ngram",))
        sb = max(batches)
        for workload in ("repeat", "random"):
            for mode in spec_modes:
                reset_gen_stats()
                grid.append(bench_spec(
                    model, sb, min(contexts), args.new_tokens,
                    args.page_size, mode, args.spec_tokens, workload))
                stats_by_series[
                    f"device/spec-{mode or 'off'}/{workload}"] = \
                    reg.stats_snapshot("generation.")
    if args.loop_steps != "0":
        # the host-free decode loop A/B: one decode-bound cell per N,
        # N=1 as the per-step baseline of the same ragged engine
        ns = ([1, 4, 8] if args.loop_steps == "both"
              else sorted({int(x) for x in args.loop_steps.split(",")}))
        lb = max(batches)
        for n in ns:
            reset_gen_stats()
            grid.append(bench_loop(
                model, lb, min(contexts), args.new_tokens,
                args.page_size, n, stochastic=args.loop_stochastic,
                ttft_probe=True))
            stats_by_series[f"device/loop-{n}"] = \
                reg.stats_snapshot("generation.")
    if args.prefix != "off":
        # the shared-system-prompt A/B: chunked prefill (warm hits
        # resume mid-prompt through the chunk loop), one cell per
        # (pool, cache mode); system prompt 2x the largest context
        modes = (("off", "on") if args.prefix == "both"
                 else (args.prefix,))
        sys_tokens = max(contexts) * 2
        for pool in pools:
            for mode in modes:
                reset_gen_stats()
                grid.append(bench_prefix(
                    model, args.prefix_users, sys_tokens, 8,
                    args.new_tokens, args.page_size, pool,
                    prefix_on=(mode == "on"),
                    chunk_tokens=args.chunk_tokens))
                stats_by_series[f"{pool}/prefix-{mode}"] = \
                    reg.stats_snapshot("generation.")
    if args.replicas != "0":
        # the fleet-tier A/B: multi-turn sessions over a shared system
        # prompt, affinity vs random routing per replica count
        if args.replicas == "both":
            counts = [1, 2]
        elif args.replicas == "N":
            counts = [2]
        else:
            counts = [int(args.replicas)]
        sys_tokens = max(contexts)
        transports = (("inproc", "proc")
                      if args.fleet_transport == "both"
                      else (args.fleet_transport,))
        for transport in transports:
            for n in counts:
                routings = ("affinity",) if n == 1 \
                    else ("affinity", "random")
                for routing in routings:
                    grid.append(bench_fleet(
                        model, n, args.fleet_sessions, sys_tokens, 8,
                        args.new_tokens, args.page_size, routing,
                        args.chunk_tokens, transport=transport))
            # the drain-migration probe: live vs cold-resubmit per
            # transport (stream-gap p95, migrated_replay_tokens — the
            # live-migration acceptance number is the 0)
            for live in (True, False):
                grid.append(bench_drain_migration(
                    model, transport, live, sys_tokens,
                    max(32, args.new_tokens), args.page_size,
                    args.chunk_tokens))
    if args.page_transfer != "off":
        # the data-plane A/B: relay vs p2p wire x raw vs compressed
        # codec — one adoption cell per combo, router_relay_bytes the
        # p2p acceptance number (0) and compression_ratio the honest
        # measured ratio on this model's pages
        pt_modes = (("relay", "p2p") if args.page_transfer == "both"
                    else (args.page_transfer,))
        pc_modes = (("raw", "compressed") if args.page_codec == "both"
                    else (args.page_codec,))
        for transfer in pt_modes:
            for codec in pc_modes:
                grid.append(bench_page_transfer(
                    model, transfer, codec, max(contexts),
                    args.new_tokens, args.page_size,
                    args.chunk_tokens))
    if args.pd != "off":
        # P/D disaggregation A/B: split (prefill + decode classes)
        # vs mixed (role-less baseline) under the same long-wave +
        # interactive workload — the decode-class TTFT p95 is the
        # headline, the handoff books are the proof of mechanism
        pd_modes = (("mixed", "split") if args.pd == "both"
                    else (args.pd,))
        for mode in pd_modes:
            grid.append(bench_pd(
                model, mode, args.fleet_sessions, max(contexts),
                args.new_tokens, args.page_size, args.chunk_tokens))
    if args.chaos:
        # the chaos soak: seeded kill+stall over a subprocess fleet —
        # the robustness sibling of the drain probe (faults INJECTED,
        # not administered)
        grid.append(bench_chaos(model, args.chaos_seed, 3, 8,
                                max(8, min(16, args.new_tokens))))
    doc = {
        "bench": "generation_decode",
        "platform": jax.devices()[0].platform,
        "model": {"vocab": args.vocab, "layers": args.layers,
                  "heads": args.heads, "head_dim": args.head_dim},
        "pools": list(pools),
        "decodes": list(decodes),
        "prefills": list(prefills),
        "tp_degrees": list(tps),
        "step": args.step,
        "spec": args.spec,
        "chunk_tokens": args.chunk_tokens,
        "prefix": args.prefix,
        "replicas": args.replicas,
        "fleet_transport": args.fleet_transport,
        "pd": args.pd,
        "page_transfer": args.page_transfer,
        "page_codec": args.page_codec,
        "chaos": bool(args.chaos),
        "grid": grid,
        "stats": stats_by_series,
    }
    line = json.dumps(doc)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
