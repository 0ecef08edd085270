"""Generation metrics: `generation.*` counters/gauges in the profiler
StatRegistry (the serving.* pattern from serving/metrics.py, applied to
the decode engine).

Exposes the same three methods AdmissionQueue calls on its metrics
object (`set_queue_depth`, `count_rejected_busy`,
`count_rejected_deadline`), so the generation scheduler reuses the
serving AdmissionQueue unchanged — bounded admission with typed
busy/deadline rejection lands in `generation.*` instead of `serving.*`.

Metric names:

- ``generation.requests_total``       accepted generation requests
- ``generation.rejected_busy``        admission rejections (queue full)
- ``generation.rejected_deadline``    deadline-expired rejections
- ``generation.queue_depth``          gauge: requests waiting
- ``generation.steps_total``          engine decode steps
- ``generation.prefill_tokens_total`` prompt tokens prefilled
- ``generation.tokens_total``         tokens generated (sampled)
- ``generation.finished_total``       sequences completed
- ``generation.preempted_total``      sequences preempted (pages reclaimed)
- ``generation.kv_bytes_moved``       KV bytes across the host<->device
                                      boundary (host pools: the whole pool
                                      per layer per step; DeviceKVPool:
                                      just the appended token payload —
                                      the O(pool) vs O(tokens) A/B)
- ``generation.prefill_compiles_total``  batched-prefill executables built
                                      (== (batch, length) buckets touched)
- ``generation.prefill_cache_hits`` / ``_misses``  prefill bucket cache
- ``generation.decode_dispatches_per_step``  gauge: engine-issued device
                                      program invocations in the last
                                      decode step (fused: exactly 1;
                                      eager: one scatter + one attention
                                      per layer on the device backend —
                                      model-internal eager ops are not
                                      visible to the engine, so the eager
                                      number is a lower bound)
- ``generation.decode_host_syncs_per_step``  gauge: blocking device->host
                                      fetches in the last decode step
                                      (fused: the single logits/token
                                      fetch; host pools add a K/V
                                      download per layer)
- ``generation.decode_compiles_total``  fused decode executables built
                                      (== (batch, pages, greedy) bucket
                                      signatures touched)
- ``generation.decode_cache_hits`` / ``_misses``  fused bucket cache
- ``generation.prefill_chunks_total``  chunked-prefill dispatches (one
                                      chunk of one prompt each; the
                                      ragged step counts its packed
                                      chunk here too)
- ``generation.step_rows_useful``     real token rows the step's fused
                                      dispatches computed (decode rows
                                      + prefill-chunk rows)
- ``generation.step_rows_dispatched``  total row slots those dispatches
                                      carried (legacy: decode batch
                                      bucket + the fixed chunk axis;
                                      ragged: the fixed packed axis) —
                                      the denominator of the padding
                                      reclaim A/B
- ``generation.step_row_utilization``  gauge: last step's useful /
                                      dispatched rows (0..1)
- ``generation.padded_token_waste``   rows of MASKED DUMMY WORK: rows
                                      dispatched as (part of) a
                                      sequence that are pure padding —
                                      legacy decode's fabricated dummy
                                      sequences (full transformer +
                                      zero-length attention + sampled
                                      logits per row) and the legacy
                                      chunk's masked token-axis padding
                                      inside a real sequence's
                                      dispatch.  The RAGGED step has
                                      none by construction (descriptors
                                      cover exactly the packed rows;
                                      slots past them belong to no
                                      sequence: no pool write, no
                                      attention, no logits row — their
                                      inert fraction is what
                                      step_row_utilization reports)
- ``generation.decode_compiles_prewarm``  fused decode executables built
                                      by the mid-prefill pre-warm path
                                      (the `prewarm` tag on
                                      decode_compiles_total)
- ``generation.slot_occupancy_pct``   gauge: active / decode slots
- ``generation.page_utilization_pct`` gauge: pool pages in use
- ``generation.prefix_cache_hit_tokens``  prompt tokens served from the
                                      prefix cache (aliased pages) at
                                      admission instead of re-prefilled
- ``generation.prefix_cache_hit_rate``  gauge: cumulative hit tokens /
                                      prompt tokens looked up (0..1)
- ``generation.shared_pages``         gauge: physical pages aliased by
                                      >1 page table right now (N users
                                      of one system prompt, ONE copy)
- ``generation.cow_copies``           copy-on-write page copies (first
                                      divergent append into a shared
                                      page)
- ``generation.prefix_evictions``     cached refcount-0 pages evicted
                                      back to the free list under pool
                                      pressure (LRU, before preemption)
- ``generation.prefix_pages_registered``  pages newly indexed into the
                                      prefix trie — prompt pages at
                                      prefill completion plus the
                                      decode-tail pages indexed at
                                      retire (generated tokens a
                                      multi-turn client re-sends)
- ``generation.kernel_path``          gauge (string): which attention
                                      implementation the engine's step
                                      mode dispatches —
                                      ``"<mode>:pallas"`` or
                                      ``"<mode>:jnp-reference"`` where
                                      mode is ragged/fused/eager.  Set
                                      at engine build (the dispatch
                                      path cannot change after), so a
                                      silent fallback to the reference
                                      path is visible in every stats
                                      snapshot instead of inferred
                                      from timings
- ``generation.kv_pool_layout``       gauge (string): how the KV pool
                                      is stored — ``"kernel"``
                                      ([H, P, page, D], read by the
                                      Pallas kernels as stored),
                                      ``"token"`` ([P, page, H, D]),
                                      ``"latent"`` (a latent cache's
                                      [P, page, lanes]) or
                                      ``"kv_rows"`` (grouped-query
                                      heads' keys then values in one
                                      [P, page, lanes] row).  Stamped
                                      at engine build beside
                                      kernel_path
- ``generation.kv_layer_groups``      gauge (string, JSON): the kinds
                                      of layer the pool serves and how
                                      many of each, e.g. ``{"full": 2,
                                      "window": 6}``: one page table
                                      and free list a kind
- ``generation.kv_window_tokens``     gauge: keys a window layer keeps
                                      (0: every layer keeps all)
- ``generation.kv_window_pages_reserved`` / ``_released``
                                      window-group pages taken by
                                      reservations, and given back
                                      BEHIND the window while their
                                      sequence lives (what a finished
                                      sequence returns is not counted)
- ``generation.step_score_blocks``    visible (query tile, page) pairs
                                      per head the TILED ragged kernel
                                      multiplies (the query-axis tiling
                                      skip rule, mirrored host-side per
                                      dispatch — ops/pallas
                                      ragged_score_blocks, under the
                                      query tile of the kernel that
                                      runs).  Emitted
                                      ONLY when the kernel path
                                      dispatched; 0 on the jnp
                                      reference, which runs no tiled
                                      kernel to proxy
- ``generation.step_score_blocks_untiled``  what the UNTILED kernel
                                      (full packed token axis per live
                                      (descriptor, page) cell) would
                                      have computed on the same
                                      dispatches, in the same tile
                                      units — tiled < untiled is the
                                      measured out-of-span skip
- ``generation.step_grid_cells``      page SLOTS per head the ragged
                                      kernel's grid WALKED, dispatch by
                                      dispatch: its grid is a compacted
                                      list of the live (descriptor,
                                      page group, query tile) cells
                                      under a traced bound, a cell
                                      holds G pages (ops/pallas
                                      ragged_cell_shape x
                                      ragged_grid_cells on a per-head
                                      pool, latent_pages_per_cell x
                                      latent_grid_cells on a latent
                                      one), a descriptor's last group
                                      is padded, and so
                                      step_score_blocks over this
                                      reads how full the groups are
                                      (same units, same 0 on the jnp
                                      reference)
- ``generation.ragged_pages_per_cell`` / ``_heads_per_cell``
                                      gauges: G and Hb, the pages and
                                      the heads one grid step of the
                                      per-head ragged kernel holds
                                      (ops/pallas ragged_cell_shape at
                                      the engine's shapes; a pages
                                      bucket under G holds itself).
                                      Stamped at engine build beside
                                      latent_pages_per_cell, 0 unless
                                      that kernel runs
- ``generation.latent_pages_per_cell``  gauge: pages one grid step of
                                      the latent kernel holds (ops/
                                      pallas latent_pages_per_cell at
                                      the pool's page size; a pages
                                      bucket under it holds itself).
                                      Stamped at engine build beside
                                      kv_pool_layout, 0 unless a
                                      latent pool is read by the
                                      kernel: which kernel a
                                      snapshot's numbers belong to
- ``generation.kv_quant_dtype``       gauge (string): the pool storage
                                      dtype ("float32" / "bfloat16" /
                                      "int8") stamped at engine build —
                                      every snapshot says what
                                      precision its numbers were
                                      measured at
- ``generation.moe_assignments_total`` / ``_assignments_max_expert`` /
  ``_experts_touched``                (token, expert) pairs the expert
                                      layers computed; the busiest
                                      expert's count a layer, summed;
                                      experts with any row a layer,
                                      summed — counted inside the step
                                      by a model with `step_counters`
- ``generation.kv_token_bytes``       gauge: bytes one cached token
                                      costs over all layers (a row
                                      pool stamps it at engine build)
- ``generation.kv_scale_bytes``       int8 scale bytes in flight
                                      (writes, exports, imports, COW)
                                      — a SUBSET of kv_bytes_moved
                                      (scales are folded into the
                                      total: bytes in flight are bytes
                                      in flight), split out so the
                                      quantization overhead is visible
- ``generation.collective_quantized``  gauge: 1 when the EQuARX-style
                                      quantized ring actually carries
                                      the two per-layer allreduces, 0
                                      otherwise — a requested-but-
                                      inactive flag (no mesh, tp == 1)
                                      reads 0, so a silent fp32
                                      fallback is a stats fact
                                      (mirrors kernel_path)
- ``generation.spec_mode``            gauge (string): the speculative-
                                      decoding proposer the engine
                                      runs ("off" / "ngram"), stamped
                                      at engine build like kernel_path
                                      — a silent fallback to
                                      non-speculative decode is a
                                      stats fact, never an inference
                                      from rates
- ``generation.spec_proposed_tokens``  draft tokens the proposer packed
                                      into ragged verify rows
- ``generation.spec_accepted_tokens``  drafts the on-device accept
                                      epilogue verified (each one a
                                      token retired WITHOUT its own
                                      dispatch)
- ``generation.spec_acceptance_rate``  gauge: cumulative accepted /
                                      proposed (0..1)
- ``generation.spec_rewind_tokens``   rejected drafts rewound out of
                                      the KV cache (truncate) — the
                                      wasted-work counter the
                                      overhead-bound gen_bench cell
                                      watches
- ``generation.spec_draft_rows``      speculative VERIFY rows
                                      dispatched (one per drafting
                                      sequence per step) — the
                                      denominator of the true mean
                                      accepted length,
                                      accepted / draft_rows
- ``generation.mesh_devices``         gauge: tensor-parallel degree of
                                      the engine's mesh (1 unsharded)
- ``generation.collective_bytes_per_step``  gauge: estimated on-wire
                                      allreduce bytes of the last
                                      sharded dispatch (2 allreduces
                                      per layer over the [rows,
                                      d_model] fp32 activation x the
                                      ring factor 2(N-1)/N; 0 when
                                      unsharded) — the profile hook the
                                      EQuARX-style quantized-collective
                                      follow-on is measured against
- ``generation.loop_steps``           gauge: N of the host-free decode
                                      loop (fused.LoopedRaggedStep) —
                                      1 means the per-step path,
                                      stamped at engine build like
                                      kernel_path, so every snapshot
                                      says how many decode steps each
                                      dispatch fused
- ``generation.decode_host_fetches_per_token``  gauge: cumulative host
                                      fetches / tokens on the loop
                                      path — the loop's acceptance
                                      number (<= 1/N on a decode-only
                                      batch; the per-step path pays
                                      ~1)
- ``generation.steps_overlapped``      ragged steps enqueued BEHIND a step
                                      whose tokens the host had not read
                                      (engine._step_ragged's pipeline at
                                      depth 1): over the ragged steps
                                      dispatched, how often the host's
                                      work on a step hid behind the
                                      device
- ``generation.pipeline_drains`` / ``.stochastic`` / ``.speculation`` /
  ``.preempt`` / ``.handoff`` / ``.api``  times that pipeline ran at
                                      depth 0 for a cause other than
                                      want of work, in all and by cause:
                                      a step with a stochastic sampler
                                      (its token is drawn on the host),
                                      speculation or the host-free loop
                                      on, a plan that would preempt, a
                                      hand-off engine, a call that needs
                                      the engine settled (cancel,
                                      evacuate, import, export,
                                      shutdown) with a step in flight
- ``generation.overlap_rows_discarded``  rows of a step in flight whose
                                      sequence was gone when the step
                                      was read (a stop token or
                                      sequence, a deadline, a cancel):
                                      dispatched in vain, never applied
- ``generation.loop_early_exits``     loop dispatches that exited
                                      before iteration N because every
                                      live row had finished (the
                                      on-device done-mask early exit)
- ``generation.loop_wasted_steps``    loop iterations rows sat already-
                                      finished while the rest of the
                                      batch kept going — the
                                      latency-vs-waste cost of big N
                                      the gen_bench loop A/B watches
"""
import json

from ..profiler.monitor import StatRegistry

PREFIX = "generation."

REQUESTS_TOTAL = PREFIX + "requests_total"
REJECTED_BUSY = PREFIX + "rejected_busy"
REJECTED_DEADLINE = PREFIX + "rejected_deadline"
QUEUE_DEPTH = PREFIX + "queue_depth"
STEPS_TOTAL = PREFIX + "steps_total"
PREFILL_TOKENS_TOTAL = PREFIX + "prefill_tokens_total"
TOKENS_TOTAL = PREFIX + "tokens_total"
FINISHED_TOTAL = PREFIX + "finished_total"
PREEMPTED_TOTAL = PREFIX + "preempted_total"
KV_BYTES_MOVED = PREFIX + "kv_bytes_moved"
PREFILL_COMPILES_TOTAL = PREFIX + "prefill_compiles_total"
PREFILL_CACHE_HITS = PREFIX + "prefill_cache_hits"
PREFILL_CACHE_MISSES = PREFIX + "prefill_cache_misses"
DECODE_DISPATCHES_PER_STEP = PREFIX + "decode_dispatches_per_step"
DECODE_HOST_SYNCS_PER_STEP = PREFIX + "decode_host_syncs_per_step"
DECODE_COMPILES_TOTAL = PREFIX + "decode_compiles_total"
DECODE_CACHE_HITS = PREFIX + "decode_cache_hits"
DECODE_CACHE_MISSES = PREFIX + "decode_cache_misses"
PREFILL_CHUNKS_TOTAL = PREFIX + "prefill_chunks_total"
STEP_ROWS_USEFUL = PREFIX + "step_rows_useful"
STEP_ROWS_DISPATCHED = PREFIX + "step_rows_dispatched"
STEP_ROW_UTILIZATION = PREFIX + "step_row_utilization"
PADDED_TOKEN_WASTE = PREFIX + "padded_token_waste"
DECODE_COMPILES_PREWARM = PREFIX + "decode_compiles_prewarm"
SLOT_OCCUPANCY_PCT = PREFIX + "slot_occupancy_pct"
PAGE_UTILIZATION_PCT = PREFIX + "page_utilization_pct"
KERNEL_PATH = PREFIX + "kernel_path"
KV_POOL_LAYOUT = PREFIX + "kv_pool_layout"
STEP_SCORE_BLOCKS = PREFIX + "step_score_blocks"
STEP_SCORE_BLOCKS_UNTILED = PREFIX + "step_score_blocks_untiled"
STEP_GRID_CELLS = PREFIX + "step_grid_cells"
SPEC_MODE = PREFIX + "spec_mode"
SPEC_PROPOSED_TOKENS = PREFIX + "spec_proposed_tokens"
SPEC_ACCEPTED_TOKENS = PREFIX + "spec_accepted_tokens"
SPEC_ACCEPTANCE_RATE = PREFIX + "spec_acceptance_rate"
SPEC_REWIND_TOKENS = PREFIX + "spec_rewind_tokens"
SPEC_DRAFT_ROWS = PREFIX + "spec_draft_rows"
MESH_DEVICES = PREFIX + "mesh_devices"
COLLECTIVE_BYTES_PER_STEP = PREFIX + "collective_bytes_per_step"
KV_QUANT_DTYPE = PREFIX + "kv_quant_dtype"
KV_TOKEN_BYTES = PREFIX + "kv_token_bytes"
KV_STATE_BYTES_A_SLOT = PREFIX + "kv_state_bytes_a_slot"
KV_LAYER_GROUPS = PREFIX + "kv_layer_groups"
KV_WINDOW_TOKENS = PREFIX + "kv_window_tokens"
KV_WINDOW_PAGES_RESERVED = PREFIX + "kv_window_pages_reserved"
KV_WINDOW_PAGES_RELEASED = PREFIX + "kv_window_pages_released"
LATENT_PAGES_PER_CELL = PREFIX + "latent_pages_per_cell"
RAGGED_PAGES_PER_CELL = PREFIX + "ragged_pages_per_cell"
RAGGED_HEADS_PER_CELL = PREFIX + "ragged_heads_per_cell"
KV_SCALE_BYTES = PREFIX + "kv_scale_bytes"
COLLECTIVE_QUANTIZED = PREFIX + "collective_quantized"
PREFIX_CACHE_HIT_TOKENS = PREFIX + "prefix_cache_hit_tokens"
PREFIX_CACHE_HIT_RATE = PREFIX + "prefix_cache_hit_rate"
SHARED_PAGES = PREFIX + "shared_pages"
COW_COPIES = PREFIX + "cow_copies"
PREFIX_EVICTIONS = PREFIX + "prefix_evictions"
PREFIX_PAGES_REGISTERED = PREFIX + "prefix_pages_registered"
LOOP_STEPS = PREFIX + "loop_steps"
DECODE_HOST_FETCHES_PER_TOKEN = PREFIX + "decode_host_fetches_per_token"
LOOP_EARLY_EXITS = PREFIX + "loop_early_exits"
LOOP_WASTED_STEPS = PREFIX + "loop_wasted_steps"
STEPS_OVERLAPPED = PREFIX + "steps_overlapped"
PIPELINE_DRAINS = PREFIX + "pipeline_drains"
OVERLAP_ROWS_DISCARDED = PREFIX + "overlap_rows_discarded"


class GenerationMetrics:
    """Writes generation.* to the process StatRegistry (STAT_ADD
    parity: concurrent engines aggregate)."""

    def __init__(self, registry=None):
        self._reg = registry or StatRegistry.instance()
        # prefix-cache hit-rate accumulators (per-engine: the gauge is
        # this engine's cumulative warm fraction, not a fleet mix)
        self._prefix_hit_cum = 0
        self._prefix_lookup_cum = 0
        # speculative-decoding acceptance accumulators (per-engine,
        # like the prefix hit rate)
        self._spec_proposed_cum = 0
        self._spec_accepted_cum = 0
        # host-free-loop fetch-rate accumulators (per-engine, same
        # pattern): the gauge is cumulative fetches / tokens on the
        # loop path
        self._loop_fetch_cum = 0
        self._loop_token_cum = 0

    def _stat(self, name):
        return self._reg.get_stat(name)

    # --- AdmissionQueue metrics interface ---
    def set_queue_depth(self, depth):
        self._stat(QUEUE_DEPTH).set(int(depth))

    def count_rejected_busy(self):
        self._stat(REJECTED_BUSY).increase()

    def count_rejected_deadline(self, n=1):
        self._stat(REJECTED_DEADLINE).increase(n)

    # --- counters ---
    def count_request(self):
        self._stat(REQUESTS_TOTAL).increase()

    def count_prefill(self, tokens):
        self._stat(PREFILL_TOKENS_TOTAL).increase(int(tokens))

    def count_finished(self):
        self._stat(FINISHED_TOTAL).increase()

    def count_token(self):
        """One sampled-and-emitted token (prefill's first token and
        decode tokens alike)."""
        self._stat(TOKENS_TOTAL).increase()

    def count_preempted(self, n=1):
        self._stat(PREEMPTED_TOTAL).increase(n)

    def count_kv_bytes(self, n):
        """KV bytes the cache moved (or would move) host<->device this
        step — the engine drains PagedKVCache.take_bytes_moved() here."""
        if n:
            self._stat(KV_BYTES_MOVED).increase(int(n))

    # --- CompiledModelCache metrics interface (prefill bucket cache) ---
    def count_cache(self, hit):
        self._stat(PREFILL_CACHE_HITS if hit
                   else PREFILL_CACHE_MISSES).increase()

    def count_compile(self):
        self._stat(PREFILL_COMPILES_TOTAL).increase()

    def count_chunk(self):
        """One chunked-prefill dispatch (a chunk of one prompt)."""
        self._stat(PREFILL_CHUNKS_TOTAL).increase()

    # --- prefix cache ---
    def count_prefix_lookup(self, hit_tokens, prompt_tokens):
        """One admission-time prefix lookup over a `prompt_tokens`-long
        token list, of which `hit_tokens` were served by aliasing
        cached pages (0 = cold).  Maintains the cumulative hit-rate
        gauge alongside the hit-token counter."""
        if hit_tokens:
            self._stat(PREFIX_CACHE_HIT_TOKENS).increase(int(hit_tokens))
        self._prefix_hit_cum += int(hit_tokens)
        self._prefix_lookup_cum += int(prompt_tokens)
        if self._prefix_lookup_cum:
            self._stat(PREFIX_CACHE_HIT_RATE).set(
                round(self._prefix_hit_cum / self._prefix_lookup_cum, 3))

    def observe_shared_pages(self, n):
        """Gauge: physical pages currently aliased by more than one
        page table (the engine samples the cache every step)."""
        self._stat(SHARED_PAGES).set(int(n))

    def count_cow(self, n=1):
        # touch the stat even at 0 so every snapshot carries the key
        stat = self._stat(COW_COPIES)
        if n:
            stat.increase(int(n))

    def count_prefix_evictions(self, n=1):
        stat = self._stat(PREFIX_EVICTIONS)
        if n:
            stat.increase(int(n))

    def count_prefix_registered(self, n):
        """Pages newly indexed into the prefix trie (prompt pages at
        prefill completion, decode-tail pages at retire)."""
        stat = self._stat(PREFIX_PAGES_REGISTERED)
        if n:
            stat.increase(int(n))

    def count_decode_prewarm(self):
        """One fused-decode executable compiled by the PRE-WARM path
        (built while its sequence was still mid-prefill, so the first
        decode after prefill pays no retrace).  The compile also lands
        in decode_compiles_total through the normal cache metrics; this
        counter is the `prewarm` tag splitting it out."""
        self._stat(DECODE_COMPILES_PREWARM).increase()

    # --- fused decode bucket cache (CompiledModelCache interface via
    # the DecodeCacheMetrics adapter below) ---
    def count_decode_cache(self, hit):
        self._stat(DECODE_CACHE_HITS if hit
                   else DECODE_CACHE_MISSES).increase()

    def count_decode_compile(self):
        self._stat(DECODE_COMPILES_TOTAL).increase()

    # --- per-step observation ---
    def observe_decode_step(self, dispatches, host_syncs):
        """Per-step dispatch/sync gauges — the ragged path's acceptance
        numbers (1 and <=1) and the eager/fused A/B baselines."""
        self._stat(DECODE_DISPATCHES_PER_STEP).set(int(dispatches))
        self._stat(DECODE_HOST_SYNCS_PER_STEP).set(int(host_syncs))

    def count_step_extra_dispatches(self, n):
        """Fold extra device dispatches the step issued OUTSIDE the
        decode call into the per-step gauge — the legacy chunked step's
        jitted chunk dispatch, so the legacy-vs-ragged
        dispatches-per-step A/B reads its true 2 vs 1 (the decode paths
        SET the gauge; this adds on top, called after them)."""
        stat = self._stat(DECODE_DISPATCHES_PER_STEP)
        stat.set(int(stat.get()) + int(n))

    def set_kernel_path(self, mode, use_kernel):
        """Gauge (string): ``"<mode>:pallas"`` / ``"<mode>:jnp-reference"``
        — the attention implementation the engine's step mode
        dispatches, stamped once at engine build so every snapshot says
        which path produced its numbers."""
        path = "pallas" if use_kernel else "jnp-reference"
        self._stat(KERNEL_PATH).set(f"{mode}:{path}")

    def set_kv_pool_layout(self, layout):
        """Gauge (string): ``"kernel"`` / ``"token"`` / ``"latent"`` /
        ``"kv_rows"`` — the layout the KV pool is stored in, stamped
        once at engine build beside kernel_path, so every snapshot says
        which layout produced its numbers."""
        self._stat(KV_POOL_LAYOUT).set(str(layout))

    def set_kv_layer_groups(self, groups, window_tokens):
        """Gauges, stamped at engine build: ``{kind: layers}`` as JSON
        and the window the window layers keep (0 without any)."""
        self._stat(KV_LAYER_GROUPS).set(json.dumps(groups, sort_keys=True))
        self._stat(KV_WINDOW_TOKENS).set(int(window_tokens))

    def count_window_pages(self, reserved, released):
        """Window-group pages reserved, and released behind a live
        sequence's window, since the last step's accounting."""
        if reserved:
            self._stat(KV_WINDOW_PAGES_RESERVED).increase(int(reserved))
        if released:
            self._stat(KV_WINDOW_PAGES_RELEASED).increase(int(released))

    def count_score_blocks(self, tiled, untiled, grid_cells):
        """FLOP-proxy accounting for one ragged dispatch: score blocks
        the query-TILED kernel computes vs what the untiled kernel
        would have, and the page slots its grid walked (same units;
        ops/pallas ragged_score_blocks, ragged_grid_cells).  All 0 on
        the jnp reference path."""
        if grid_cells:
            self._stat(STEP_SCORE_BLOCKS).increase(int(tiled))
            self._stat(STEP_SCORE_BLOCKS_UNTILED).increase(int(untiled))
            self._stat(STEP_GRID_CELLS).increase(int(grid_cells))

    def count_model_step(self, names, values):
        """What a model counted inside its steps (`step_counters`, e.g.
        the experts' ``generation.moe_assignments_total``,
        ``_assignments_max_expert`` — the busiest expert's count a
        layer, summed — and ``_experts_touched``)."""
        for name, value in zip(names, values):
            self._stat(name).increase(int(value))

    def set_kv_token_bytes(self, n):
        """Gauge: bytes one cached token costs over all layers, stamped
        at engine build by a cache that knows it (a latent pool)."""
        self._stat(KV_TOKEN_BYTES).set(int(n))

    def set_kv_state_bytes_a_slot(self, n):
        """Gauge: bytes one decode slot costs over all state layers
        (their tails and recurrent states), stamped at engine build by
        a cache that has such layers."""
        self._stat(KV_STATE_BYTES_A_SLOT).set(int(n))

    def set_model_gauges(self, gauges):
        """Gauges a model states of itself (`build_gauges()`, e.g. the
        experts a layer holds of its router's width), stamped at engine
        build under ``generation.<name>``."""
        for name, value in gauges.items():
            self._stat(PREFIX + name).set(int(value))

    def set_latent_pages_per_cell(self, n):
        """Gauge: pages a grid step of the latent kernel holds, stamped
        at engine build (0 by an engine that runs no latent kernel)."""
        self._stat(LATENT_PAGES_PER_CELL).set(int(n))

    def set_ragged_cell(self, pages, heads):
        """Gauges: pages and heads a grid step of the per-head ragged
        kernel holds, stamped at engine build (0 by an engine that
        runs another kernel, or none)."""
        self._stat(RAGGED_PAGES_PER_CELL).set(int(pages))
        self._stat(RAGGED_HEADS_PER_CELL).set(int(heads))

    def set_kv_quant_dtype(self, dtype_name):
        """Gauge (string): the KV pool storage dtype, stamped once at
        engine build (the pool cannot change precision after)."""
        self._stat(KV_QUANT_DTYPE).set(str(dtype_name))

    def count_kv_scale_bytes(self, n):
        """int8 scale traffic drained from the cache each step (already
        folded into kv_bytes_moved; this is the split-out view).
        Touches the stat even at 0 so quantized engines always carry
        the key."""
        stat = self._stat(KV_SCALE_BYTES)
        if n:
            stat.increase(int(n))

    def set_collective_quantized(self, active):
        """Gauge: whether the quantized ring ACTUALLY carries the
        sharded step's allreduces (flag requested AND tp > 1) — set at
        engine build like kernel_path, so an fp32 fallback is visible
        in every snapshot."""
        self._stat(COLLECTIVE_QUANTIZED).set(1 if active else 0)

    def set_spec_mode(self, mode):
        """Gauge (string): the speculative-decoding proposer this
        engine dispatches ("off" / "ngram"), stamped once at engine
        build — the kernel_path pattern.  Touches every spec counter
        too, so the schema is complete from the first snapshot:
        spec_acceptance_rate == 0 is a statement, not a gap."""
        self._stat(SPEC_MODE).set(str(mode))
        self._stat(SPEC_PROPOSED_TOKENS)
        self._stat(SPEC_ACCEPTED_TOKENS)
        self._stat(SPEC_REWIND_TOKENS)
        self._stat(SPEC_DRAFT_ROWS)
        self._stat(SPEC_ACCEPTANCE_RATE).set(0.0)

    def set_loop_steps(self, n):
        """Gauge: N of the host-free decode loop (1 = the per-step
        path), stamped once at engine build — the kernel_path pattern.
        Touches every loop counter too, so the schema is complete from
        the first snapshot: decode_host_fetches_per_token == 0 on a
        loop-off engine is a statement, not a gap."""
        self._stat(LOOP_STEPS).set(int(n))
        self._stat(LOOP_EARLY_EXITS)
        self._stat(LOOP_WASTED_STEPS)
        self._stat(DECODE_HOST_FETCHES_PER_TOKEN).set(0.0)

    def observe_loop(self, tokens, fetches, early_exit, wasted):
        """One host-free loop dispatch retired: `tokens` emitted across
        the batch for `fetches` host fetches (1 by construction),
        `early_exit` when the done masks ended the loop before
        iteration N, `wasted` the already-finished row-iterations the
        batch stragglers cost.  Maintains the cumulative
        fetches-per-token gauge — the loop's <= 1/N acceptance
        number."""
        self._loop_fetch_cum += int(fetches)
        self._loop_token_cum += int(tokens)
        if self._loop_token_cum:
            self._stat(DECODE_HOST_FETCHES_PER_TOKEN).set(
                round(self._loop_fetch_cum / self._loop_token_cum, 4))
        if early_exit:
            self._stat(LOOP_EARLY_EXITS).increase()
        if wasted:
            self._stat(LOOP_WASTED_STEPS).increase(int(wasted))

    def count_spec(self, proposed, accepted, rewound):
        """One speculative row's verify outcome: `proposed` drafts
        packed, `accepted` verified, `rewound` truncated back out of
        the cache.  Maintains the cumulative acceptance-rate gauge and
        the draft-row count (the mean-accepted-length denominator)."""
        if proposed:
            self._stat(SPEC_PROPOSED_TOKENS).increase(int(proposed))
            self._stat(SPEC_DRAFT_ROWS).increase()
        if accepted:
            self._stat(SPEC_ACCEPTED_TOKENS).increase(int(accepted))
        if rewound:
            self._stat(SPEC_REWIND_TOKENS).increase(int(rewound))
        self._spec_proposed_cum += int(proposed)
        self._spec_accepted_cum += int(accepted)
        if self._spec_proposed_cum:
            self._stat(SPEC_ACCEPTANCE_RATE).set(
                round(self._spec_accepted_cum / self._spec_proposed_cum,
                      3))

    def set_mesh_devices(self, n):
        """Gauge: the engine's tensor-parallel degree (mesh axis size;
        1 when unsharded) — set once at engine construction so every
        stats_snapshot carries the topology its numbers were measured
        on."""
        self._stat(MESH_DEVICES).set(int(n))

    def observe_collective_bytes(self, n):
        """Gauge: estimated allreduce bytes of the last sharded
        dispatch (fused decode step or jitted prefill chunk) —
        fused._collective_bytes_estimate documents the formula.  0 on
        every unsharded path."""
        self._stat(COLLECTIVE_BYTES_PER_STEP).set(int(n))

    def observe_step_rows(self, useful, dispatched, waste):
        """Row accounting for one engine step's fused dispatches:
        `useful` real token rows out of `dispatched` row slots, of
        which `waste` rows were MASKED DUMMY WORK (fabricated dummy
        sequences / in-sequence padding — see the module docstring;
        the ragged step's structural zero).  Touches every stat so the
        schema is complete from the first snapshot — padded_token_waste
        == 0 is a statement, not a gap."""
        self._stat(STEP_ROWS_USEFUL).increase(int(useful))
        self._stat(STEP_ROWS_DISPATCHED).increase(int(dispatched))
        stat = self._stat(PADDED_TOKEN_WASTE)
        if waste:
            stat.increase(int(waste))
        if dispatched:
            self._stat(STEP_ROW_UTILIZATION).set(
                round(useful / dispatched, 3))

    def count_step_overlapped(self):
        """One ragged step enqueued behind a step still in flight."""
        self._stat(STEPS_OVERLAPPED).increase()

    def count_pipeline_drain(self, reason):
        """The ragged step's pipeline emptied for `reason`
        (``stochastic`` / ``speculation`` / ``preempt`` / ``handoff`` /
        ``api``) rather than for want of work."""
        self._stat(PIPELINE_DRAINS).increase()
        self._stat(PIPELINE_DRAINS + "." + reason).increase()

    def count_overlap_rows_discarded(self, n):
        """Rows of a step in flight dropped at its emit: their sequence
        had been retired meanwhile."""
        if n:
            self._stat(OVERLAP_ROWS_DISCARDED).increase(int(n))

    def observe_step(self):
        """One engine step that sampled at least one token (the token
        counter itself is kept by count_token at the sampling site)."""
        self._stat(STEPS_TOTAL).increase()

    def observe_occupancy(self, active, slots, page_utilization):
        if slots:
            self._stat(SLOT_OCCUPANCY_PCT).set(
                round(100.0 * active / slots, 1))
        self._stat(PAGE_UTILIZATION_PCT).set(
            round(100.0 * page_utilization, 1))

    # --- reads ---
    def snapshot(self):
        """All generation.* stats currently in the registry."""
        return {k: v for k, v in self._reg.stats().items()
                if k.startswith(PREFIX)}


class DecodeCacheMetrics:
    """Adapter giving the fused decode step's CompiledModelCache the
    metrics interface it expects (`count_cache` / `count_compile`) while
    landing the counts under generation.decode_* instead of the prefill
    names the GenerationMetrics methods of those names write."""

    def __init__(self, generation_metrics):
        self._gm = generation_metrics

    def count_cache(self, hit):
        self._gm.count_decode_cache(hit)

    def count_compile(self):
        self._gm.count_decode_compile()

