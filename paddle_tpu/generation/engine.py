"""GenerationEngine: paged-KV continuous-batching autoregressive decode.

Composes the subsystem end to end::

    submit(prompt) -> AdmissionQueue -> scheduler slots -> PREFILL (dense
       <- GenerationHandle (stream)                        causal, KV -> pages)
             ^                                          -> DECODE steps
             |   token-by-token                            (paged attention,
             +---------------------------------------------sample, stream)

The model is anything implementing the decode protocol below; the engine
owns the KV pages, the schedule, sampling, and metrics.  Greedy decode
through this engine is TOKEN-IDENTICAL to naive sequential full-recompute
generation — continuous batching and paging change the cost of a token,
never its value (the oracle tests/test_generation.py enforces).

Model protocol (duck-typed)::

    model.num_layers, model.num_heads, model.head_dim, model.vocab_size
    model.prefill(tokens[T])  -> (last_logits [V], k [L,T,H,D], v [L,T,H,D])
    model.prefill_batch(tokens[B,T], lengths[B])          # optional
        -> (last_logits [B,V], k [B,L,T,H,D], v [B,L,T,H,D])
        # enables bucketed batched prefill: prompts are length-padded to
        # a ShapeBucketer menu so prefill compiles once per bucket;
        # models without it prefill one sequence at a time
    model.decode(tokens[B], positions[B], attend) -> logits [B, V]
        # calls, per layer:  attend(layer, q[B,H,D], k[B,H,D], v[B,H,D])
        #                      -> attention output [B,H,D]
        # the engine's attend() appends k/v to the paged cache and runs
        # paged decode attention over each sequence's page table
    model.decode_params() -> pytree                       # optional
    model.decode_step_fn(page_size, num_pages, use_kernel=...,
                         pool_layout=..., greedy=...) -> pure fn
        # optional pair enabling the FUSED decode path (fused.py): the
        # fn runs the WHOLE decode step — embed, every layer's paged
        # scatter-append + attention, final logits — as one traceable
        # body over (params, tokens, positions, k_pools, v_pools,
        # page_tables, lens), jitted with the pools donated and
        # dispatched ONCE per step; rows with lens == 0 are padding and
        # must never write a pool page (sentinel + mode="drop")
    model.prefill_chunk(tokens[n], start, attend) -> last_logits [V]
        # optional, enables CHUNKED prefill (eager): tokens are the
        # prompt slice at global positions start..start+n-1; per layer
        # attend(layer, q[n,H,D], k[n,H,D], v[n,H,D]) -> [n,H,D]
        # appends the chunk's K/V to the paged cache and runs causal
        # attention over prefix + chunk
    model.prefill_chunk_fn(page_size, num_pages, use_kernel=...,
                           pool_layout=...) -> pure fn      # optional
        # the jitted chunk variant (fused.ChunkedPrefillStep): the fn
        # runs one whole chunk — embed, per-layer donated scatter of
        # the chunk's K/V, paged prefix+chunk attention, last-position
        # logits — over (params, tokens[C], start, length, k_pools,
        # v_pools, page_table); rows >= length are bucket padding
        # (sentinel + mode="drop", logits never read)

Overload behavior is inherited from serving: a full queue raises
ServerBusyError at submit, lapsed deadlines resolve handles with
DeadlineExceededError, and page exhaustion preempts the youngest
sequences (recompute-style) before ever failing a request.
"""
import math
import queue
import threading
import time

import concurrent.futures

import numpy as np

from ..profiler import RecordEvent
from ..serving.admission import RequestTooLargeError, ServingError
from ..serving.bucketing import CompiledModelCache, ShapeBucketer
from .decode_attention import paged_decode_attention
from .kv_cache import (DeviceKVPool, LatentRows, OutOfPagesError,
                       PagedKVCache, WindowPageGroup)
from .metrics import GenerationMetrics
from .sampling import SamplingParams, sample_token, sample_tokens_batch
from .scheduler import (ContinuousBatchingScheduler, GenerationRequest,
                        SequenceState)

# auto chunk size for chunked prefill on TPU (GenerationConfig
# .prefill_chunk_tokens=None): a multiple of 8 so the chunk-query axis
# is Mosaic-sublane-aligned for the Pallas chunk kernel
DEFAULT_PREFILL_CHUNK_TOKENS = 64


class GenerationConfig:
    """Engine knobs; defaults suit a small CPU demo (docs/GENERATION.md
    documents each).

    kv_backend: "host" (numpy pools, whole pool shipped per step),
        "device" (DeviceKVPool: HBM-resident pools, donated scatter
        appends, O(tokens) transfer per step), or None = auto (device
        on TPU, host elsewhere).
    kv_dtype: pool storage dtype — np.float32 (default), bfloat16
        (half the bytes, storage-rounding), or "int8"/np.int8:
        QUANTIZED pools with per-page per-head abs-max scales, half of
        bf16 again (~2x resident sequences per pool byte).  int8 is
        LOSSY: the acceptance contract shifts from bitwise identity
        vs the fp32 oracle to the quality gate (bounded max-logit
        drift + >=99% greedy-token agreement — generation/quality.py),
        while int8-vs-int8 runs stay strictly token-identical across
        engine paths, pool layouts, preemption, warm starts, and the
        mesh (docs/GENERATION.md "Quantized KV and collectives").
    quantized_collectives: EQuARX-style int8 allreduces — the sharded
        step's two per-layer Megatron allreduces run as an explicit
        quantize->psum->dequant ring (per-shard abs-max scales, placed
        exactly where the fp32 allreduces sit), cutting
        collective_bytes_per_step ~4x.  Lossy like int8 KV, gated by
        the same quality harness.  Inert without a mesh (tp == 1 has
        no collectives) — generation.collective_quantized says whether
        it is ACTUALLY on.
    max_prefill_batch: waiting requests admitted+prefilled together per
        step (batched prefill); 1 restores one-at-a-time prefill.
    prefill_length_buckets: padded-length menu for batched prefill
        (shared semantics with serving.ShapeBucketer); None = auto, a
        geometric menu covering every admissible prompt.
    jit_prefill: AOT-compile one prefill executable per (batch, length)
        bucket; None = auto (on TPU only — XLA fusion drifts floats at
        the ulp level, and the CPU tier-1 oracle demands bitwise token
        identity, so CPU defaults to the eager exact path; the bucket
        cache still bounds and counts shape signatures either way).
    decode: "eager" (per-layer attend callbacks, the exact oracle
        path), "fused" (FusedDecodeStep: the whole step as ONE jitted
        pool-donating dispatch, requires the device KV backend and a
        model with decode_step_fn), or None = auto — fused on TPU when
        the model supports it, eager elsewhere (same reasoning as
        jit_prefill: the CPU tier-1 oracle stays anchored on the
        bitwise-exact eager path).
    decode_batch_buckets: padded-batch menu for the fused decode step;
        None = auto (powers of two up to max_decode_slots).
    pool_layout: DeviceKVPool storage layout — "token"
        ([P, page_size, H, D], append-natural) or "kernel"
        ([H, P, page_size, D], what the Pallas decode kernel consumes:
        scatters write the kernel layout so the kernel path skips its
        per-call whole-pool transpose).  None = auto: the layout
        follows the reader — "kernel" for a per-head device pool read
        by the Pallas kernels (use_kernel resolved true), "token" for
        host pools, the jnp gather path, and a pool whose rows no
        in-place writer serves in kernel layout
        (ops.pallas.pool_scatter_in_place: other than float32 heads of
        128); a latent pool has its own layout either way.  "kernel"
        is device backend only.
    prefill_chunk_tokens: CHUNKED prefill — split every admitted prompt
        into fixed-size chunks of this many tokens and stream them in
        one chunk per engine step, interleaved with decode, instead of
        one monolithic (batch, length)-bucketed prefill call that
        blocks every decode slot for the whole prompt.  0 disables
        (full prefill); None = auto, mirroring the decode auto policy:
        chunked (DEFAULT_PREFILL_CHUNK_TOKENS) on TPU when the JITTED
        chunk path is available (device pools + model.prefill_chunk_fn
        + jit_prefill — the eager per-layer chunk loop would regress
        TTFT there, so it stays explicit opt-in), full prefill
        elsewhere — the CPU tier-1 oracle stays anchored on the
        one-shot path, and chunked-vs-full
        token identity is itself oracle-tested (greedy AND
        seeded-stochastic, incl. preemption re-prefill).  With
        reduced-precision pools (kv_dtype=bfloat16) the prefix is
        re-read at storage precision — like decode — so tokens may
        differ from one-shot prefill at the storage-rounding level.
    step_token_budget: the per-step token capacity — the RAGGED step's
        fixed packed token axis (decode rows + the step's chunk PACK
        fill exactly this many slots; the executable's token shape, so
        it never retraces).  None = auto: prefill_chunk_tokens +
        max_decode_slots (max_decode_slots alone when chunking is off),
        which always holds the full decode batch plus a whole chunk.
        The room left after the decode rows is PACKED with multiple
        prompts' chunks (scheduler.plan_pack, FIFO: the oldest
        prompt's full chunk first, then younger prompts' chunks into
        the leftover — short prompts stop queueing behind long ones
        for TTFT); with chunking on the budget must leave at least one
        prefill row past the decode batch so prompts cannot starve.
        The legacy chunked path packs by the same rule — one chunk
        dispatch per pack member plus the whole decode batch, every
        step; the old decode-owed stall dance died with the
        two-dispatch step it arbitrated (docs/GENERATION.md "Ragged
        mixed-batch step").
    prefill_pack: multi-prompt chunk packing (True, the default):
        each step's leftover token room after the oldest prompt's
        chunk is filled with MORE prompts' chunks (scheduler.plan_pack)
        so short prompts stop queueing behind long ones for TTFT.
        False restores one chunk per step — the ablation baseline the
        gen_bench packing A/B measures against.
    step_mode: "ragged" (RaggedStep: the decode batch AND the step's
        prefill chunk pack in ONE pool-donating mixed-batch
        dispatch — one executable per pages bucket TOTAL, no dummy
        decode rows), "legacy" (the FusedDecodeStep /
        ChunkedPrefillStep pair, or the eager path per `decode`), or
        None = auto — ragged on TPU when the model implements
        ragged_step_fn with device pools, legacy elsewhere (the CPU
        tier-1 oracle stays anchored on the eager legacy path;
        ragged-vs-legacy token identity is itself oracle-tested,
        tests/test_ragged_step.py).  step_mode="ragged" replaces the
        decode and jitted-chunk dispatch paths entirely, so it
        rejects an explicit `decode=` setting.
    mesh: a ``jax.sharding.Mesh`` (parallel.tp_mesh builds one) turning
        on TENSOR-PARALLEL sharded decode: KV pools, attention, and the
        per-layer QKV/MLP weights shard over the HEAD axis with
        NamedSharding, and each fused decode step stays ONE GSPMD
        dispatch whose collectives XLA inserts from the annotations
        (docs/GENERATION.md "Sharded decode").  Requires the device KV
        backend, the fused decode path (auto resolves both), and a
        model whose num_heads divides by the mesh axis.  The Pallas
        kernels are MESH-NATIVE: under a mesh, use_kernel runs each
        kernel as a shard_map over the head-sharded mesh (per-shard
        program = the same kernel on num_heads/tp heads over that
        shard's pool slice; the two Megatron allreduces stay
        XLA-placed), so the kernel path and the sharded path are no
        longer mutually exclusive.
    tp_axis: the mesh axis name to shard heads over; None = the mesh's
        first axis.  Only meaningful with `mesh`.
    spec_mode: SPECULATIVE DECODING through the ragged step — "ngram"
        runs the model-free prompt-lookup proposer
        (generation/speculation.py): per greedy decode row, the
        sequence's current n-gram suffix is matched against its own
        history (prompt + generated tail) and up to `spec_tokens`
        draft continuations pack into the row's ragged descriptor as
        ``[start, len = 1 + k, kv_len]`` — the pages bucket stays the
        ONLY executable axis, so the compile menu is unchanged.  The
        trace's accept/reject epilogue verifies every draft on device
        (per-position argmax vs the shifted draft ids) and the host
        fetches accepted counts + the bonus token in the step's single
        sync: an accepting row retires accepted + 1 tokens from ONE
        dispatch.  Rejected drafts rewind through
        ``PagedKVCache.truncate``.  Greedy speculative decode is
        TOKEN-IDENTICAL to non-speculative decode — by construction
        for float pools (the ragged attention's masked-softmax makes
        a verify row's logits a pure function of its position and
        visible bytes); int8 pools add one scale-pregrow caveat
        bounded by the PR 12 quality gate and pinned strict on the
        reference-model matrix (docs/GENERATION.md "Speculative
        decoding").  Non-greedy rows,
        mid-prefill rows, and proposer misses decode exactly as today
        in the same batch.  "off" / None disables (the tier-1 CPU
        oracle default).  Requires the ragged step (speculation rides
        its packed token axis); spec_mode="ngram" with step_mode unset
        resolves step_mode to "ragged".
    spec_tokens: draft cap per speculating row (default 4).  A static
        trace constant — it shapes a [S, k] verify intermediate, never
        a new executable signature — and the auto step_token_budget
        grows by max_decode_slots * spec_tokens so a fully speculating
        batch still leaves the prefill chunk its room.
    loop_steps: HOST-FREE DECODE LOOP — fuse N ragged decode steps
        into ONE dispatch with on-device sampling and stop matching
        (docs/GENERATION.md "Host-free decode loop").  1 (the tier-1
        CPU oracle default) keeps the per-step path; N > 1 makes a
        decode-only boundary dispatch fused.LoopedRaggedStep and pay
        ONE host fetch per N steps instead of per token.  Scheduler
        joins/admissions happen at loop boundaries, so N is a
        latency-vs-admission knob — token streams are identical to
        N = 1 by the oracle suite (tests/test_looped_decode.py).
        Requires the ragged step; loop_steps > 1 with step_mode unset
        resolves step_mode to "ragged".  Boundaries that are not
        decode-only (a prefill chunk is packed, a row's stop config
        exceeds the loop's static caps, page/position headroom is
        short) fall back to the single-step dispatch for that
        boundary.
    prefix_cache: PREFIX CACHING — refcounted copy-on-write page
        sharing across sequences (docs/GENERATION.md "Prefix
        caching").  Full pages of every completed prompt are indexed
        by a token chain; admission aliases the longest cached run
        into the new sequence's page table and prefill resumes at the
        first unmatched token, so N users of one system prompt pay its
        prefill once and hold one physical copy.  Freed prompt pages
        stay resident as an LRU cache evicted only under pool
        pressure, before any preemption.  Requires a prefill path
        that can resume MID-prompt: chunked prefill
        (prefill_chunk_tokens), or a model implementing the eager
        `prefill_chunk` protocol for the one-shot-prefill engine
        modes.  None = auto, mirroring the other policies: on on TPU
        when CHUNKED prefill is active (the jitted resume path —
        eager-only suffix resume would regress warm TTFT there, so it
        stays explicit opt-in, exactly like eager chunking), off
        elsewhere (the CPU tier-1 oracle stays anchored on the cold
        path; warm-vs-cold token identity is itself oracle-tested,
        tests/test_prefix_cache.py).
    """

    def __init__(self, max_decode_slots=8, num_pages=256, page_size=16,
                 queue_depth=64, default_timeout_ms=None,
                 default_max_new_tokens=16, use_kernel=None,
                 kv_dtype=np.float32, kv_backend=None, max_prefill_batch=4,
                 prefill_length_buckets=None, jit_prefill=None,
                 decode=None, decode_batch_buckets=None, pool_layout=None,
                 prefill_chunk_tokens=None, step_token_budget=None,
                 mesh=None, tp_axis=None, prefix_cache=None,
                 step_mode=None, prefill_pack=True,
                 quantized_collectives=False, spec_mode=None,
                 spec_tokens=4, loop_steps=1):
        self.max_decode_slots = int(max_decode_slots)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.queue_depth = int(queue_depth)
        self.default_timeout_ms = default_timeout_ms
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.use_kernel = use_kernel  # None: auto (Pallas on TPU)
        # accepts np dtypes and names ("int8", "bfloat16"); normalized
        # once here so every consumer compares one representation
        self.kv_dtype = np.dtype(kv_dtype)
        self.quantized_collectives = bool(quantized_collectives)
        if kv_backend not in (None, "host", "device"):
            raise ValueError(
                f"kv_backend must be 'host', 'device' or None (auto), "
                f"got {kv_backend!r}")
        self.kv_backend = kv_backend
        self.max_prefill_batch = int(max_prefill_batch)
        if self.max_prefill_batch < 1:
            raise ValueError("max_prefill_batch must be >= 1")
        self.prefill_length_buckets = prefill_length_buckets
        self.jit_prefill = jit_prefill
        if decode not in (None, "eager", "fused"):
            raise ValueError(
                f"decode must be 'eager', 'fused' or None (auto), got "
                f"{decode!r}")
        self.decode = decode
        self.decode_batch_buckets = decode_batch_buckets
        if pool_layout not in (None, "token", "kernel"):
            raise ValueError(
                f"pool_layout must be 'token', 'kernel' or None, got "
                f"{pool_layout!r}")
        self.pool_layout = pool_layout
        if prefill_chunk_tokens is not None and int(prefill_chunk_tokens) < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0 (0 disables chunking) "
                f"or None (auto), got {prefill_chunk_tokens}")
        self.prefill_chunk_tokens = (None if prefill_chunk_tokens is None
                                     else int(prefill_chunk_tokens))
        if step_token_budget is not None and int(step_token_budget) < 1:
            raise ValueError(
                f"step_token_budget must be >= 1 or None (auto), got "
                f"{step_token_budget}")
        self.step_token_budget = (None if step_token_budget is None
                                  else int(step_token_budget))
        if mesh is not None:
            names = tuple(getattr(mesh, "axis_names", ()))
            if not names:
                raise ValueError(
                    f"mesh must be a jax.sharding.Mesh with named axes, "
                    f"got {type(mesh).__name__}")
            if tp_axis is None:
                tp_axis = names[0]
            elif tp_axis not in names:
                raise ValueError(
                    f"tp_axis {tp_axis!r} is not an axis of the mesh "
                    f"{names}")
        elif tp_axis is not None:
            raise ValueError(
                f"tp_axis={tp_axis!r} without a mesh makes no sense")
        self.mesh = mesh
        self.tp_axis = tp_axis
        if prefix_cache not in (None, True, False):
            raise ValueError(
                f"prefix_cache must be True, False or None (auto), got "
                f"{prefix_cache!r}")
        self.prefix_cache = prefix_cache
        if step_mode not in (None, "legacy", "ragged"):
            raise ValueError(
                f"step_mode must be 'legacy', 'ragged' or None (auto), "
                f"got {step_mode!r}")
        if step_mode == "ragged" and decode is not None:
            raise ValueError(
                "step_mode='ragged' replaces the decode dispatch path "
                "(one mixed-batch executable serves decode AND prefill "
                f"chunks); decode={decode!r} makes no sense with it")
        self.step_mode = step_mode
        if spec_mode not in (None, "off", "ngram"):
            raise ValueError(
                f"spec_mode must be 'ngram', 'off' or None, got "
                f"{spec_mode!r}")
        self.spec_mode = spec_mode or "off"
        self.spec_tokens = int(spec_tokens)
        # only meaningful (and only validated) with speculation on: a
        # templated config carrying spec_tokens=0 alongside an unset
        # spec_mode naturally means "disabled", not an error
        if self.spec_mode == "ngram" and self.spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1 with spec_mode='ngram', "
                f"got {spec_tokens}")
        if self.spec_mode == "ngram" and step_mode == "legacy":
            raise ValueError(
                "spec_mode='ngram' rides the ragged step's packed "
                "token axis (a speculating row is a [start, 1+k, "
                "kv_len] descriptor); step_mode='legacy' has no such "
                "axis")
        self.loop_steps = int(loop_steps)
        if self.loop_steps < 1:
            raise ValueError(
                f"loop_steps must be >= 1 (1 = the per-step path), "
                f"got {loop_steps}")
        if self.loop_steps > 1 and step_mode == "legacy":
            raise ValueError(
                "loop_steps > 1 is the host-free decode loop over the "
                "RAGGED step (N fused ragged iterations per dispatch); "
                "step_mode='legacy' has no such dispatch")
        # multi-prompt chunk packing (plan_pack): True fills each step's
        # leftover token room with MORE prompts' chunks (the RPA packing
        # rule — the default); False restores one chunk per step (the
        # ablation baseline the gen_bench packing A/B measures against)
        self.prefill_pack = bool(prefill_pack)


class GenerationResult:
    """Final outcome of one request."""

    __slots__ = ("token_ids", "finish_reason", "prompt_len", "preemptions")

    def __init__(self, token_ids, finish_reason, prompt_len, preemptions):
        self.token_ids = list(token_ids)
        self.finish_reason = finish_reason  # "stop"|"length"|"cancelled"
        self.prompt_len = prompt_len
        self.preemptions = preemptions

    def __repr__(self):
        return (f"GenerationResult(tokens={self.token_ids}, "
                f"finish_reason={self.finish_reason!r})")


class GenerationHandle:
    """Per-request streaming future.

    `result(timeout)` blocks for the final GenerationResult;
    `tokens(timeout)` iterates token ids AS THEY ARE SAMPLED (ends on
    completion; raises the typed error on failure).  Duck-types the
    Future surface the AdmissionQueue touches (done/set_exception), so
    queue-side deadline reaping resolves the stream too."""

    _DONE = object()

    def __init__(self):
        self._fut = concurrent.futures.Future()
        self._events = queue.SimpleQueue()
        # time-to-first-token probes (monotonic seconds): submit() stamps
        # submitted_s, the first sampled token stamps first_token_s —
        # tools/gen_bench.py's chunked-prefill TTFT A/B reads both
        self.submitted_s = None
        self.first_token_s = None
        # prompt tokens served by the prefix cache at FIRST admission
        # (0 = cold, None = not admitted yet): the per-request warm/cold
        # signal the serving tier (and future SLO routing) reads
        self.prefix_hit_tokens = None
        # the request's own timeline, beside the two probes above (same
        # clock, first writer wins): admitted_s when the scheduler
        # first gave it a slot (a preempted sequence's re-admission
        # does not move it), finished_s when the engine retired it (a
        # request that fails has none), prefill_chunks the ragged
        # dispatches that carried a chunk of its prompt, seq_id the
        # scheduler's id — what a traced step's `seqs` attribute lists
        self.admitted_s = None
        self.finished_s = None
        self.prefill_chunks = 0
        self.seq_id = None
        # authoritative delivered-token count: every fleet remigration
        # reads it as the replay-skip FLOOR, so no race in transport
        # ledger bookkeeping can ever replay a token this handle
        # already streamed (docs/SERVING.md "Failure model")
        self.n_streamed = 0

    # --- engine side ---
    def _push_token(self, token):
        if self.first_token_s is None:
            self.first_token_s = time.monotonic()
        self.n_streamed += 1
        self._events.put(int(token))

    def _finish(self, result):
        if not self._fut.done():
            self._fut.set_result(result)
        self._events.put(self._DONE)

    def set_exception(self, exc):
        try:
            self._fut.set_exception(exc)
        except concurrent.futures.InvalidStateError:
            return
        self._events.put(self._DONE)

    # --- client side ---
    def done(self):
        return self._fut.done()

    def result(self, timeout=None):
        return self._fut.result(timeout)

    def exception(self, timeout=None):
        return self._fut.exception(timeout)

    def add_done_callback(self, fn):
        """Run ``fn(handle)`` once the request resolves — result OR
        typed failure (concurrent.futures callback semantics: called
        immediately if already done).  The fleet tier hangs its
        route-confirmation hook here: prefix_hit_tokens is stamped at
        first admission, so a completed handle tells the router whether
        a prefix-affinity bet actually paid (docs/SERVING.md "Fleet
        tier")."""
        self._fut.add_done_callback(lambda _f: fn(self))

    def tokens(self, timeout=None):
        """Yield token ids as they stream; `timeout` bounds the wait for
        EACH token (queue.Empty on a stall)."""
        while True:
            ev = self._events.get(timeout=timeout)
            if ev is self._DONE:
                break
            yield ev
        # surface the typed failure to stream consumers as well
        exc = self._fut.exception(timeout=0)
        if exc is not None:
            raise exc


class UnsupportedModelPathError(ValueError):
    """The configuration asks for a path this model does not implement
    (a model that keeps a row cache, off the ragged step): refused when
    the engine is built, never mis-served."""


def _refuse_paths_off_the_ragged_step(model, config, windowed,
                                      stateful=False):
    """A model with `kv_rows` (one row a token and layer: a latent
    cache, grouped-query heads side by side) is served by the ragged
    step over device pools and by nothing else; every option that
    selects another path is refused here, once, by name.  `windowed`:
    some of its layers keep only a window of tokens
    (`kv_layer_kinds`), which also rules the prefix cache out: a hit
    would need, for those layers, the last window of the matched
    prefix, and its first owner has given those pages back.
    `stateful`: some of its layers keep a recurrent state a slot and no
    pages.  That rules the prefix cache out too (a hit would need the
    state at the matched prefix's end, which nobody kept), and gives
    speculation and `loop_steps` a second reason (`truncate()` cannot
    rewind a recurrence); pages of such a model are exported and
    imported by nothing (`DeviceKVPool._refuse_latent`)."""
    asked = {
        "kv_backend='host'": config.kv_backend == "host",
        f"decode={config.decode!r}": config.decode is not None,
        "step_mode='legacy'": config.step_mode == "legacy",
        f"loop_steps={config.loop_steps}": config.loop_steps > 1,
        f"spec_mode={config.spec_mode!r}": config.spec_mode == "ngram",
        f"kv_dtype={config.kv_dtype}":
            config.kv_dtype != np.dtype(np.float32),
        f"pool_layout={config.pool_layout!r}":
            config.pool_layout not in (None, "token"),
        "mesh": config.mesh is not None,
        "prefill_chunk_tokens=0": config.prefill_chunk_tokens == 0,
        "prefix_cache=True": ((windowed or stateful)
                              and config.prefix_cache is True),
    }
    bad = [name for name, hit in asked.items() if hit]
    if bad:
        raise UnsupportedModelPathError(
            f"{type(model).__name__} keeps one row a token in its cache "
            f"and is served by the ragged step over a device pool in the "
            f"model's own dtype, with chunked prefill"
            + (" and, its window layers giving pages back, without the "
               "prefix cache" if windowed else "")
            + (" and, its state layers keeping a recurrent state a slot "
               "that no page holds and no truncate() rewinds, without "
               "the prefix cache, speculation, loop_steps or page "
               "export/import" if stateful else "")
            + f"; not carried for it: {', '.join(bad)}")


class _StepInFlight:
    """A ragged step the device has been given and the host has not yet
    read: its unmaterialized outputs, who samples from which descriptor
    (``(state, descriptor, state.preemptions as dispatched)``, so that a
    row is never applied to another incarnation), and the descriptors
    it advanced."""

    __slots__ = ("ids", "logits", "counters", "samplers", "descriptor_of",
                 "greedy", "decode_rows", "spec_rows", "advanced")


class GenerationEngine:
    """Paged-KV continuous-batching decode engine over a protocol model."""

    _IDLE_POLL_S = 0.02

    def __init__(self, model, config=None, metrics=None, start=True):
        import jax

        self.model = model
        self.config = config or GenerationConfig()
        self.metrics = metrics or GenerationMetrics()
        on_tpu = jax.default_backend() == "tpu"
        # what a token's cache row is, where the model says (a latent
        # cache): everything model-specific is settled here, while the
        # engine is built, and never asked again inside a step
        kv_rows = model.kv_rows() if hasattr(model, "kv_rows") else None
        # ... and which of its layers keep only a window of tokens: the
        # pool then holds a second page table and free list for them
        kinds, window_tokens = (model.kv_layer_kinds()
                                if hasattr(model, "kv_layer_kinds")
                                else ((), 0))
        windowed = "window" in kinds
        # ... and which keep no pages at all but a recurrent state a
        # decode slot, which the pool holds beside the pages
        stateful = "state" in kinds
        if kv_rows is not None:
            _refuse_paths_off_the_ragged_step(model, self.config, windowed,
                                              stateful)
        # tensor-parallel mesh: sharded decode is device-pool + fused
        # by construction, so the mesh flips both auto policies
        mesh = self.config.mesh
        tp_axis = self.config.tp_axis
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp_degree = (int(mesh.shape[tp_axis])
                          if mesh is not None else 1)
        backend = self.config.kv_backend or (
            "device" if (on_tpu or mesh is not None or kv_rows is not None)
            else "host")
        if mesh is not None and backend != "device":
            raise ValueError(
                "mesh-sharded generation requires kv_backend='device': "
                "host numpy pools cannot carry a NamedSharding")
        # the kernels are mesh-native (shard_map over the head-sharded
        # mesh, ops/pallas/paged_attention._head_shard_map), so a mesh
        # no longer forces the jnp fallback: sharded and fast are the
        # same path.  Genuinely unsupported combos (heads not divisible
        # by tp) still fail loudly — at pool construction and again in
        # the kernel wrapper.
        self._use_kernel = (self.config.use_kernel
                            if self.config.use_kernel is not None
                            else on_tpu)
        # the pool's layout follows its reader: a per-head device pool
        # read by the Pallas kernels is stored [H, P, page, D], as they
        # consume it, so no step transposes a pool — where its rows can
        # be written in place there (pool_scatter_in_place); the jnp
        # gather, host pools and a latent pool (no head axis) keep the
        # token layout
        pool_layout = self.config.pool_layout
        if pool_layout is None:
            pool_layout = "token"
            if backend == "device" and kv_rows is None and self._use_kernel:
                from ..ops.pallas.paged_attention import (
                    pool_scatter_in_place)

                if pool_scatter_in_place(
                        (model.num_heads, self.config.num_pages,
                         self.config.page_size, model.head_dim),
                        self.config.kv_dtype):
                    pool_layout = "kernel"
        if backend == "device":
            window = None
            if windowed:
                # `num_pages` is the full layers' group; the window
                # group is sized so that it cannot run out: every slot
                # and one sequence being admitted at the most pages a
                # sequence holds there (its window and one chunk)
                chunk = (self.config.prefill_chunk_tokens
                         or DEFAULT_PREFILL_CHUNK_TOKENS)
                cap = WindowPageGroup.pages_a_sequence(
                    self.config.page_size, window_tokens, chunk)
                window = (kinds, window_tokens,
                          (self.config.max_decode_slots + 1) * cap, chunk)
            self.cache = DeviceKVPool(
                model.num_layers, model.num_heads, model.head_dim,
                num_pages=self.config.num_pages,
                page_size=self.config.page_size,
                dtype=self.config.kv_dtype, pool_layout=pool_layout,
                mesh=mesh, tp_axis=tp_axis, rows=kv_rows, window=window,
                state=((kinds, model.kv_slot_state(),
                        self.config.max_decode_slots)
                       if stateful else None))
        else:
            if pool_layout == "kernel":
                raise ValueError(
                    "pool_layout='kernel' requires kv_backend='device' "
                    "(host numpy pools only store the token layout)")
            self.cache = PagedKVCache(
                model.num_layers, model.num_heads, model.head_dim,
                num_pages=self.config.num_pages,
                page_size=self.config.page_size,
                dtype=self.config.kv_dtype)
        # int8 pools: every write quantizes, every read dequantizes;
        # the scale arrays ride the donation chain and the eager attend
        # passes them to the scale-aware attention dispatchers
        self.kv_quant = bool(self.cache.quantized)
        # quantized collectives are real only when collectives exist
        # (tp > 1); the collective_quantized gauge reports the truth
        self._quant_collectives = (self.config.quantized_collectives
                                   and self.tp_degree > 1)
        self.scheduler = ContinuousBatchingScheduler(
            self.cache, num_slots=self.config.max_decode_slots,
            queue_depth=self.config.queue_depth, metrics=self.metrics)
        self._bucketer = self._build_bucketer()
        jit_prefill = (self.config.jit_prefill if self.config.jit_prefill
                       is not None else on_tpu)
        # one prefill "executable" per (batch, length) bucket — AOT-
        # compiled when jit_prefill, the raw eager fn otherwise (bitwise
        # parity with the sequential oracle); either way the signature
        # cache is the compile-count probe
        self.prefill_cache = None
        if hasattr(model, "prefill_batch"):
            self.prefill_cache = CompiledModelCache(
                model.prefill_batch, metrics=self.metrics, aot=jit_prefill)
        # decode path: fused (one jitted pool-donating dispatch per step)
        # mirrors jit_prefill's auto policy — TPU default, eager-exact
        # stays the CPU tier-1 default so the zero-tolerance oracle is
        # anchored on the unfused path
        fusable = (backend == "device"
                   and hasattr(model, "decode_step_fn")
                   and hasattr(model, "decode_params"))
        # ragged mixed-batch step: ONE pool-donating dispatch serves the
        # decode batch and the step's prefill chunk — auto on TPU when
        # the model implements the ragged protocol, legacy elsewhere
        # (the CPU tier-1 oracle stays anchored on the eager legacy
        # path; ragged-vs-legacy identity is itself oracle-tested)
        ragged_capable = (backend == "device"
                         and hasattr(model, "ragged_step_fn")
                         and hasattr(model, "decode_params"))
        spec_on = self.config.spec_mode == "ngram"
        loop_on = self.config.loop_steps > 1
        step_mode = self.config.step_mode
        if step_mode is None:
            # spec_mode="ngram" / loop_steps > 1 are explicit opt-outs
            # of the eager oracle anyway: asking for either resolves
            # the auto step mode to ragged wherever the model supports
            # it (CPU included)
            step_mode = "ragged" if (
                (on_tpu or spec_on or loop_on or kv_rows is not None)
                and ragged_capable) else "legacy"
        if step_mode == "ragged" and not ragged_capable:
            raise ValueError(
                "step_mode='ragged' needs kv_backend='device' and a "
                "model implementing ragged_step_fn/decode_params "
                f"(backend={backend!r}, model={type(model).__name__})")
        if spec_on and step_mode != "ragged":
            raise ValueError(
                "spec_mode='ngram' rides the ragged step's packed "
                "token axis; this engine resolved to step_mode="
                f"{step_mode!r} (kv_backend={backend!r}, model="
                f"{type(model).__name__})")
        if loop_on and (step_mode != "ragged"
                        or not hasattr(model, "ragged_loop_fn")):
            raise ValueError(
                f"loop_steps={self.config.loop_steps} needs the "
                "ragged step and a model implementing ragged_loop_fn "
                f"(step_mode={step_mode!r}, "
                f"model={type(model).__name__})")
        self.step_mode = step_mode
        decode = self.config.decode
        if step_mode == "ragged":
            decode = "ragged"
        elif decode is None:
            decode = ("fused" if ((on_tpu or mesh is not None) and fusable)
                      else "eager")
        if mesh is not None and decode not in ("fused", "ragged"):
            raise ValueError(
                "mesh-sharded decode runs only on the fused or ragged "
                "path (one GSPMD dispatch per step); decode='eager' "
                "under a mesh is not supported — the eager single-chip "
                "path is the oracle sharded decode is measured against."
                "  The model must implement decode_step_fn/decode_params "
                f"({type(model).__name__})")
        elif decode == "fused" and not fusable:
            raise ValueError(
                "decode='fused' needs kv_backend='device' and a model "
                "implementing decode_step_fn/decode_params "
                f"(backend={backend!r}, model={type(model).__name__})")
        self.decode_mode = decode
        self._fused = None
        self._ragged = None
        if decode == "fused":
            from .fused import FusedDecodeStep, decode_batch_menu

            buckets = (self.config.decode_batch_buckets
                       or decode_batch_menu(self.config.max_decode_slots))
            if max(buckets) < self.config.max_decode_slots:
                # surface the misconfiguration at build, not as a
                # load-dependent RequestTooLargeError poisoning every
                # in-flight request the first time all slots fill
                raise ValueError(
                    f"decode_batch_buckets top bucket {max(buckets)} < "
                    f"max_decode_slots={self.config.max_decode_slots}: "
                    f"a full decode batch could never be padded")
            self._fused = FusedDecodeStep(
                model, self.cache, self.metrics,
                use_kernel=self._use_kernel, batch_buckets=buckets,
                mesh=mesh, tp_axis=tp_axis,
                quant_collectives=self._quant_collectives)
        # chunked prefill policy mirrors jit_prefill/decode: auto picks
        # chunking on TPU when the model implements the chunk protocol;
        # the CPU tier-1 default stays the one-shot prefill the
        # zero-tolerance oracle is anchored on (chunked-vs-full identity
        # is itself oracle-tested, tests/test_chunked_prefill.py)
        chunk_jitable = (backend == "device"
                        and hasattr(model, "prefill_chunk_fn")
                        and hasattr(model, "decode_params"))
        chunk_eager_ok = hasattr(model, "prefill_chunk")
        chunk = self.config.prefill_chunk_tokens
        if chunk is None:
            # auto only picks a JITTED chunk path, mirroring the decode
            # auto policy: on TPU the fast path or nothing — the
            # per-layer eager chunk loop would REGRESS TTFT vs one
            # jitted full prefill, so eager chunking stays explicit
            # opt-in (it is the CPU oracle path).  The ragged step IS a
            # jitted chunk path (chunks ride the one mixed-batch
            # dispatch); otherwise device pools + prefill_chunk_fn +
            # jit_prefill are required, and jit_prefill=False must
            # degrade to full prefill, never raise on a config the user
            # didn't write.
            chunk = (DEFAULT_PREFILL_CHUNK_TOKENS
                     if (step_mode == "ragged"
                         or (on_tpu and chunk_jitable and jit_prefill))
                     else 0)
        elif chunk and not (chunk_jitable or chunk_eager_ok
                            or step_mode == "ragged"):
            raise ValueError(
                f"prefill_chunk_tokens={chunk} needs a model implementing "
                f"prefill_chunk (eager) or prefill_chunk_fn + "
                f"decode_params with kv_backend='device', or the ragged "
                f"step ({type(model).__name__} has none)")
        self.prefill_chunk_tokens = chunk
        self._chunk_step = None
        if step_mode == "ragged":
            pass  # chunks ride the ragged dispatch; no separate step
        elif chunk and jit_prefill and chunk_jitable:
            from .fused import ChunkedPrefillStep

            self._chunk_step = ChunkedPrefillStep(
                model, self.cache, self.metrics, chunk,
                use_kernel=self._use_kernel, mesh=mesh, tp_axis=tp_axis,
                quant_collectives=self._quant_collectives)
        elif chunk and not chunk_eager_ok:
            raise ValueError(
                "chunked prefill without jit_prefill + kv_backend="
                "'device' runs the eager chunk path, which needs "
                f"model.prefill_chunk ({type(model).__name__} lacks it)")
        # prefix caching: a warm hit resumes prefill MID-prompt, which
        # only a chunk-capable path can do — the chunked-prefill loop
        # resumes at prefill_pos natively, and the one-shot modes fall
        # back to one eager prefill_chunk call over the suffix.  Auto
        # mirrors the other policies: on on TPU when supported, off on
        # CPU so the tier-1 oracle stays anchored cold (warm-vs-cold
        # identity is itself oracle-tested, tests/test_prefix_cache.py).
        prefix_ok = bool(chunk) or chunk_eager_ok
        prefix = self.config.prefix_cache
        if windowed or stateful:
            prefix = False   # True was refused above; auto resolves off
        elif prefix is None:
            # auto requires chunked prefill to actually be ON, not just
            # an eager chunk protocol: with chunking off, a warm hit's
            # suffix runs the per-layer eager loop — the path the chunk
            # auto policy itself refuses on TPU for regressing TTFT
            # (a 16-token hit on an 8k prompt must not trade one jitted
            # prefill for thousands of eager dispatches).  Eager-only
            # warm resume stays explicit opt-in, like eager chunking.
            prefix = on_tpu and bool(chunk)
        elif prefix and not prefix_ok:
            raise ValueError(
                "prefix_cache=True needs a prefill path that can resume "
                "mid-prompt: chunked prefill (prefill_chunk_tokens) or "
                "a model implementing prefill_chunk — one-shot "
                f"model.prefill always starts at token 0 "
                f"({type(model).__name__})")
        self.prefix_cache_enabled = bool(prefix)
        self.scheduler.prefix_cache = self.prefix_cache_enabled
        slots = self.config.max_decode_slots
        # speculation sizes the auto packed axis for a fully drafting
        # batch — decode rows carry 1 + spec_tokens rows each — while
        # the prefill chunk keeps its own room; an explicit budget
        # instead CLIPS drafts at plan time (speculation is a pure
        # optimization, it never squeezes a decode or chunk row out)
        self.spec_tokens = self.config.spec_tokens if spec_on else 0
        spec_room = slots * self.spec_tokens
        self.step_token_budget = (
            self.config.step_token_budget
            if self.config.step_token_budget is not None
            else (chunk + slots + spec_room if chunk
                  else (slots + spec_room if step_mode == "ragged"
                        else None)))
        if step_mode == "ragged":
            # the budget IS the ragged executable's packed token axis:
            # it must hold the full decode batch, plus at least one
            # prefill row when chunking is on (a full batch that never
            # finished would otherwise starve prompts forever)
            need = slots + (1 if chunk else 0)
            if self.step_token_budget < need:
                raise ValueError(
                    f"step_token_budget={self.step_token_budget} < "
                    f"{need}: the ragged step's packed token axis must "
                    f"hold every decode slot"
                    + (" plus at least one prefill-chunk row"
                       if chunk else ""))
            from .fused import RaggedStep

            self._ragged = RaggedStep(
                model, self.cache, self.metrics,
                max_tokens=self.step_token_budget,
                max_seqs=slots + 1, use_kernel=self._use_kernel,
                mesh=mesh, tp_axis=tp_axis,
                quant_collectives=self._quant_collectives,
                spec_tokens=self.spec_tokens)
        # the host-free decode loop: N fused ragged iterations per
        # dispatch at decode-only boundaries, ONE host fetch per N
        # steps — built ALONGSIDE the single-step RaggedStep, which
        # stays the fallback for non-decode-only boundaries (chunks
        # packed, stop configs past the static caps, headroom short)
        self._loop = None
        if loop_on:
            # capability was validated up front with the step-mode
            # resolution, before any executable was built
            from .fused import LoopedRaggedStep

            self._loop = LoopedRaggedStep(
                model, self.cache, self.metrics, max_seqs=slots,
                loop_steps=self.config.loop_steps,
                use_kernel=self._use_kernel, mesh=mesh, tp_axis=tp_axis,
                quant_collectives=self._quant_collectives,
                spec_tokens=self.spec_tokens)
        self.loop_steps = self.config.loop_steps if loop_on else 1
        # the prompt-lookup proposer (None = speculation off): host-
        # side, model-free, consulted once per greedy decode row per
        # step by scheduler.plan_spec
        self._spec = None
        if spec_on:
            from .speculation import NgramProposer

            self._spec = NgramProposer()
        self.metrics.set_mesh_devices(self.tp_degree)
        # which attention implementation this engine's step mode
        # dispatches — "pallas" or "jnp-reference", prefixed with the
        # step mode — so a silent fallback to the reference path is a
        # visible stats fact instead of an inference from timings (the
        # bug class that hid the mesh/kernel gap for three PRs)
        self.metrics.set_kernel_path(self.decode_mode, self._use_kernel)
        # and which layout the KV pool is stored in, beside it
        self.metrics.set_kv_pool_layout(
            kv_rows.layout if kv_rows is not None
            else self.cache.pool_layout)
        # ... which kinds of layer it serves, how many of each, and the
        # window the window layers keep (0: every layer keeps all)
        self.metrics.set_kv_layer_groups(
            {kind: kinds.count(kind) for kind in sorted(set(kinds))}
            or {"full": int(model.num_layers)},
            window_tokens if windowed else 0)
        # ... and, for a latent pool the kernel reads, the pages a grid
        # step of that kernel holds (0: no latent kernel runs)
        pages_per_cell = 0
        if isinstance(kv_rows, LatentRows) and self._use_kernel:
            from ..ops.pallas.paged_attention import latent_pages_per_cell

            pages_per_cell = latent_pages_per_cell(self.cache.page_size,
                                                   self.cache.num_pages)
        self.metrics.set_latent_pages_per_cell(pages_per_cell)
        # ... and, for a per-head pool the ragged kernel reads, the
        # pages and the heads (of a mesh shard's) a grid step holds
        cell = (0, 0)
        if (self._ragged is not None and kv_rows is None
                and self._use_kernel):
            from ..ops.pallas.paged_attention import ragged_cell_shape

            cell = ragged_cell_shape(
                self.cache.page_size, self.cache.num_pages,
                self.step_token_budget,
                int(model.num_heads) // self.tp_degree, model.head_dim,
                np.dtype(self.cache.dtype).itemsize)[:2]
        self.metrics.set_ragged_cell(*cell)
        # precision facts, stamped once like kernel_path: what dtype
        # the pools store, and whether the quantized ring ACTUALLY
        # carries the allreduces (a requested-but-inert flag reads 0)
        self.metrics.set_kv_quant_dtype(str(self.cache.dtype))
        if kv_rows is not None:
            # the layers that keep pages: a state layer keeps none
            state_layers = self.cache.state_layers
            self.metrics.set_kv_token_bytes(kv_rows.token_bytes(
                self.cache.num_layers - state_layers))
            if stateful:
                self.metrics.set_kv_state_bytes_a_slot(
                    state_layers * self.cache.slot_state.bytes_a_slot)
        if hasattr(model, "build_gauges"):
            self.metrics.set_model_gauges(model.build_gauges())
        self.metrics.set_collective_quantized(self._quant_collectives)
        # the spec_mode build stamp (kernel_path pattern): engine
        # construction refuses unsupported spec combos, so the stamp
        # is the truth — "off" in a snapshot MEANS non-speculative
        self.metrics.set_spec_mode(self.config.spec_mode)
        # the loop_steps build stamp, same pattern: 1 in a snapshot
        # MEANS the per-step path produced its numbers
        self.metrics.set_loop_steps(self.loop_steps)
        self._lock = threading.Lock()  # one stepper at a time
        # monotone step-progress stamp: bumped every COMPLETED step()
        # call, with `in_step` flagging the window where a step HOLDS
        # the lock (a long jit compile inside a step is progress, not
        # a wedge).  A subprocess replica's heartbeat carries both, so
        # a wedged engine — step loop BLOCKED on the lock, heartbeat
        # thread alive — shows as work-without-progress-outside-a-step,
        # the fleet wedge watchdog's signal (docs/SERVING.md "Failure
        # model")
        self._step_seq = 0
        self._in_step = False
        # P/D disaggregation seam (serving/disagg): a PREFILL-class
        # engine parks every sequence the moment its prompt is
        # consumed — exported as a live-migration snapshot into
        # _handoff_out instead of decoding here — and `on_handoff` is
        # notified AFTER the step lock is released (pull model: the
        # collector drains take_handoffs(), so no router lock is ever
        # taken under the engine lock)
        self._handoff = False
        self.on_handoff = None
        self._handoff_out = []
        # the ragged step keeps ONE step in flight: enqueued, its ids
        # not read (`_step_ragged`); None at depth 0
        self._inflight = None
        self._closed = False
        self._stop = threading.Event()
        self._thread = None
        if start:
            self.start()

    def _build_bucketer(self):
        """The prefill shape menu: batch buckets up to max_prefill_batch,
        length buckets from config or a geometric auto-menu covering
        every admissible prompt (capped so a padded bucket can never
        exceed the model's max_positions)."""
        from .fused import decode_batch_menu

        cfg = self.config
        batch = decode_batch_menu(cfg.max_prefill_batch)
        max_pos = getattr(self.model, "max_positions", None)
        lengths = cfg.prefill_length_buckets
        if lengths is None:
            limit = cfg.num_pages * cfg.page_size
            if max_pos is not None:
                limit = min(limit, int(max_pos))
            menu = [x for x in ShapeBucketer.geometric_menu(limit)
                    if x < limit]
            lengths = tuple(menu) + (limit,)
        elif max_pos is not None:
            # a padded bucket may never exceed what the model can embed:
            # clip oversized explicit entries to max_positions (buckets
            # beyond the POOL are fine — padding is dropped, not written)
            lengths = tuple(sorted({min(int(b), int(max_pos))
                                    for b in lengths}))
        return ShapeBucketer(batch_buckets=tuple(sorted(set(batch))),
                             length_buckets=lengths)

    # --------------------------- client API -------------------------
    def submit(self, prompt, max_new_tokens=None, sampling=None,
               stop_tokens=(), timeout_ms=None, handle=None):
        """Enqueue one prompt; returns a GenerationHandle immediately.
        Raises ServerBusyError (queue full) / RequestTooLargeError
        (prompt can never fit the page pool) synchronously.

        `handle` lets a CALLER supply the handle object the engine
        drives (anything duck-typing the engine-side surface:
        _push_token/_finish/set_exception/done plus the submitted_s /
        first_token_s / prefix_hit_tokens attributes; the engine also
        sets admitted_s / finished_s / prefill_chunks / seq_id on it,
        so a handle with __slots__ lists those too) — the hook the
        fleet tier uses so one client-held handle can survive a
        drain-migration cold resubmit on a sibling replica
        (serving/fleet.py).  submitted_s is stamped only when unset, so
        a resubmitted request keeps its original TTFT clock."""
        if self._closed:
            raise ServingError("generation engine is shut down")
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        if sampling is None:
            sampling = SamplingParams()
        timeout_ms = (self.config.default_timeout_ms
                      if timeout_ms is None else timeout_ms)
        deadline = (None if timeout_ms is None
                    else time.monotonic() + float(timeout_ms) / 1e3)
        max_pos = getattr(self.model, "max_positions", None)
        if max_pos is not None and len(prompt) + max_new_tokens > max_pos:
            raise RequestTooLargeError(
                f"prompt of {len(prompt)} + max_new_tokens="
                f"{max_new_tokens} exceeds the model's max_positions="
                f"{max_pos}")
        if handle is None:
            handle = GenerationHandle()
        if handle.submitted_s is None:
            handle.submitted_s = time.monotonic()
        req = GenerationRequest(prompt, handle, sampling,
                                max_new_tokens=max_new_tokens,
                                stop_tokens=stop_tokens, deadline=deadline)
        self.scheduler.submit(req)
        self.metrics.count_request()
        return handle

    def generate(self, prompt, **kw):
        """Blocking convenience: submit + result."""
        return self.submit(prompt, **kw).result()

    def stats(self):
        """generation.* metrics snapshot + live cache stats."""
        snap = self.metrics.snapshot()
        snap.update({"cache." + k: v for k, v in self.cache.stats().items()})
        return snap

    def evacuate(self, include_active=False):
        """Atomically extract unfinished work for a fleet-tier drain
        (serving/fleet.py): every NOT-YET-PLACED request (admission
        queue + the pending re-prefill line) always, plus — when
        `include_active` — every live slot-holder, which is retired
        here (slot and pages freed) WITHOUT resolving its handle.
        Returns ``[(GenerationRequest, n_emitted)]``; the caller owns
        resubmitting each request (sampling is seeded per request, so a
        cold resubmit replays the identical stream and the first
        `n_emitted` tokens — already streamed to the client — can be
        skipped by a relay handle).  Runs under the step lock, so no
        token can land on an extracted request after this returns.
        Expired requests are reaped with the typed deadline error
        instead of being returned."""
        with self._lock:
            self._retire_inflight("api")
            out = self.scheduler.take_pending()
            if include_active:
                for state in self.scheduler.active():
                    self.scheduler.retire(state)
                    if state.request.expired():
                        state.request.reject_expired()
                        self.metrics.count_rejected_deadline()
                        continue
                    out.append((state.request, state.n_generated))
            return out

    # ---------------------- disaggregation hooks --------------------
    # Live migration and the fleet page service (serving/disagg):
    # export ships raw resident state — page BYTES, page table shape,
    # positions, sampling RNG — and import installs it into a sibling
    # engine so a mid-decode stream RESUMES instead of replaying, and a
    # warm prefix run is adopted by a pool that never prefilled it.
    # All four run under the step lock: no token can land on (or page
    # be evicted from) state that is mid-flight.

    def evacuate_for_migration(self):
        """The live-migration drain extraction: everything evacuate()
        moves, but live decode-phase residents leave as SEQUENCE
        SNAPSHOTS (page bytes + decode state) instead of cold
        resubmits.  Returns ``(cold, live)`` — `cold` is evacuate()'s
        ``[(GenerationRequest, n_emitted)]`` (queued work plus
        mid-prefill slot-holders, which have no finished pages worth
        shipping), `live` a list of snapshot dicts for
        ``import_sequence`` on a sibling (each carries the client
        handle under "future").  Expired requests are reaped typed on
        the way."""
        with self._lock:
            self._retire_inflight("api")
            cold = self.scheduler.take_pending()
            # snaps already parked for P/D handoff but not yet
            # collected ride the live list unchanged — they hold page
            # BYTES, not pool pages, so this can never leak
            live, self._handoff_out = self._handoff_out, []
            for state in self.scheduler.active():
                if state.request.expired():
                    self.scheduler.retire(state)
                    state.request.reject_expired()
                    self.metrics.count_rejected_deadline()
                    continue
                if state.prefilling or not self.cache.has(state.seq_id):
                    self.scheduler.retire(state)
                    cold.append((state.request, state.n_generated))
                    continue
                live.append(self._export_sequence(state))
            return cold, live

    def _export_sequence(self, state):
        """Snapshot one decode-phase resident for live migration —
        page bytes first (export_pages), THEN retire (which frees the
        pages) — and hand back everything a sibling needs to resume
        the stream mid-decode: tokens so far, generated count, the
        sampling RNG (its state IS the stream position for stochastic
        requests), and the cache length the pages cover.  The handle
        is NOT resolved: the importer keeps pushing into it."""
        req = state.request
        length = self.cache.seq_len(state.seq_id)
        out = self.cache.export_pages(
            self.cache.page_table(state.seq_id))
        # quantized pools export (k, v, k_scale, v_scale): the scales
        # ARE the payload's grid and travel with it
        k, v = out[0], out[1]
        k_scale, v_scale = (out[2], out[3]) if len(out) == 4 \
            else (None, None)
        snap = {
            "prompt": list(req.prompt),
            "max_new_tokens": int(req.max_new_tokens),
            "stop_tokens": tuple(req.stop_tokens),
            "sampling": req.params,
            "deadline": req.deadline,
            "tokens": list(state.tokens),
            "n_generated": int(state.n_generated),
            "preemptions": int(state.preemptions),
            "rng": state.rng,
            "cache_len": int(length),
            "k": k, "v": v,
            "k_scale": k_scale, "v_scale": v_scale,
            "future": req.future,
        }
        self.scheduler.retire(state)
        return snap

    def import_sequence(self, snap, handle=None):
        """LIVE-MIGRATION import: adopt a sibling-exported mid-decode
        resident — install its page bytes into this pool, rebuild its
        SequenceState (tokens, RNG, counters), seat it in a free slot,
        and let the normal step loop resume its decode with ZERO
        replayed tokens.  Returns True when adopted; False when this
        engine cannot hold it right now (no free slot, pool too full
        even after eviction, or layout-incompatible pools) — the
        caller falls back to the cold-resubmit ladder, which is always
        correct (seeded sampling replays identically)."""
        if handle is None:
            handle = snap.get("future")
        with self._lock:
            self._retire_inflight("api")
            if self._closed or self.scheduler.free_slots() == 0:
                return False
            try:
                # a quantization-boundary mismatch (bf16 snapshot into
                # an int8 pool or vice versa) raises the typed
                # KVQuantMismatchError — a ValueError, so the caller's
                # cold-resubmit ladder handles the heterogeneous fleet
                # gracefully instead of corrupting a pool
                pages = self.cache.import_pages(
                    snap["k"], snap["v"], snap.get("k_scale"),
                    snap.get("v_scale"))
            except (OutOfPagesError, ValueError):
                return False
            seq_id = None
            attached = False
            try:
                req = GenerationRequest(
                    snap["prompt"], handle, snap["sampling"],
                    max_new_tokens=snap["max_new_tokens"],
                    stop_tokens=snap["stop_tokens"],
                    deadline=snap.get("deadline"))
                state = SequenceState(self.scheduler.next_seq_id(), req)
                seq_id = state.seq_id
                self.cache.allocate(seq_id)
                self.cache.adopt_imported(seq_id, pages,
                                          snap["cache_len"])
                attached = True
                state.tokens = list(snap["tokens"])
                state.n_generated = int(snap["n_generated"])
                state.preemptions = int(snap["preemptions"])
                state.rng = snap["rng"]
                state.prefilling = False
                state.prefill_pos = int(snap["cache_len"])
                self.scheduler.place_imported(state)
            except Exception:   # noqa: BLE001 — a poisoned snapshot or
                # a failure mid-install (crash-injection territory)
                # must not leak the imported pages or strand a
                # half-built resident: give everything back and refuse
                # typed (False → the caller's cold-resubmit ladder)
                self._recover_failed_import(seq_id, attached, pages)
                return False
            self.metrics.count_request()
            return True

    def _recover_failed_import(self, seq_id, attached, pages):
        """Roll back a mid-flight import_sequence failure so the pool
        stays consistent: free the sequence when its table holds the
        pages, otherwise route the orphaned (refcount-1, ownerless)
        pages through a throwaway adopter so the free list gets every
        byte back — drain + flush == all-free must survive a crash at
        ANY point of the install."""
        try:
            if attached and seq_id is not None:
                self.cache.free(seq_id)
                return
            if seq_id is not None and self.cache.has(seq_id):
                self.cache.free(seq_id)
            if pages:
                rid = ("__import_recovery__", id(pages))
                self.cache.allocate(rid)
                self.cache.adopt_imported(
                    rid, pages, len(pages) * self.cache.page_size)
                self.cache.free(rid)
        except Exception:   # noqa: BLE001 — recovery is best-effort;
            pass            # never mask the refusal with a new error

    def drain_work(self, migrate=True, live=True, timeout=60.0):
        """The drain state machine BOTH transport halves run
        (InprocTransport.drain and the subprocess worker's evacuate op
        — one implementation, so the in-process oracle and the
        process-boundary replica cannot diverge): evacuate unfinished
        work and shut the engine down.  migrate=False lets residents
        finish first — stepping the engine here when no worker thread
        runs — and evacuates stragglers that outlive `timeout` (live
        snapshots when `live`, cold resubmits otherwise) so a drain
        always converges.  Returns ``(cold, live_snaps)``."""
        if migrate:
            if live:
                cold, live_snaps = self.evacuate_for_migration()
            else:
                cold, live_snaps = self.evacuate(include_active=True), []
        else:
            cold, live_snaps = self.evacuate(include_active=False), []
            deadline = time.monotonic() + float(timeout)
            while self.scheduler.active() \
                    or self.scheduler.pending_count():
                if time.monotonic() > deadline:
                    # stragglers outlived the drain budget: evacuate
                    # them (resume beats replay when live is allowed)
                    # rather than wedging the replica in 'draining'
                    if live:
                        c2, l2 = self.evacuate_for_migration()
                    else:
                        c2, l2 = self.evacuate(include_active=True), []
                    cold += c2
                    live_snaps += l2
                    break
                if self._thread is not None and self._thread.is_alive():
                    time.sleep(0.005)
                else:
                    self.step()   # stepped mode: the drain drives them
        # P/D: handoff snaps still parked when the drain ends must
        # leave with everything else (a prefill engine's residents
        # land here by construction — they never finish locally)
        with self._lock:
            parked, self._handoff_out = self._handoff_out, []
        for snap in parked:
            if live:
                live_snaps.append(snap)
            else:
                req = GenerationRequest(
                    snap["prompt"], snap["future"], snap["sampling"],
                    max_new_tokens=snap["max_new_tokens"],
                    stop_tokens=snap["stop_tokens"],
                    deadline=snap.get("deadline"))
                cold.append((req, int(snap["n_generated"])))
        self.shutdown()
        return cold, live_snaps

    def describe(self):
        """Static replica facts the router's capacity pre-filter needs
        (can_fit without an RPC) — the transport `describe` contract,
        shared by both transport halves."""
        import os

        cfg = self.config
        return {
            "page_size": cfg.page_size,
            "num_pages": cfg.num_pages,
            "max_positions": getattr(self.model, "max_positions", None),
            "default_max_new_tokens": cfg.default_max_new_tokens,
            # decode-slot ceiling: the denominator of the autoscaler's
            # decode-class occupancy signal (serving/control.py)
            "max_decode_slots": cfg.max_decode_slots,
            "pid": os.getpid(),
        }

    def load_info(self):
        """Live load facts for the router's least-loaded rung — the
        transport `load_info` contract (exact for inproc; a subprocess
        replica reports this on every heartbeat)."""
        sched = self.scheduler
        return {
            "queue_depth": sched.pending_count(),
            "active": len(sched.active()),
            "pages_in_use": self.cache.pages_in_use,
            "num_pages": self.cache.num_pages,
            # parked handoffs are unfinished work: a prefill replica
            # with uncollected snaps must not read as idle (the orphan
            # sweep and run_until_idle both key off this)
            "idle": not (sched.active() or sched.pending_count()
                         or self._handoff_out),
        }

    def export_prefix_pages(self, tokens):
        """Page-service EXPORT: the longest fully-cached page run
        matching a prefix of `tokens`, as ``{"tokens": covered_tokens,
        "k": ..., "v": ...}`` ready for a sibling's
        import_prefix_pages — or None when nothing is cached (or the
        prefix cache is off)."""
        with self._lock:
            self._retire_inflight("api")
            if not self.prefix_cache_enabled:
                return None
            pages, matched = self.cache.match_prefix_full(tokens)
            if not pages:
                return None
            # every export IS one observed unit of cross-replica
            # demand (relay and p2p both funnel through here): fold
            # it into the eviction order so fleet-hot chains survive
            self.cache.note_fleet_demand(pages)
            out = self.cache.export_pages(pages)
            payload = {"tokens": [int(t) for t in tokens[:matched]],
                       "k": out[0], "v": out[1]}
            if len(out) == 4:   # quantized: grid travels with bytes
                payload["k_scale"], payload["v_scale"] = out[2], out[3]
            return payload

    def import_prefix_pages(self, payload):
        """Page-service IMPORT: adopt a sibling-exported prefix run
        into this engine's pool + prefix index (read-only cached
        resident, COW-guarded like any locally registered run).
        Returns pages newly indexed — 0 when skipped (cache off, pool
        pressure, or layout-incompatible payload); adoption is an
        optimization and must never fail a request."""
        with self._lock:
            self._retire_inflight("api")
            if not self.prefix_cache_enabled or payload is None:
                return 0
            try:
                # KVQuantMismatchError (a ValueError) lands here too:
                # a bf16<->int8 heterogeneous adoption attempt is
                # refused typed and skipped — adoption is an
                # optimization, never a failure
                return self.cache.import_prefix_run(
                    payload["tokens"], payload["k"], payload["v"],
                    payload.get("k_scale"), payload.get("v_scale"))
            except (OutOfPagesError, ValueError):
                return 0

    # ----------------------- P/D handoff seam -----------------------
    def enable_handoff(self):
        """Make this a PREFILL-class engine: every sequence is parked
        the moment its prompt is consumed (exported exactly like a
        live migration — page bytes, RNG, counters — into an internal
        list) instead of decoding here.  The owner drains
        take_handoffs() and places each snapshot on a decode-class
        sibling via import_sequence; `on_handoff` (called after each
        step that parked something, OUTSIDE the step lock) is the
        wakeup.  A step in flight is read first: from here on none is
        left in flight (`_drain_reason`)."""
        with self._lock:
            self._retire_inflight("handoff")
            self._handoff = True

    def _sweep_handoffs_locked(self):
        """Park every prefill-complete resident (under the step lock,
        called at the end of step()).  A state is ready the moment its
        prefill is done and its first token sampled — n_generated is
        then the importer's resume base, and the client stream is
        healed to exactly that prefix by the collector."""
        parked = False
        for state in self.scheduler.active():
            if state.prefilling or state.n_generated < 1:
                continue
            if state.request.expired():
                continue   # the next step's deadline reaper owns it
            if not self.cache.has(state.seq_id):
                continue
            self._handoff_out.append(self._export_sequence(state))
            parked = True
        return parked

    def take_handoffs(self):
        """Drain parked prefill-complete snapshots (each carries the
        client handle under "future" and page BYTES — pool pages were
        freed at export, so a parked snap can never leak pages)."""
        with self._lock:
            out, self._handoff_out = self._handoff_out, []
        return out

    def handoffs_pending(self):
        return bool(self._handoff_out)

    # ---------------------------- cancel ----------------------------
    def cancel(self, handle):
        """Cancel the request owned by `handle` wherever it currently
        lives — admission queue, pending re-prefill line, or a live
        decode slot (slot and pages freed) — and resolve the handle
        with ``finish_reason="cancelled"`` and whatever tokens already
        streamed, so an abandoning client NEVER hangs and never keeps
        paying for decode it stopped reading.  False when the handle
        owns nothing here (already finished, or migrated away)."""
        with self._lock:
            self._retire_inflight("api")
            for state in self.scheduler.active():
                if state.handle is handle:
                    self.scheduler.retire(state)
                    req = state.request
                    handle._finish(GenerationResult(
                        state.tokens[len(req.prompt):], "cancelled",
                        len(req.prompt), state.preemptions))
                    self.metrics.count_finished()
                    return True
            item = self.scheduler.cancel_pending(handle)
            if item is not None:
                if isinstance(item, SequenceState):   # preempted
                    handle._finish(GenerationResult(
                        item.tokens[len(item.request.prompt):],
                        "cancelled", len(item.request.prompt),
                        item.preemptions))
                else:   # still queued, nothing generated
                    handle._finish(GenerationResult(
                        [], "cancelled", len(item.prompt), 0))
                self.metrics.count_finished()
                return True
            for i, snap in enumerate(self._handoff_out):
                if snap["future"] is handle:
                    # parked for P/D handoff but not yet collected:
                    # the snap holds bytes, not pages — drop it
                    del self._handoff_out[i]
                    handle._finish(GenerationResult(
                        snap["tokens"][len(snap["prompt"]):],
                        "cancelled", len(snap["prompt"]),
                        snap["preemptions"]))
                    self.metrics.count_finished()
                    return True
        return False

    # --------------------------- stepping ---------------------------
    @property
    def step_seq(self):
        """Completed-step counter — the wedge watchdog's progress
        stamp (frozen ⇔ the step loop is blocked or idle)."""
        return self._step_seq

    @property
    def in_step(self):
        """True while a step HOLDS the step lock (doing real work —
        possibly a long first-shape compile).  False + frozen
        step_seq + pending work ⇔ the step loop cannot even ENTER a
        step: the wedge signature."""
        return self._in_step

    def step(self):
        """One scheduler step: admit+prefill, then one decode step for
        every active sequence.  Returns the number of sequences that
        advanced (0 == idle).  Thread-safe; the background worker uses
        exactly this."""
        parked = False
        with self._lock:
            self._in_step = True
            try:
                out = self._step_locked()
            finally:
                self._in_step = False
            if self._handoff:
                parked = self._sweep_handoffs_locked()
        self._step_seq += 1
        if parked and self.on_handoff is not None:
            # outside the step lock by design: the notified collector
            # may take router/transport locks of its own
            self.on_handoff()
        return out

    def _step_locked(self):
        if self._ragged is not None:
            return self._step_ragged()
        if self.prefill_chunk_tokens:
            return self._step_chunked()
        # bounded prefill work per step: at most one batched-prefill
        # chunk's worth of admissions, so queued prompts cannot starve
        # the decode batch of a whole step
        admitted = self.scheduler.admit(limit=self.config.max_prefill_batch)
        self._prefill_admitted(admitted)
        self._reap_deadlines()
        active = self.scheduler.decode_ready()
        if not active:
            self._drain_kv_bytes()
            self._observe_occupancy()
            return 0
        with RecordEvent("generation::decode_step"):
            active = self._ensure_step_capacity()
            if not active:
                return 0
            self._decode_batch(active)
        self.metrics.observe_step()
        self._observe_step_rows(len(active))
        self._drain_kv_bytes()
        self._observe_occupancy()
        return len(active)

    def _observe_step_rows(self, decode_rows, chunk_useful=0,
                           chunk_dispatched=0):
        """Emit the step's row accounting (legacy paths): the decode
        dispatch's useful/padded rows — the fused step's bucket padding
        is exactly the masked dummy work padded_token_waste counts; the
        eager path pads nothing — plus whatever chunk dispatch the
        caller ran.  The ragged path emits its own (waste 0 by
        construction)."""
        if self._fused is not None and decode_rows:
            useful = self._fused.last_rows_useful
            dispatched = self._fused.last_rows_dispatched
        else:
            useful = dispatched = decode_rows
        useful += chunk_useful
        dispatched += chunk_dispatched
        if dispatched:
            self.metrics.observe_step_rows(useful, dispatched,
                                           dispatched - useful)

    def _decode_batch(self, active):
        """One decode dispatch (fused or eager) + sampling for `active`."""
        if self._fused is not None:
            all_greedy, out = self._decode_fused(active)
            if all_greedy:
                self._apply_tokens(active, out)
            else:
                self._apply_logits_batch(active, out)
        else:
            logits = self._decode(active)
            self._apply_logits_batch(active, logits)

    def _step_chunked(self):
        """One legacy chunked-prefill step: admit, a PACK of prefill-
        chunk dispatches (the oldest mid-prefill sequence's chunk
        first, then more prompts' chunks into the step token budget's
        leftover room — the same packing rule as the ragged step, one
        dispatch per chunk here), plus the whole decode batch — every
        step.  There is no token-budget competition: the decode-owed
        stall dance existed to arbitrate the two dispatches a tight
        budget couldn't afford together, and it died when the ragged
        step put both in ONE dispatch; the legacy path simply runs
        everything (decode never stalls), and the budget only sizes
        the pack so short prompts stop queueing behind long ones."""
        self.scheduler.admit(limit=self.config.max_prefill_batch)
        self._reap_deadlines()
        # the budget sizes the PACK, never the oldest prompt's chunk:
        # pre-pack semantics ran one full chunk every step regardless,
        # so a tight explicit budget must not starve prefill — the
        # floor guarantees the head of the line its whole chunk and
        # packs extras only from genuine leftover
        room = (max(self.step_token_budget
                    - len(self.scheduler.decode_ready()),
                    self.prefill_chunk_tokens)
                if self.step_token_budget else None)
        pack = self.scheduler.plan_pack(
            self.prefill_chunk_tokens, room=room,
            max_seqs=None if self.config.prefill_pack else 1)
        advanced = 0
        chunk_u = chunk_d = chunk_dispatched = chunk_syncs = 0
        for state, n in pack:
            if state.slot is None or not state.prefilling:
                continue  # preempted by an earlier pack reservation
            if self._prefill_chunk_step(state, n):
                advanced += 1
                if self._chunk_step is not None:
                    chunk_u += self._chunk_step.last_rows_useful
                    chunk_d += self._chunk_step.last_rows_dispatched
                    chunk_dispatched += 1   # one jitted chunk dispatch
                else:
                    chunk_u += n
                    chunk_d += n  # eager: exact rows
                if not state.prefilling:
                    chunk_syncs += 1  # final chunk: logits materialized
        decoding = self.scheduler.decode_ready()
        if decoding:
            with RecordEvent("generation::decode_step"):
                decoding = self._ensure_step_capacity()
                if decoding:
                    self._decode_batch(decoding)
            if decoding:
                self.metrics.observe_step()
                advanced += len(decoding)
        if chunk_dispatched:
            # the step really issued EXTRA device programs (one per
            # packed chunk, plus decode) — the gauge must say so, or
            # the legacy-vs-ragged dispatches-per-step A/B reads a
            # false 1 vs 1.  A chunk-only step is the pack's dispatches
            # (its host syncs are the final chunks' logits fetches).
            if decoding:
                self.metrics.count_step_extra_dispatches(chunk_dispatched)
            else:
                self.metrics.observe_decode_step(chunk_dispatched,
                                                 chunk_syncs)
        self._observe_step_rows(len(decoding), chunk_u, chunk_d)
        self._drain_kv_bytes()
        self._observe_occupancy()
        return advanced

    # --------------------------- ragged step -------------------------
    def _step_ragged(self):
        """One RAGGED mixed-batch step: the decode batch's single-token
        rows AND a PACK of prefill chunks — MULTIPLE prompts' chunks
        filling the packed axis's leftover room, not one chunk per step
        — in ONE pool-donating dispatch (fused.RaggedStep).  No dummy
        decode rows, no separate chunk dispatch, one executable per
        pages bucket TOTAL; short prompts stop queueing behind long
        ones for TTFT (the RPA packing rule).

        Order mirrors the legacy chunked step: plan and reserve the
        chunks FIRST (a reservation may preempt youngest decode
        sequences — they simply drop out of the decode batch — or even
        a YOUNGER pack member, which then drops out of the pack), then
        the decode capacity check (which may preempt chunkers — their
        freed rows drop out of the pack).

        A pipeline of depth one: the step planned here is enqueued
        BEHIND the step in flight, its decode rows taking their token
        from that step's ids on the device, and only then are the
        tokens of the step in flight read, emitted and accounted — so
        the host's work on a step runs while the device does the one
        before.  The depth is decided a step at a time from what the
        engine sees (`_drain_reason`); at depth 0 a step is dispatched
        and retired in the same call, the sequence this method always
        had.  A call that finds nothing to plan retires the step in
        flight and returns what that advanced."""
        try:
            return self._step_ragged_pipelined()
        except BaseException:
            # a failure with steps enqueued on donated pools: whatever
            # is in flight is lost with them, and both steps' sequences
            # fail as a unit (the worker's contract; `_dispatch_donating`
            # or `_retire` left the cache on fresh storage)
            self._inflight = None
            self._ragged.forget_ids()
            raise

    def _step_ragged_pipelined(self):
        with RecordEvent("generation::schedule"):
            plan = self._plan_ragged()
        if plan is None:
            # planning behind the step in flight would preempt: its
            # tokens first, then today's sequence
            self._retire_inflight("preempt")
            with RecordEvent("generation::schedule"):
                plan = self._plan_ragged()
        decoding, pack, spec_plan = plan
        if not decoding and not pack:
            advanced = self._retire_inflight()
            self._account_step()
            return advanced
        # the host-free loop takes DECODE-ONLY boundaries (no chunk in
        # the pack) whose every row fits the loop's static caps; a page
        # shortfall inside _dispatch_loop rolls back and falls through
        # to the single-step dispatch — the loop is an optimization,
        # never a new failure source
        if (self._loop is not None and decoding and not pack
                and self._loop_ready(decoding)):
            with RecordEvent("generation::loop_step"):
                looped = self._dispatch_loop(decoding, spec_plan)
            if looped is not None:
                advanced, sampled = looped
                if sampled:
                    self.metrics.observe_step()
                self._account_step()
                return advanced
        # the operator's question of a slow step: which requests rode
        # it (handle.seq_id), and on which executable
        with RecordEvent(
                "generation::ragged_step", step=self._step_seq,
                decode=len(decoding), chunk=len(pack),
                pages=lambda: self._ragged.last_pages_bucket,
                seqs=lambda: "/".join(
                    str(s.seq_id)
                    for s in decoding + [c[0] for c in pack])):
            advanced = self._dispatch_ragged(decoding, pack, spec_plan)
        self._account_step()
        return advanced

    def _decode_ready(self):
        """The decode batch of the next step: the scheduler's, less the
        sequences whose token in flight is their last by
        `max_new_tokens` (a finish by length is the one the host knows
        a step ahead)."""
        ready = self.scheduler.decode_ready()
        if self._inflight is None:
            return ready
        ahead = self._inflight.descriptor_of
        return [s for s in ready if s.seq_id not in ahead
                or s.n_generated + 1 < s.request.max_new_tokens]

    def _plan_ragged(self):
        """Everything a ragged step decides before it packs: admission,
        deadlines, the chunk pack and the drafts, their page
        reservations and the decode capacity check.  Returns
        ``(decoding, pack, spec_plan)`` with pack as
        ``[(state, n, start)]``: reserved, still-alive chunks — or
        None, with nothing reserved, where a step is in flight and the
        reservations could not all be met without preempting."""
        admitted = self.scheduler.admit(limit=self.config.max_prefill_batch)
        if not self.prefill_chunk_tokens:
            # no chunking: prompts take the one-shot prefill paths and
            # only decode rides the ragged dispatch
            self._prefill_admitted(admitted)
        self._reap_deadlines()
        # plan the prefill-chunk pack FIRST (exactly the room the
        # spec-off engine would give it), THEN let drafts fill the
        # genuine leftover: drafts are an optimization, and a prompt's
        # TTFT is not theirs to spend — under a tight explicit budget
        # the chunk keeps its full pre-speculation share and the
        # drafts get the scraps, never the other way around.  Rows
        # preempted below simply leave their drafts unused.
        planned = []
        if self.prefill_chunk_tokens:
            room = self.step_token_budget - len(self._decode_ready())
            planned = self.scheduler.plan_pack(
                self.prefill_chunk_tokens, room=room,
                max_seqs=(self._ragged.max_seqs
                          if self.config.prefill_pack else 1))
        if self._inflight is not None and not self._pages_suffice(planned):
            return None
        spec_plan = {}
        if self._spec is not None:
            spec_plan = self.scheduler.plan_spec(
                self._spec, self.spec_tokens,
                room=(self.step_token_budget
                      - len(self.scheduler.decode_ready())
                      - sum(n for _, n in planned)))
        pack = []
        for state, n in planned:
            if state.slot is None or not state.prefilling:
                continue  # preempted by an earlier pack reservation
            start = self._reserve_chunk(state, n)
            if start is not None:
                pack.append((state, n, start))
        decoding = self._ensure_step_capacity()
        # reservations and the capacity check preempt youngest-first —
        # a victim's reserved span died with its pages, so it (and any
        # pack member preempted by a LATER member's reservation) drops
        # out of the pack here
        pack = [(s, n, st) for s, n, st in pack
                if s.slot is not None and s.prefilling]
        return decoding, pack, spec_plan

    def _pages_suffice(self, planned):
        """Whether the planned chunks and a token a decode row can all
        be reserved from what is free or evictable, in both page
        groups: the sum `_ensure_step_capacity` takes, over the chunks
        too.  Asked only with a step in flight, whose tokens are read
        before anything is preempted."""
        cache = self.cache
        spans = ([(s.seq_id, n) for s, n in planned]
                 + [(s.seq_id, 1) for s in self._decode_ready()])
        if sum(cache.pages_needed(sid, n)
               for sid, n in spans) > cache.available_pages:
            return False
        wg = cache.window_group
        return wg is None or sum(
            wg.pages_needed(sid, cache.seq_len(sid) + n)
            for sid, n in spans) <= wg.free_pages

    def _account_step(self):
        """A ragged step's closing counters, under their own span so
        that what the accounting costs is itself visible."""
        with RecordEvent("generation::account"):
            if self.cache.window_group is not None:
                # pages behind every live sequence's window go back to
                # the window group, and its counters out
                self.cache.release_window_pages()
                self.metrics.count_window_pages(
                    *self.cache.window_group.take_counters())
            self._drain_kv_bytes()
            self._observe_occupancy()

    def _drain_reason(self, step):
        """Why `step`, just enqueued, cannot stay in flight — the
        pipeline's depth, decided a step at a time from what the engine
        sees — or None where it can.  `stochastic`: a sampler's token is
        drawn on the host from the logits; `speculation`: the next
        step's rows depend on what this one accepts (and the host-free
        loop fetches inside its own dispatch); `handoff`: a sequence is
        parked, pages and all, the moment its prompt is consumed."""
        if self._spec is not None or self._loop is not None:
            return "speculation"
        if self._handoff:
            return "handoff"
        return None if step.greedy else "stochastic"

    def _retire_inflight(self, reason=None):
        """Read, emit and account the step in flight, if any; `reason`
        says why the pipeline drains here (`generation.pipeline_drains`)
        when it is not for want of work.  Returns the descriptors that
        step advanced, 0 with nothing in flight."""
        step, self._inflight = self._inflight, None
        if step is None:
            return 0
        if reason is not None:
            self.metrics.count_pipeline_drain(reason)
        try:
            self._retire(step)
        finally:
            # the ids are kept for the rows of a step enqueued behind
            # this one, and there is none: with nothing in flight the
            # engine holds no device array but its pools
            self._ragged.forget_ids()
        return step.advanced

    def _dispatch_ragged(self, decoding, pack, spec_plan=None):
        """Pack and enqueue a step, THEN retire the step in flight: the
        decode batch's spans first (slot order — each sequence's
        committed token, or the descriptor of the step in flight that
        holds it, followed by its draft tokens when it speculates this
        step), then each packed chunk's rows consecutively; descriptor i
        covers decode sequence i (len = 1 + drafts), descriptor B + j
        the pack's j-th chunk.  The new step stays in flight unless
        `_drain_reason` says otherwise.  Returns the descriptors it
        advanced."""
        behind, b = self._inflight, len(decoding)
        with RecordEvent("generation::pack"):
            fixed, spec_rows = self._pack_ragged(decoding, pack, spec_plan)
        step = _StepInFlight()
        with RecordEvent("generation::dispatch"):
            step.ids, step.logits, step.counters = \
                self._ragged.dispatch(fixed)
        self._inflight = step
        # host work behind the device: hidden for as long as it is
        # shorter than the kernel
        with RecordEvent("generation::post_dispatch"):
            self._ragged.count_kernel_cells(fixed)
            # the scatter ran inside the dispatch; keep the O(tokens) write
            # bound visible in kv_bytes_moved (comparable across paths)
            self.cache.count_fused_append(self._ragged.last_rows_useful)
            finishing = []  # [(state, descriptor index)]
            for j, (state, n, start) in enumerate(pack):
                state.prefill_pos += n
                state.handle.prefill_chunks = getattr(
                    state.handle, "prefill_chunks", 0) + 1
                self.metrics.count_prefill(n)
                self.metrics.count_chunk()
                self._prewarm_decode(state)
                if state.prefill_pos == len(state.tokens):
                    state.prefilling = False
                    self._register_prefix(state)
                    finishing.append((state, b + j))
            # samplers: every decode row, plus each packed chunk's last
            # row when it just completed its prompt (those logits ARE
            # the first-token logits), each with its descriptor index
            samplers = [(s, i) for i, s in enumerate(decoding)] + finishing
            step.samplers = [(s, di, s.preemptions) for s, di in samplers]
            # {seq_id: descriptor}: whose next token the ids hold
            step.descriptor_of = {s.seq_id: di for s, di in samplers}
            step.greedy = all(s.request.params.greedy for s, _ in samplers)
            step.decode_rows, step.spec_rows = b, spec_rows
            step.advanced = b + len(pack)
            # what the dispatch itself settled is booked here, behind
            # the device (the next step's `pad` overwrites the `last_*`
            # read): one dispatch, and the one host sync it will be
            # read with
            self.metrics.observe_decode_step(
                self._ragged.last_dispatches, 1 if step.samplers else 0)
            self.metrics.observe_collective_bytes(
                self._ragged.last_collective_bytes)
            # zero padded_token_waste by construction: descriptors
            # cover exactly the packed rows; the fixed axis's inert
            # slots are reported by step_row_utilization, not counted
            # as dummy work
            self.metrics.observe_step_rows(
                self._ragged.last_rows_useful,
                self._ragged.last_rows_dispatched, 0)
            # the query-tiling FLOP proxy: score blocks this dispatch
            # computed vs the untiled kernel's bill on the same
            # descriptors, and the grid it was given
            self.metrics.count_score_blocks(
                self._ragged.last_score_blocks,
                self._ragged.last_score_blocks_untiled,
                self._ragged.last_grid_cells)
        if behind is not None:
            self.metrics.count_step_overlapped()
            self._retire(behind)
        reason = self._drain_reason(step)
        if reason is not None:
            self._retire_inflight(reason)
        return step.advanced

    def _retire(self, step):
        """A dispatched step's host half: the single host sync — the
        wait for the device: the ids (with speculation the [S, 3] int
        block) of an all-greedy step, the logits (augmented [S, V + 3])
        when any sampler is stochastic; a mid-prompt chunk-only step
        fetches NOTHING — then sampling and the pushes to the handles,
        then the step's own counters.  A row is applied only to the
        sequence it was dispatched for: one that a stop, a deadline or
        a cancel retired while the row was in flight (or that was
        preempted and admitted again) never receives it; the row is
        dropped and counted, and its over-reserved position went back
        with the sequence's pages."""
        samplers = [(s, di) for s, di, stamp in step.samplers
                    if s.slot is not None and s.preemptions == stamp]
        fetched = None
        if step.samplers:
            with RecordEvent("generation::fetch"):
                try:
                    fetched = np.asarray(step.ids if step.greedy
                                         else step.logits)
                except BaseException:
                    # the device failed the step, and a later one may
                    # already sit on its donated pools: fresh storage,
                    # as `_dispatch_donating` leaves it
                    self.cache.reset_pools()
                    raise
        with RecordEvent("generation::emit"):
            if not samplers:
                sampled = 0
            elif self._spec is not None:
                sampled = self._apply_ragged_spec(
                    samplers, step.spec_rows, step.decode_rows, fetched,
                    step.greedy)
            else:
                states = [s for s, _ in samplers]
                picked = fetched[[di for _, di in samplers]]
                if step.greedy:
                    self._apply_tokens(states, picked)
                else:
                    self._apply_logits_batch(states, picked)
                sampled = len(samplers)
        with RecordEvent("generation::account"):
            if sampled:
                self.metrics.observe_step()
            self.metrics.count_overlap_rows_discarded(
                len(step.samplers) - len(samplers))
            if step.counters is not None:
                # what the model counted inside THIS step, which the
                # fetch above has waited for: never a later step's
                # block, whose read would wait for that step
                self.metrics.count_model_step(
                    self._ragged.step_counters, np.asarray(step.counters))

    def _pack_ragged(self, decoding, pack, spec_plan):
        """The host half of a ragged dispatch: reserve the decode rows
        (and their drafts), lay every descriptor's rows on the packed
        axis, and pad to the executable's fixed shapes.  Returns
        ``(fixed, spec_rows)``: RaggedStep.pad's arguments and
        ``{decode row: its drafts}``."""
        seq_ids, d_tokens, positions = self._reserve_decode_rows(decoding)
        # speculation: EXTEND a drafting row's reservation past its
        # guaranteed decode token.  The capacity check only vouched for
        # one token per row, so an extension that finds no page simply
        # drops that row's drafts — speculation never preempts a
        # sequence and never fails a request over pages
        spec_rows = {}
        if spec_plan:
            for i, s in enumerate(decoding):
                drafts = spec_plan.get(s.seq_id)
                if not drafts:
                    continue
                try:
                    self.cache.reserve(s.seq_id, len(drafts))
                except OutOfPagesError:
                    continue
                if self.prefix_cache_enabled:
                    # the draft span's COW guard, mirroring the decode
                    # rows' in _reserve_decode_rows (reserve just
                    # privatized any shared tail page)
                    self.cache.check_span_writable(
                        s.seq_id, int(positions[i]) + 1, len(drafts))
                spec_rows[i] = drafts
        tokens = []
        desc_ids = []
        spans = []     # descriptor j's (first position, row count)
        # a decode row whose sequence sampled in the step in flight has
        # no token on the host yet: `src` names the descriptor of that
        # step whose id it is (no drafts ride such a step, so decode
        # row i is packed row i)
        ahead = (self._inflight.descriptor_of
                 if self._inflight is not None else {})
        src = [ahead.get(s.seq_id, -1) for s in decoding] if ahead else None
        for i, s in enumerate(decoding):
            drafts = spec_rows.get(i, ())
            tokens.append(int(d_tokens[i]))
            tokens += drafts
            spans.append((int(positions[i]), 1 + len(drafts)))
            desc_ids.append(s.seq_id)
        for state, n, start in pack:
            # COW-safe donation chain for each chunk span, mirroring the
            # decode rows' guard in _reserve_decode_rows
            self.cache.check_span_writable(state.seq_id, start, n)
            tokens += state.tokens[start:start + n]
            spans.append((start, n))
            desc_ids.append(state.seq_id)
        # kv_lens straight off the cache: a decode row's length already
        # includes its reserved token(s) — drafts included — each
        # chunk's its whole span; and pt row j IS descriptor j's table,
        # so the scatter targets below index it directly (one table
        # walk per step, not two)
        pt, kv_lens = self.cache.gather_block_tables(desc_ids)
        t_real = len(tokens)
        ps = self.cache.page_size
        # one vectorized fill for EVERY span shape — len-1 decode rows,
        # multi-row draft spans, chunk runs: descriptor j owns packed
        # rows [starts[j], starts[j] + lens[j]) at positions
        # span_pos0[j] + offset-within-span (O(1) numpy calls whatever
        # the batch size — the spec-off hot path pays no python loop)
        lens = np.asarray([n for _, n in spans], np.int32)
        span_pos0 = np.asarray([start for start, _ in spans], np.int32)
        starts = np.zeros((len(spans),), np.int32)
        np.cumsum(lens[:-1], out=starts[1:])
        pos_all = (np.repeat(span_pos0, lens)
                   + np.arange(t_real, dtype=np.int32)
                   - np.repeat(starts, lens)).astype(np.int32)
        desc_of_row = np.repeat(np.arange(len(spans), dtype=np.int32),
                                lens)
        pages = pt[desc_of_row, pos_all // ps]
        rows = pos_all % ps
        packed = (np.asarray(tokens, np.int32), pos_all, pages, rows, pt,
                  starts, lens, kv_lens)
        if self.cache.window_group is not None:
            # the same rows and descriptors through the window group's
            # tables: where the window layers write and read them
            w_pt = self.cache.window_group.gather_tables(desc_ids,
                                                         pt.shape[1])
            packed += ((w_pt[desc_of_row, pos_all // ps], w_pt),)
        extra = {}
        if src is not None:
            extra["src"] = src + [-1] * (t_real - len(src))
        if self.cache.slot_state is not None:
            # where each descriptor's recurrent state lives: its slot
            extra["state_slots"] = ([s.slot for s in decoding]
                                    + [state.slot for state, _, _ in pack])
        return self._ragged.pad(*packed, **extra), spec_rows

    def _apply_ragged_spec(self, samplers, spec_rows, b, fetched, greedy):
        """The speculative step's sampling half, over its ONE host
        fetch: the [S, 3] int block (last-row argmax, accepted count,
        bonus) of an all-greedy step, the [S, V + 3] augmented logits
        when any sampler is stochastic.  Per ``(state, descriptor)``
        sampler exactly one of: accepted drafts + bonus (speculating
        rows, the first `b` descriptors), the last-row argmax (plain
        greedy rows and finishing greedy chunks), or batched host
        sampling from the logits columns (stochastic rows).  Returns
        the tokens emitted."""
        if greedy:
            ints, logits_h = fetched, None
        else:
            vocab = int(self.model.vocab_size)
            logits_h = fetched[:, :vocab]
            # the appended int columns are exact in f32 (ids < vocab,
            # accepted <= spec_tokens — both far under 2**24)
            ints = fetched[:, vocab:].astype(np.int64)
        ids_col, acc_col, bonus_col = ints[:, 0], ints[:, 1], ints[:, 2]
        emitted = 0
        stoch = []   # (state, descriptor): one batched host sample
        for s, di in samplers:
            if not s.request.params.greedy:
                stoch.append((s, di))
                continue
            drafts = spec_rows.get(di) if di < b else None
            if drafts:
                emitted += self._apply_spec_row(
                    s, drafts, int(acc_col[di]), int(bonus_col[di]))
            elif s.n_generated >= s.request.max_new_tokens:
                self._finish(s, "length")
            else:
                self._apply_token(s, int(ids_col[di]))
                emitted += 1
        if stoch:
            self._apply_logits_batch([s for s, _ in stoch],
                                     logits_h[[di for _, di in stoch]])
            emitted += len(stoch)
        return emitted

    def _apply_spec_row(self, state, drafts, accepted, bonus):
        """Retire one speculating row's verified tokens.  The cache is
        truncated FIRST — the rejected draft tail leaves before any
        token is streamed, so a stop/length finish inside the apply
        loop (which frees the pages wholesale) can never race a
        rewind, and a surviving row holds exactly len(tokens) - 1
        resident positions, the decode invariant.  The accepted drafts
        and the bonus token then stream one at a time through the
        NORMAL per-token gate (_apply_token) — stop tokens, multi-
        token stop sequences, and max_new_tokens clip the emission at
        exactly the token the non-speculative engine would have
        stopped at, so speculation can never stream past a stop.
        Returns tokens emitted."""
        accepted = max(0, min(int(accepted), len(drafts)))
        rewound = len(drafts) - accepted
        if rewound:
            self.cache.truncate(
                state.seq_id,
                self.cache.seq_len(state.seq_id) - rewound)
        self.metrics.count_spec(len(drafts), accepted, rewound)
        emitted = 0
        for tok in list(drafts[:accepted]) + [int(bonus)]:
            if state.slot is None:
                break   # a stop/length finish retired the row mid-run
            before = state.n_generated
            self._apply_token(state, int(tok))
            emitted += state.n_generated - before
        return emitted

    def _loop_ready(self, decoding):
        """Row-level eligibility for the host-free loop at this
        decode-only boundary: every row must fit the loop executable's
        STATIC stop caps (caps are trace constants — a row past them
        would silently drop its stop conditions), have a token to
        generate, and have position headroom for the loop's whole
        write horizon.  Any misfit row sends the WHOLE boundary down
        the single-step path — per-row mixing would reintroduce the
        per-token fetch for the loop rows too, since the step's one
        fetch is the step's latency floor either way."""
        lp = self._loop
        horizon = lp.loop_steps + lp.spec_tokens
        limit = int(self.model.max_positions) - 1
        for s in decoding:
            req = s.request
            p = req.params
            if (req.max_new_tokens - s.n_generated < 1
                    or len(req.stop_tokens) > lp.max_stop_ids
                    or len(p.stop_sequences) > lp.max_stop_seqs
                    or p.max_stop_len > lp.max_stop_len
                    or len(s.tokens) - 1 + horizon > limit):
                return False
        return True

    def _dispatch_loop(self, decoding, spec_plan):
        """One host-free loop dispatch: N ragged decode iterations with
        on-device sampling and stop matching, ONE host fetch
        (fused.LoopedRaggedStep).  Reserves the loop's whole write
        horizon per row up front (N + that row's drafts — the furthest
        position any iteration can scatter to); a shortfall rolls back
        every reservation and returns None, and the caller falls
        through to the single-step dispatch.  After the fetch, each
        row's pre-gated tokens stream through the NORMAL per-token
        gate (_apply_token — device and host run the same gate order,
        so the re-check is a no-op by construction and the one-gate
        invariant stays literally true), the SampleStream counter
        advances to the device's value, and survivors truncate back to
        final_pos — resident == len(tokens) - 1, the decode invariant.
        Returns ``(descriptors_advanced, tokens_emitted)``."""
        lp = self._loop
        n_steps, kk = lp.loop_steps, lp.spec_tokens
        kd = max(kk, 1)
        b = len(decoding)
        drafts = np.zeros((b, kd), np.int32)
        dlens = np.zeros((b,), np.int32)
        if spec_plan:
            for i, s in enumerate(decoding):
                d = spec_plan.get(s.seq_id)
                if d:
                    d = list(d)[:kk]
                    drafts[i, :len(d)] = d
                    dlens[i] = len(d)
        reserved = []   # rollback ledger: (seq_id, pre-reserve length)
        for i, s in enumerate(decoding):
            need = n_steps + int(dlens[i])
            try:
                p0 = self.cache.reserve(s.seq_id, need)
            except OutOfPagesError:
                for sid, back in reserved:
                    self.cache.truncate(sid, back)
                return None
            reserved.append((s.seq_id, p0))
            if self.prefix_cache_enabled:
                # the COW guard over the whole horizon, mirroring
                # _reserve_decode_rows (reserve just privatized any
                # shared tail page)
                self.cache.check_span_writable(s.seq_id, p0, need)
        pt, _ = self.cache.gather_block_tables(
            [s.seq_id for s in decoding])
        ms, ns, ls = lp.max_stop_ids, lp.max_stop_seqs, lp.max_stop_len
        cur_tok = np.asarray([s.tokens[-1] for s in decoding], np.int32)
        cur_pos = np.asarray([p0 for _, p0 in reserved], np.int32)
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        top_ps = np.ones((b,), np.float32)
        seeds = np.zeros((b,), np.int32)
        counters = np.zeros((b,), np.int32)
        remaining = np.zeros((b,), np.int32)
        stop_ids = np.full((b, ms), -1, np.int32)
        stop_seqs = np.full((b, ns, ls), -1, np.int32)
        stop_seq_lens = np.zeros((b, ns), np.int32)
        tail = np.full((b, ls - 1), -1, np.int32)
        for i, s in enumerate(decoding):
            req = s.request
            p = req.params
            temps[i] = p.temperature
            top_ks[i] = p.top_k or 0
            top_ps[i] = 1.0 if p.top_p is None else p.top_p
            seeds[i] = np.int32(np.uint32(s.rng.seed))
            counters[i] = np.int32(np.uint32(s.rng.counter))
            remaining[i] = req.max_new_tokens - s.n_generated
            st = list(req.stop_tokens)
            stop_ids[i, :len(st)] = st
            for j, sq in enumerate(p.stop_sequences):
                stop_seqs[i, j, ls - len(sq):] = sq
                stop_seq_lens[i, j] = len(sq)
            take = min(s.n_generated, ls - 1)
            if take:
                tail[i, ls - 1 - take:] = s.tokens[len(s.tokens) - take:]
        res = lp.step(cur_tok, cur_pos, pt, temps, top_ks, top_ps,
                      seeds, counters, remaining, stop_ids, stop_seqs,
                      stop_seq_lens, tail, drafts, dlens)
        iters = lp.last_iters
        sampled = 0
        wasted = 0
        writes = 0
        for i, s in enumerate(decoding):
            row = res[i]
            ne = int(row[n_steps + kk])
            fin = int(row[n_steps + kk + 1])
            fin_it = int(row[n_steps + kk + 2])
            final_pos = int(row[n_steps + kk + 3])
            s.rng.counter = int(row[n_steps + kk + 4]) & 0xFFFFFFFF
            emitted = [int(t) for t in row[:ne]]
            if dlens[i]:
                # the verify rule makes the bonus token differ from
                # the draft it replaced, so the emitted stream's
                # common prefix with the drafts IS the accepted count
                # (undercounts only when a stop clips mid-draft — the
                # row retires that dispatch anyway)
                acc = 0
                for j in range(min(int(dlens[i]), len(emitted))):
                    if emitted[j] != int(drafts[i, j]):
                        break
                    acc += 1
                self.metrics.count_spec(int(dlens[i]), acc,
                                        int(dlens[i]) - acc)
            # iterations this row actually decoded in (its KV writes),
            # vs iterations it sat finished while the batch ran on
            active_iters = (fin_it + 1) if fin else iters
            writes += active_iters + int(dlens[i])
            if fin:
                wasted += iters - active_iters
            # truncate FIRST (the _apply_spec_row ordering): the
            # reserved-but-unwritten tail leaves before any token
            # streams, so a finish inside the apply loop (which frees
            # the pages wholesale) can never race the rewind
            self.cache.truncate(s.seq_id, final_pos)
            for tok in emitted:
                if s.slot is None:
                    break
                self._apply_token(s, tok)
            sampled += len(emitted)
            if s.slot is not None and fin == 1:
                # the device withheld the stop-completing token,
                # exactly like the host gate; finish the row here
                self._finish(s, "stop")
        # the in-trace scatters, kept visible in kv_bytes_moved: one
        # write per active iteration per row, plus iteration 0's draft
        # rows
        self.cache.count_fused_append(writes)
        self.metrics.observe_decode_step(lp.last_dispatches,
                                         lp.last_syncs)
        self.metrics.observe_loop(sampled, lp.last_syncs,
                                  iters < n_steps, wasted)
        self.metrics.observe_collective_bytes(lp.last_collective_bytes)
        self.metrics.observe_step_rows(lp.last_rows_useful,
                                       lp.last_rows_dispatched, 0)
        return b, sampled

    def run_until_idle(self, max_steps=100000):
        """Drive step() until queue+slots drain (tests/benchmarks)."""
        steps = 0
        while (self.scheduler.active() or self.scheduler.pending_count()):
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"not idle after {max_steps} steps")
        with self._lock:
            # a step whose every row rode in vain may still be in flight
            self._retire_inflight()
        return steps

    # --------------------------- internals --------------------------
    def _prefill_admitted(self, states):
        """Prefill newly admitted sequences, batched: group by padded-
        length bucket, then run chunks of <= max_prefill_batch through
        one model call each.  Models without `prefill_batch` fall back
        to the per-sequence path.  WARM sequences (a prefix-cache hit
        advanced prefill_pos at admission) cannot ride the one-shot
        paths — those always start at token 0 — so they take the
        suffix-resume path instead."""
        if not states:
            return
        warm = [s for s in states if s.prefill_pos > 0]
        for state in warm:
            self._prefill_suffix(state)
        states = [s for s in states if s.prefill_pos == 0]
        if not states:
            return
        if self.prefill_cache is None:
            for state in states:
                self._prefill(state)
            return
        groups = {}
        for state in states:
            try:
                bucket = self._bucketer.length_bucket(len(state.tokens))
            except RequestTooLargeError:
                # beyond the explicit length menu — a long prompt, or an
                # accepted sequence that GREW past the top bucket and is
                # re-prefilling after preemption.  Serve it unbatched at
                # its exact shape (one-off compile) rather than failing:
                # admission is the only rejection point, and preemption
                # must never change a request's outcome
                self._prefill(state)
                continue
            groups.setdefault(bucket, []).append(state)
        size = self.config.max_prefill_batch
        for bucket in sorted(groups):
            group = groups[bucket]
            for i in range(0, len(group), size):
                self._prefill_chunk(group[i:i + size])

    def _prefill_chunk(self, states):
        """One batched prefill: reserve every span, pad prompts to the
        (batch, length) bucket, one model call, scatter the K/V spans
        into the pool (padding positions are dropped, never written),
        and sample each sequence's first token from its own row."""
        ready = []
        for state in states:
            try:
                start = self.cache.reserve(state.seq_id, len(state.tokens))
            except OutOfPagesError as e:
                # a lone sequence that outgrew the whole pool: typed
                # failure (admit() covers every other capacity case)
                self.scheduler.retire(state)
                state.handle.set_exception(e)
                continue
            ready.append((state, start))
        if not ready:
            return
        with RecordEvent("generation::prefill"):
            tokens, lengths = self._bucketer.pad_token_batch(
                [state.tokens for state, _ in ready])
            b_real = len(ready)
            # padded batch rows prefill a 1-token dummy (row 0 gather
            # stays in bounds); their K/V and logits are discarded
            lengths_padded = np.ones((tokens.shape[0],), np.int32)
            lengths_padded[:b_real] = lengths
            exe = self.prefill_cache.get([tokens, lengths_padded])
            last_logits, k, v = exe(tokens, lengths_padded)
            self.cache.write_prefill_batch(
                [state.seq_id for state, _ in ready],
                [start for _, start in ready], lengths,
                k[:b_real], v[:b_real])
        last_logits = np.asarray(last_logits)  # one device->host transfer
        for state, _ in ready:
            state.prefilling = False
            state.prefill_pos = len(state.tokens)
            self.metrics.count_prefill(len(state.tokens))
            self._register_prefix(state)
        # prefill's last-position logits ARE the next-token logits: new
        # prompts sample their first token here (vectorized greedy
        # argmax), and a preempted sequence resumes exactly where its
        # decode left off
        self._apply_logits_batch([state for state, _ in ready],
                                 last_logits[:b_real])

    def _prefill(self, state):
        try:
            with RecordEvent("generation::prefill"):
                tokens = np.asarray(state.tokens, np.int32)
                last_logits, k, v = self.model.prefill(tokens)
                self.cache.append_prefill(state.seq_id, k, v)
        except OutOfPagesError as e:
            # a lone sequence that outgrew the whole pool: typed failure
            self.scheduler.retire(state)
            state.handle.set_exception(e)
            return
        state.prefilling = False
        state.prefill_pos = len(state.tokens)
        self.metrics.count_prefill(len(state.tokens))
        self._register_prefix(state)
        # prefill's last-position logits ARE the next-token logits: new
        # prompts sample their first token here, and a preempted sequence
        # resumes exactly where its decode left off
        self._on_logits(state, last_logits)

    def _prefill_suffix(self, state):
        """Warm-start prefill: positions [0, prefill_pos) are ALIASED
        cached pages (adopted at admission, zero bytes moved); only the
        divergent suffix is computed, as one eager prefill_chunk call
        attending over aliased prefix + suffix through the page table.
        The suffix's last-position logits ARE the next-token logits,
        exactly as in full prefill — a warm hit changes how much
        prefill runs, never what the sequence samples.  (The chunked
        engine mode never lands here: its chunk loop resumes at
        prefill_pos natively.)"""
        n = len(state.tokens) - state.prefill_pos
        try:
            # reserve may copy-on-write the clipped tail page (counted
            # in pages_needed) — after this every written page is
            # private, which _check_span enforces
            start = self.cache.reserve(state.seq_id, n)
        except OutOfPagesError as e:
            self.scheduler.retire(state)
            state.handle.set_exception(e)
            return
        assert start == state.prefill_pos, \
            "cache length diverged from matched prefix"
        with RecordEvent("generation::prefill"):
            logits_last = self._prefill_chunk_eager(
                state, state.tokens[start:], start)
        state.prefilling = False
        state.prefill_pos = len(state.tokens)
        self.metrics.count_prefill(n)
        self._register_prefix(state)
        self._on_logits(state, logits_last)

    def _register_prefix(self, state):
        """Index the completed prompt's full pages for future matches
        (no-op when prefix caching is off).  Registration happens at
        prefill COMPLETION — not retire — so concurrent requests
        sharing the prompt alias it while this sequence still decodes.
        Only PROMPT tokens are indexed here; the decode tail joins the
        index at retire (_register_decode_tail), when the generated
        pages are final."""
        if self.prefix_cache_enabled:
            self.metrics.count_prefix_registered(self.cache.register_prefix(
                state.seq_id, state.tokens[:len(state.request.prompt)]))

    def _register_decode_tail(self, state):
        """Decode-tail indexing: at retire, extend the sequence's
        cached run over full pages of GENERATED tokens too.  A
        multi-turn client that re-sends the assistant turn verbatim
        (prompt_2 = prompt_1 + answer_1 + user_2) then warm-hits past
        the old prompt into the answer it was just streamed — the
        ROADMAP decode-tail follow-on.  Valid for the same reason
        prompt pages are: causal attention makes a position's K/V a
        function of the token prefix alone, and a retired sequence's
        pages are final.  register_prefix clips to full pages AND to
        the cache length, so the newest sampled token (never decoded,
        so never written) and a stop-finish's unappended stop token
        are naturally excluded."""
        if self.prefix_cache_enabled and self.cache.has(state.seq_id):
            self.metrics.count_prefix_registered(
                self.cache.register_prefix(state.seq_id, state.tokens))

    # ------------------------ chunked prefill -----------------------
    def _reserve_chunk(self, state, n):
        """Grow `state`'s reservation by its next `n` chunk tokens,
        preempting youngest-others on page shortage (never the chunker
        itself — preempting it to feed itself would free nothing it can
        keep).  Returns the span start, or None after a typed failure
        retired the sequence (the pool cannot hold its prefix even
        alone).  Shared by the legacy chunk dispatch and the ragged
        step's chunk packing."""
        while True:
            try:
                start = self.cache.reserve(state.seq_id, n)
                break
            except OutOfPagesError as e:
                victim = self.scheduler.preempt_youngest(exclude=state)
                if victim is not None:
                    self.metrics.count_preempted()
                    continue
                # even with every other sequence preempted the pool
                # cannot hold this prefix: typed failure
                self.scheduler.retire(state)
                state.handle.set_exception(e)
                return None
        assert start == state.prefill_pos, \
            "cache length diverged from prefill progress"
        return start

    def _prefill_chunk_step(self, state, n):
        """Dispatch ONE prefill chunk for `state`: reserve `n` tokens
        (incremental reservation growth — preempting youngest-others on
        page shortage), run the chunk through the jitted
        ChunkedPrefillStep or the eager attend path, and on the FINAL
        chunk sample the first token from the chunk's last-position
        logits (they ARE the next-token logits, exactly as in full
        prefill).  Returns True when the chunk ran."""
        start = self._reserve_chunk(state, n)
        if start is None:
            return False
        tokens = state.tokens[start:start + n]
        with RecordEvent("generation::prefill"):
            if self._chunk_step is not None:
                logits_last = self._chunk_step.run(state.seq_id, tokens,
                                                   start)
                # the jitted chunk scatters in-trace; count the O(tokens)
                # write bound anyway so kv_bytes_moved / kv_prefill_bytes
                # stay comparable across prefill paths (same contract as
                # the fused decode step)
                self.cache.count_fused_append(n)
                self.metrics.observe_collective_bytes(
                    self._chunk_step.last_collective_bytes)
            else:
                logits_last = self._prefill_chunk_eager(state, tokens,
                                                        start)
        state.prefill_pos += n
        self.metrics.count_prefill(n)
        self.metrics.count_chunk()
        self._prewarm_decode(state)
        if state.prefill_pos == len(state.tokens):
            state.prefilling = False
            self._register_prefix(state)
            # the ONLY chunk logits ever materialized: mid-prompt chunks
            # return unmaterialized device values (ChunkedPrefillStep),
            # so a streaming prompt costs zero host syncs until here
            self._on_logits(state, np.asarray(logits_last))
        return True

    def _prefill_chunk_eager(self, state, tokens, start):
        """The eager chunk path (the bitwise oracle, mirrors _decode):
        the model projects the chunk, the engine's attend callback
        writes its K/V span into the paged pool (per layer) and attends
        over prefix + chunk read back through the cache — so the jitted
        path's scatter-then-gather semantics hold here too (reduced-
        precision pools round the chunk keys at storage in BOTH
        paths)."""
        from .decode_attention import chunk_prefill_attention_reference

        seq_id = state.seq_id
        n = len(tokens)

        def attend(layer, q, k_new, v_new):
            self.cache.write_prefill_tokens(seq_id, start, layer,
                                            k_new, v_new)
            k_all, v_all = self.cache.gather_prefix(seq_id, layer,
                                                    start + n)
            return chunk_prefill_attention_reference(q, k_all, v_all,
                                                     start)

        return np.asarray(
            self.model.prefill_chunk(np.asarray(tokens, np.int32),
                                     start, attend))

    def prewarm_decode(self, batch_rows, pages_cols, greedy=True):
        """Pre-compile the fused decode executable for a (batch, pages,
        greedy) signature without dispatching anything — benchmarks use
        this to move bucket compiles OUT of the measured window
        (tools/gen_bench.py), and the chunked-prefill path calls the
        same machinery automatically for the bucket a mid-prefill
        sequence will land in.  No-op on the eager decode path.
        Returns True when this call actually compiled (counted in
        decode_compiles_total with the `prewarm` tag,
        decode_compiles_prewarm).  On the ragged path the pages bucket
        is the WHOLE signature — batch_rows and greedy are ignored
        (the one executable serves every batch size and sampling
        mix)."""
        if self._ragged is not None:
            try:
                compiled = self._ragged.prewarm(pages_cols)
            except RequestTooLargeError:
                return False
            if compiled:
                self.metrics.count_decode_prewarm()
            return compiled
        if self._fused is None:
            return False
        try:
            compiled = self._fused.prewarm(batch_rows, pages_cols, greedy)
        except RequestTooLargeError:
            return False  # past the bucket menu: nothing to pre-warm
        if compiled:
            self.metrics.count_decode_prewarm()
        return compiled

    def _prewarm_decode(self, state):
        """Decode-bucket pre-warm: while `state` is mid-prefill, compile
        the executable its first decode step will land in, so the
        prefill->decode seam pays no retrace — the fused (batch bucket,
        pages bucket, greedy) signature, or on the ragged path the
        pages bucket alone (the only signature axis).  At most once per
        prefill."""
        if (self._fused is None and self._ragged is None) \
                or state.prewarmed or not state.prefilling:
            return
        state.prewarmed = True
        decoding = self.scheduler.decode_ready()
        batch_rows = len(decoding) + 1
        pages = [len(self.cache.page_table(s.seq_id)) for s in decoding]
        pages.append(math.ceil((len(state.tokens) + 1)
                               / self.cache.page_size))
        greedy = (state.request.params.greedy
                  and all(s.request.params.greedy for s in decoding))
        self.prewarm_decode(batch_rows, max(pages), greedy)

    def _reap_deadlines(self):
        now = time.monotonic()
        for state in self.scheduler.active():
            if state.request.expired(now):
                self.scheduler.retire(state)
                state.request.reject_expired()
                self.metrics.count_rejected_deadline()

    def _ensure_step_capacity(self):
        """Reserve-ability check for one token per decode-ready
        sequence; preempts youngest-first (mid-prefill slot-holders are
        preemption candidates too — their pages are the cheapest to
        reclaim), ONE victim at a time with the shortfall recomputed
        after each (a victim's own page need leaves the books with it —
        a batchwide shortfall computed up front would preempt too much
        or give up while preemption could still succeed).  Returns the
        surviving decode batch (slot order)."""
        while True:
            active = self._decode_ready()
            if not active:
                return active
            need = sum(self.cache.pages_needed(s.seq_id, 1) for s in active)
            # available = free + evictable cached prefix runs: reserve()
            # evicts refcount-0 cache pages (LRU) before failing, so a
            # resident prefix cache is never a reason to preempt a live
            # sequence
            wg = self.cache.window_group
            if need <= self.cache.available_pages and (
                    wg is None or sum(
                        wg.pages_needed(s.seq_id,
                                        self.cache.seq_len(s.seq_id) + 1)
                        for s in active) <= wg.free_pages):
                return active
            victim = self.scheduler.preempt_youngest()
            if victim is not None:
                self.metrics.count_preempted()
                continue
            # a lone sequence the pool cannot grow: typed failure
            lone = active[0]
            self.scheduler.retire(lone)
            lone.handle.set_exception(OutOfPagesError(
                f"sequence of {len(lone.tokens)} tokens needs another "
                f"page and the pool ({self.cache.num_pages} pages of "
                f"{self.cache.page_size}) has none free even with every "
                f"other sequence preempted"))

    def _reserve_decode_rows(self, active):
        """Reserve this step's token per decode sequence and gather the
        per-row inputs (seq ids, last tokens, positions) — ONE home for
        the reserve + COW-guard + token-gather contract, shared by the
        legacy decode paths and the ragged pack.  The COW guard: the
        in-trace scatter must never land in a prefix-shared page —
        reserve() just privatized each tail page, verified host-side
        here (only meaningful, and only paid, when sharing can exist
        at all)."""
        seq_ids = [s.seq_id for s in active]
        positions = np.asarray(
            [self.cache.reserve(s.seq_id, 1) for s in active], np.int32)
        if self.prefix_cache_enabled:
            for sid, pos in zip(seq_ids, positions):
                self.cache.check_span_writable(sid, int(pos), 1)
        tokens = np.asarray([s.tokens[-1] for s in active], np.int32)
        return seq_ids, tokens, positions

    def _decode_inputs(self, active):
        """Reserve this step's token per sequence and batch the step
        inputs (page tables/lengths cannot change within the step —
        every page it touches was just reserved)."""
        seq_ids, tokens, positions = self._reserve_decode_rows(active)
        pt, lens = self.cache.gather_block_tables(seq_ids)
        return seq_ids, tokens, positions, pt, lens

    def _decode(self, active):
        seq_ids, tokens, positions, pt, lens = self._decode_inputs(active)
        on_device = isinstance(self.cache, DeviceKVPool)
        counts = {"dispatches": 0, "syncs": 0}

        def attend(layer, q, k_new, v_new):
            # one batched write per layer: host backend copies to numpy
            # (a device->host fetch of the step's K/V), DeviceKVPool
            # runs a single donated scatter dispatch (O(B) tokens)
            self.cache.write_decode_tokens(seq_ids, positions, layer,
                                           k_new, v_new)
            if on_device:
                counts["dispatches"] += 1
            else:
                counts["syncs"] += 1
            # layer_pools hands device-resident pools straight through —
            # the host backend uploads O(pool) here, which is exactly
            # what generation.kv_bytes_moved makes visible
            k_pool, v_pool = self.cache.layer_pools(layer)
            ks, vs = self.cache.layer_scales(layer)
            counts["dispatches"] += 1
            return paged_decode_attention(
                q, k_pool, v_pool, pt, lens,
                use_kernel=self._use_kernel,
                layout=self.cache.pool_layout, k_scale=ks, v_scale=vs)

        logits = np.asarray(self.model.decode(tokens, positions, attend))
        counts["syncs"] += 1  # the [B, V] logits fetch
        self.metrics.observe_decode_step(counts["dispatches"],
                                         counts["syncs"])
        return logits

    def _decode_fused(self, active):
        """One fused dispatch for the whole step: returns
        ``(all_greedy, out)`` where `out` is [B] int32 token ids when
        every live request is greedy (argmax ran on device) else the
        [B, V] logits block."""
        _, tokens, positions, pt, lens = self._decode_inputs(active)
        all_greedy = all(s.request.params.greedy for s in active)
        out = self._fused.step(tokens, positions, pt, lens, all_greedy)
        # the scatter ran inside the dispatch; keep the O(tokens) write
        # bound visible in kv_bytes_moved (comparable across paths)
        self.cache.count_fused_append(len(active))
        self.metrics.observe_decode_step(self._fused.last_dispatches,
                                         self._fused.last_syncs)
        self.metrics.observe_collective_bytes(
            self._fused.last_collective_bytes)
        return all_greedy, out

    def _on_logits(self, state, logits_row):
        """Sample the next token for `state`, stream it, and finish the
        sequence when a stop condition fires (the per-row path: single
        prefill and one-off fallbacks; batches go through
        _apply_logits_batch)."""
        req = state.request
        if state.n_generated >= req.max_new_tokens:
            self._finish(state, "length")
            return
        with RecordEvent("generation::sample"):
            token = sample_token(np.asarray(logits_row), req.params,
                                 state.rng)
        self._apply_token(state, token)

    def _apply_token(self, state, token):
        """Stream one already-sampled token and retire on stop/length.

        Stop conditions are checked BEFORE the token is appended or
        streamed: single stop tokens as always, and multi-token
        SamplingParams.stop_sequences by suffix-matching the generated
        stream — a token that would COMPLETE a stop sequence is
        clipped exactly like a single stop token (the sequence's
        earlier tokens were necessarily already streamed; only the
        completing one can be withheld).  Every engine path — eager,
        fused, ragged, and the speculative accept loop — emits tokens
        through this one gate, so speculation can never stream past a
        stop the non-speculative oracle would have honored."""
        req = state.request
        if token in req.stop_tokens:
            self._finish(state, "stop")
            return
        window = req.params.max_stop_len
        if window:
            gen_len = state.n_generated
            take = min(gen_len, window - 1)
            tail = (state.tokens[len(state.tokens) - take:] if take
                    else []) + [token]
            for seq in req.params.stop_sequences:
                if len(tail) >= len(seq) \
                        and tuple(tail[len(tail) - len(seq):]) == seq:
                    self._finish(state, "stop")
                    return
        state.tokens.append(token)
        state.n_generated += 1
        state.handle._push_token(token)
        self.metrics.count_token()
        if state.n_generated >= req.max_new_tokens:
            self._finish(state, "length")

    def _apply_logits_batch(self, states, logits):
        """Sample + apply one token per row of a [B, V] logits block.
        Greedy rows share ONE vectorized argmax (sample_tokens_batch);
        stochastic rows keep their per-request RNGs — token-identical
        to the per-row path by construction."""
        logits = np.asarray(logits)
        live = []
        for i, state in enumerate(states):
            # length-finish before sampling (max_new_tokens == 0 lands
            # here straight from prefill)
            if state.n_generated >= state.request.max_new_tokens:
                self._finish(state, "length")
            else:
                live.append((i, state))
        if not live:
            return
        with RecordEvent("generation::sample"):
            tokens = sample_tokens_batch(
                logits[[i for i, _ in live]],
                [s.request.params for _, s in live],
                [s.rng for _, s in live])
        for (_, state), token in zip(live, tokens):
            self._apply_token(state, token)

    def _apply_tokens(self, states, tokens):
        """Apply device-sampled (fused all-greedy argmax) token ids."""
        for state, token in zip(states, tokens):
            if state.n_generated >= state.request.max_new_tokens:
                self._finish(state, "length")
                continue
            self._apply_token(state, int(token))

    def _finish(self, state, reason):
        self._register_decode_tail(state)
        self.scheduler.retire(state)
        req = state.request
        result = GenerationResult(
            state.tokens[len(req.prompt):], reason, len(req.prompt),
            state.preemptions)
        handle = state.handle
        if getattr(handle, "finished_s", None) is None:
            handle.finished_s = time.monotonic()
        handle._finish(result)
        self.metrics.count_finished()

    def _drain_kv_bytes(self):
        """Drain the cache's byte counters into generation.* once per
        step: kv_bytes_moved (scale bytes folded in — they are bytes
        in flight too) plus the split-out kv_scale_bytes for quantized
        pools."""
        self.metrics.count_kv_bytes(self.cache.take_bytes_moved())
        if self.kv_quant:
            self.metrics.count_kv_scale_bytes(
                self.cache.take_scale_bytes())

    def _observe_occupancy(self):
        self.metrics.observe_occupancy(
            len(self.scheduler.active()), self.scheduler.num_slots,
            self.cache.utilization())
        # prefix-cache observability: per-step shared-page gauge plus
        # the cache-internal COW/eviction counters drained like
        # take_bytes_moved.  Skipped entirely when the feature is off —
        # nothing registers or shares pages then, and shared_pages
        # scans the per-page refcounts
        if self.prefix_cache_enabled:
            cow, evictions = self.cache.take_prefix_counters()
            self.metrics.count_cow(cow)
            self.metrics.count_prefix_evictions(evictions)
            self.metrics.observe_shared_pages(self.cache.shared_pages)

    # --------------------------- lifecycle --------------------------
    def start(self):
        """Start the background stepping worker (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._worker, name="generation-engine", daemon=True)
        self._thread.start()

    def _worker(self):
        while not self._stop.is_set():
            try:
                advanced = self.step()
            except Exception as e:  # noqa: BLE001 — a poisoned step must
                # not strand clients on a dead worker: the batch fails as
                # a unit (DynamicBatcher._dispatch semantics) and the
                # loop keeps draining the queue with typed errors.  The
                # cleanup takes the step lock: a client thread may be
                # driving step() concurrently (supported), and retiring
                # under its feet would free pages mid-step.
                with self._lock:
                    for state in self.scheduler.active():
                        self.scheduler.retire(state)
                        state.handle.set_exception(e)
                continue
            if advanced == 0 and not self.scheduler.pending_count():
                time.sleep(self._IDLE_POLL_S)

    def shutdown(self, timeout=5.0):
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
        # fail live slots before the queued backlog so errors are typed.
        # Under the step lock: a step outliving the join timeout (or a
        # client-driven step()) must finish before its pages are freed —
        # retiring mid-step would make attend() write into freed pages.
        with self._lock:
            # the tokens of a step in flight are computed: they are
            # delivered before what is still unfinished is failed
            try:
                self._retire_inflight("api")
            except Exception:   # noqa: BLE001 — its sequences fail
                pass            # with the rest, below
            for state in self.scheduler.active():
                self.scheduler.retire(state)
                state.handle.set_exception(ServingError(
                    "generation engine shut down mid-decode"))
        self.scheduler.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
