"""FusedDecodeStep: the whole decode step as ONE jitted dispatch.

The eager decode loop is correct but chatty: per token it issues ~2
device calls per layer (scatter-append + paged attention) plus the
model's own eager projection chain, then syncs the full [B, V] logits
block to host and samples row by row.  On TPU that dispatch/sync
overhead — not FLOPs — bounds tokens/s at small batch (the gap "Ragged
Paged Attention" closes by keeping the decode step inside one compiled
program).

This module collapses the step to one executable::

    tokens[B], positions[B], page_tables[B,MP], lens[B]
        -> embed -> L x (donated scatter-append + paged attention)
        -> logits [B, V]   (or argmax'd tokens [B] for all-greedy)

traced ONCE per shape bucket and dispatched ONCE per decode step.
The KV pool state rides through as donated arguments
(`DeviceKVPool.take_pool_state` / `put_pool_state` — k/v pools, plus
the per-layer scale arrays for int8 pools): XLA updates the buffers in
place and returns the same storage, so per-step host work collapses to
argument upload plus one small fetch.

Shape stability comes from decode-batch bucketing: the live batch B
(sequences join and finish every step) is padded to a small
ShapeBucketer menu with masked DUMMY rows — lens == 0, so their K/V
write is routed to the out-of-range sentinel page (dropped on device,
mode="drop") and their attention row is zero-length (exact zeros) —
and the page-table axis is padded to a power-of-two pages bucket.  One
executable per (batch bucket, pages bucket, greedy) signature, built
through serving's CompiledModelCache (donate_argnums), so steady-state
decode never traces again and the compile count is bounded by the menu.

The model opts in via the optional protocol methods::

    model.decode_params() -> pytree of weights
    model.decode_step_fn(page_size, num_pages, use_kernel=...,
                         pool_layout=..., greedy=...) -> pure fn
        fn(params, tokens, positions, k_pools, v_pools, page_tables,
           lens) -> (logits_or_tokens, k_pools', v_pools')

Policy mirrors jit_prefill: fused is the TPU auto-default, the
eager-exact path stays the CPU tier-1 default (XLA whole-program fusion
reassociates floats at the ulp level; the zero-tolerance token-identity
oracle is anchored on eager).  Forced fused on CPU is the acceptance
probe: exactly 1 dispatch, <=1 host sync per decode step
(tests/test_fused_decode.py).
"""
import numpy as np

from ..serving.bucketing import CompiledModelCache, ShapeBucketer
from .metrics import DecodeCacheMetrics

# The parts of a served model's ragged step.  Every operation of the
# step sits under exactly one of them, its outermost `jax.named_scope`
# (a part may hold scopes of its own: attention/window, state_space/
# scan); the compiled text keeps them, and a device profile is split by
# them through `profiler.device_op_scopes()` (docs/GENERATION.md,
# "Reading a trace").
STEP_SCOPES = ("hand_over", "embed", "attention", "mlp", "experts",
               "state_space", "head")


def step_scope(part):
    """The named scope of one part of the step, from `STEP_SCOPES`."""
    import jax

    if part not in STEP_SCOPES:
        raise ValueError(f"{part!r} is no part of the step: {STEP_SCOPES}")
    return jax.named_scope(part)


def _wrap_donating(num_layers, tree, jax_mod, call, n_fixed=4, n_out=1,
                   n_groups=2, group_sizes=None):
    """Flatten a pool-donating step fn to the positional-array calling
    convention CompiledModelCache keys and compiles on:
    ``(*fixed, *state_groups, *param_leaves)`` where the state is
    `n_groups` length-L array groups — k/v pools (n_groups == 2), plus
    the k/v scale arrays for quantized pools (n_groups == 4, the
    DeviceKVPool.take_pool_state layout).  `call(params, fixed,
    *groups)` adapts to the inner fn's own argument order and returns
    ``(out, *groups_out)`` — `out` a single array when n_out == 1,
    else a tuple of n_out arrays (the ragged step's ids + logits).
    `group_sizes`: the groups' lengths where they are not all L
    (`DeviceKVPool.state_group_sizes`: a cache with state layers)."""
    unflatten = jax_mod.tree_util.tree_unflatten
    sizes = tuple(group_sizes or (num_layers,) * n_groups)
    ends = np.cumsum(sizes)

    def step(*flat):
        fixed, leaves = flat[:n_fixed], flat[n_fixed:]
        groups = [list(leaves[end - size:end])
                  for size, end in zip(sizes, ends)]
        params = unflatten(tree, leaves[ends[-1]:])
        out, *groups_out = call(params, fixed, *groups)
        outs = (out,) if n_out == 1 else tuple(out)
        flat_state = [a for grp in groups_out for a in grp]
        return (*outs, *flat_state)

    return step


# the pool state sits at wrapper args n_fixed .. n_fixed+n_groups*L in
# that convention: donated so XLA updates the KV storage (and, for int8
# pools, the scale arrays) in place instead of copying every call
def _pool_donate_plan(num_layers, n_fixed=4, n_groups=2, group_sizes=None):
    n_state = sum(group_sizes or (num_layers,) * n_groups)
    return tuple(range(n_fixed, n_fixed + n_state))


def _shard_params(model, mesh, tp_axis, jax_mod):
    """Flatten decode_params(), committing each leaf to its
    NamedSharding when a mesh is given: the model's decode_param_specs
    names the head-sharded layout (Megatron column/row split); a model
    without specs runs fully replicated (pools still shard — correct,
    just with gather traffic the spec'd layout avoids).  Committed
    leaves are what make the AOT signature stable: CompiledModelCache
    lowers against exactly these shardings."""
    leaves, tree = jax_mod.tree_util.tree_flatten(model.decode_params())
    if mesh is None:
        return leaves, tree
    from jax.sharding import NamedSharding, PartitionSpec

    if hasattr(model, "decode_param_specs"):
        specs = jax_mod.tree_util.tree_leaves(
            model.decode_param_specs(tp_axis),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        if len(specs) != len(leaves):
            raise ValueError(
                f"decode_param_specs yields {len(specs)} specs for "
                f"{len(leaves)} decode_params leaves — the trees must "
                f"mirror each other")
    else:
        specs = [PartitionSpec()] * len(leaves)
    return [jax_mod.device_put(p, NamedSharding(mesh, s))
            for p, s in zip(leaves, specs)], tree


def _collective_bytes_estimate(num_layers, rows, d_model, tp_degree,
                               itemsize=4, quantized=False):
    """Estimated on-wire allreduce bytes of ONE sharded dispatch
    (generation.collective_bytes_per_step).  The sharded step has two
    allreduces per layer (after wo and after w2), each over the
    [rows, d_model] activation block; a ring allreduce moves
    2*(N-1)/N of the payload per device.  `rows` is the PADDED batch
    (or chunk) actually dispatched — padding rows ride the collective
    whether live or not.  Zero when unsharded.

    `quantized` is the EQuARX-style ring
    (parallel.quantized_allreduce): int8 payload on every hop plus the
    per-hop f32 scale scalars — the ~4x cut the quantized-collectives
    acceptance criterion measures against this same estimate."""
    if tp_degree <= 1:
        return 0
    if quantized:
        from ..parallel.quantized_allreduce import (
            quantized_collective_bytes)

        return quantized_collective_bytes(num_layers, rows, d_model,
                                          tp_degree)
    payload = int(rows) * int(d_model) * int(itemsize)
    return int(2 * num_layers * payload * 2 * (tp_degree - 1)
               / tp_degree)


def _dispatch_donating(cache, exec_cache, args, num_layers, n_out=1):
    """Run ONE compiled pool-donating dispatch: compile/fetch the
    executable for `args`' signature, dispatch, install the returned
    pool state.  On ANY failure past the dispatch the donated buffers
    are gone — leave the cache on fresh storage so the engine's
    fail-the-batch-and-keep-serving recovery (engine._worker) actually
    keeps serving.  This recovery contract lives HERE, once, for every
    pool-donating step (fused decode, chunked prefill, ragged).
    Returns the non-pool output (a tuple when n_out > 1),
    unmaterialized (no host sync)."""
    n_state = sum(getattr(cache, "state_group_sizes", (num_layers,) * 2))
    exe = exec_cache.get(args)
    try:
        outs = exe(*args)
        cache.put_pool_state(list(outs[n_out:n_out + n_state]))
    except BaseException:
        cache.reset_pools()
        raise
    return outs[0] if n_out == 1 else tuple(outs[:n_out])


def _param_structs(jax_mod, mesh, param_leaves):
    """ShapeDtypeStructs of the param leaves (sharded under a mesh) —
    the pre-warm signature tail shared by every donating step."""
    sds = jax_mod.ShapeDtypeStruct
    if mesh is not None:
        return [sds(tuple(p.shape), p.dtype, sharding=p.sharding)
                for p in param_leaves]
    return [sds(tuple(p.shape), p.dtype) for p in param_leaves]


def _state_structs(jax_mod, cache, mesh, num_layers, quant):
    """ShapeDtypeStructs of the donated pool state (k/v pools, plus the
    [P, H] scale arrays for quantized pools), sharded under a mesh so
    pre-warm lowers the REAL signature."""
    sds = jax_mod.ShapeDtypeStruct
    if cache.rows is not None:
        # a row cache: one pool a layer (a window layer's holds the
        # window group's pages; a state layer's array is its state a
        # slot, and the tails follow), never sharded
        return [sds(tuple(a.shape), a.dtype)
                for a in cache.take_pool_state()]
    pool = cache.layer_pools(0)[0]
    if mesh is not None:
        pool_sds = sds(tuple(pool.shape), pool.dtype,
                       sharding=cache.pool_sharding)
    else:
        pool_sds = sds(tuple(pool.shape), pool.dtype)
    structs = [pool_sds] * (2 * num_layers)
    if quant:
        sshape = (cache.num_pages, cache.num_heads)
        if mesh is not None:
            scale_sds = sds(sshape, np.dtype(np.float32),
                            sharding=cache.scale_sharding)
        else:
            scale_sds = sds(sshape, np.dtype(np.float32))
        structs += [scale_sds] * (2 * num_layers)
    return structs


def decode_batch_menu(max_slots):
    """Power-of-two batch buckets up to (and always including) the cap —
    the one batch-menu builder for both the fused decode step and the
    engine's prefill bucketer."""
    menu, b = [], 1
    while b < max_slots:
        menu.append(b)
        b *= 2
    menu.append(int(max_slots))
    return tuple(sorted(set(menu)))


class FusedDecodeStep:
    """Owns the per-bucket fused executables and the donation chain.

    One instance per engine; `step()` is the engine's whole decode
    device interaction: pad to buckets, donate the pools in, install
    the returned pools, fetch the (sliced) result.  `last_dispatches` /
    `last_syncs` are the instrumented per-call counts the
    generation.decode_*_per_step gauges are set from — counted at the
    actual call sites, not estimated."""

    def __init__(self, model, cache, metrics, use_kernel=False,
                 batch_buckets=None, mesh=None, tp_axis=None,
                 quant_collectives=False):
        import jax

        self._jax = jax
        self._cache = cache
        self._num_layers = int(cache.num_layers)
        self._mesh = mesh
        self._tp_axis = tp_axis
        self._tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        self._d_model = int(model.num_heads) * int(model.head_dim)
        self._quant = bool(getattr(cache, "quantized", False))
        self._quant_collectives = bool(quant_collectives) and self._tp > 1
        self._n_groups = 4 if self._quant else 2
        self._param_leaves, self._param_tree = _shard_params(
            model, mesh, tp_axis, jax)
        if not batch_buckets:
            raise ValueError("batch_buckets is required (the engine "
                             "passes its decode-batch menu)")
        menu_b = tuple(int(b) for b in batch_buckets)
        pages_menu = ShapeBucketer.geometric_menu(cache.num_pages, start=1)
        self._bucketer = ShapeBucketer(batch_buckets=menu_b,
                                       length_buckets=pages_menu)
        cache_metrics = DecodeCacheMetrics(metrics)
        # mesh kwargs only reach mesh-aware models, and the quantized
        # kwargs only reach quant-aware models: the plain path keeps
        # working against the original decode_step_fn protocol
        step_kw = ({"mesh": mesh, "tp_axis": tp_axis}
                   if mesh is not None else {})
        if self._quant:
            step_kw["kv_quant"] = True
        if self._quant_collectives:
            step_kw["quant_collectives"] = True
        self._exec = {}
        for greedy in (False, True):
            fn = model.decode_step_fn(
                cache.page_size, cache.num_pages, use_kernel=use_kernel,
                pool_layout=cache.pool_layout, greedy=greedy, **step_kw)
            # fixed args: (tokens, positions, page_tables, lens); the
            # state groups (k/v pools, plus k/v scales for quantized
            # pools) sit contiguously in the model fn's *rest order, so
            # one splat lambda serves both group layouts
            wrapped = _wrap_donating(
                self._num_layers, self._param_tree, jax,
                lambda params, f, *gs, fn=fn: fn(params, f[0], f[1],
                                                 *gs, f[2], f[3]),
                n_groups=self._n_groups)
            self._exec[greedy] = CompiledModelCache(
                wrapped, metrics=cache_metrics, aot=True,
                donate_argnums=_pool_donate_plan(
                    self._num_layers, n_groups=self._n_groups),
                name=lambda args, g="_greedy" if greedy else "":
                    f"decode_step_b{args[0].shape[0]}_p{args[2].shape[1]}{g}")
        self.last_dispatches = 0
        self.last_syncs = 0
        self.last_collective_bytes = 0

    @property
    def compile_count(self):
        """Distinct (batch, pages, greedy) signatures compiled — the
        bucket menu bounds this (tests assert it stays put under
        repeated traffic)."""
        return sum(c.compile_count for c in self._exec.values())

    def cached_buckets(self):
        return {greedy: c.cached_buckets()
                for greedy, c in self._exec.items()}

    def prewarm(self, batch_rows, pages_cols, greedy):
        """AOT-compile the (batch bucket, pages bucket, greedy) decode
        executable WITHOUT running it — the mid-prefill pre-warm: while
        a prompt is still streaming chunks in, the engine predicts the
        decode signature it will land in and compiles it here, so the
        first decode step after prefill pays no retrace.  Pure
        ShapeDtypeStructs through the signature cache (get() only
        lowers+compiles; nothing is dispatched, so donation never
        consumes a live pool).  Under a mesh the structs CARRY the pool
        and param NamedShardings — without them the pre-warmed
        executable would be lowered single-device, miss the real sharded
        signature, and the first decode after prefill would silently
        retrace (and the pre-warm compile would be garbage).  Returns
        True when this call actually compiled (False: the bucket was
        already cached)."""
        bucket_b = self._bucketer.batch_bucket(
            min(max(int(batch_rows), 1), self._bucketer.max_batch))
        bucket_p = self._bucketer.length_bucket(max(int(pages_cols), 1))
        sds = self._jax.ShapeDtypeStruct
        i32 = np.dtype(np.int32)
        args = [sds((bucket_b,), i32), sds((bucket_b,), i32),
                sds((bucket_b, bucket_p), i32), sds((bucket_b,), i32)]
        args += _state_structs(self._jax, self._cache, self._mesh,
                               self._num_layers, self._quant)
        args += _param_structs(self._jax, self._mesh, self._param_leaves)
        cache = self._exec[bool(greedy)]
        before = cache.compile_count
        cache.get(args)
        return cache.compile_count > before

    def step(self, tokens, positions, page_tables, lens, greedy):
        """One fused decode step for `len(tokens)` live sequences.

        Pads every input to its bucket (dummy rows: lens 0, page table
        all zeros — kernel-DMA-safe; their write is killed in-trace via
        the sentinel), runs the ONE compiled dispatch with the pools
        donated, installs the returned pools, and fetches the result in
        the ONE host sync.  Returns the real rows: [B] int32 token ids
        when greedy, else [B, V] logits."""
        b_real = len(tokens)
        bucket_b = self._bucketer.batch_bucket(b_real)
        bucket_p = self._bucketer.length_bucket(page_tables.shape[1])
        tok = np.zeros((bucket_b,), np.int32)
        tok[:b_real] = tokens
        pos = np.zeros((bucket_b,), np.int32)
        pos[:b_real] = positions
        ln = np.zeros((bucket_b,), np.int32)
        ln[:b_real] = lens
        pt = np.zeros((bucket_b, bucket_p), np.int32)
        pt[:b_real, :page_tables.shape[1]] = page_tables
        state = self._cache.take_pool_state()
        args = [tok, pos, pt, ln, *state, *self._param_leaves]
        out = _dispatch_donating(self._cache, self._exec[bool(greedy)],
                                 args, self._num_layers)
        host = np.asarray(out)                 # the single host sync
        self.last_dispatches = 1
        self.last_syncs = 1
        # padding-waste accounting: bucket_b - b_real DUMMY rows ran the
        # whole masked step (generation.padded_token_waste)
        self.last_rows_useful = b_real
        self.last_rows_dispatched = bucket_b
        self.last_collective_bytes = _collective_bytes_estimate(
            self._num_layers, bucket_b, self._d_model, self._tp,
            quantized=self._quant_collectives)
        return host[:b_real]


class ChunkedPrefillStep:
    """One jitted pool-donating dispatch per prefill CHUNK (the prefill
    analogue of FusedDecodeStep).

    Monolithic bucketed prefill compiles one executable per
    (batch, length) bucket — O(log max_prompt) shapes, each blocking
    every decode slot for the whole prompt's forward pass.  Chunking
    fixes the token axis at `chunk_tokens` forever: every chunk of every
    prompt runs the SAME executable (per pages bucket — the page-table
    axis still grows geometrically), the chunk's K/V is scattered into
    the donated pools in-trace (`model.prefill_chunk_fn`, the same
    drop-mode sentinel semantics as the fused decode step), and the
    compile menu is O(log num_pages) — independent of prompt length,
    which is the acceptance bound tests/test_chunked_prefill.py pins on
    `generation.prefill_compiles_total`.

    Mid-prompt chunks never sync the host: `run` hands the [V]
    last-position logits back UNMATERIALIZED, and the engine fetches
    only the FINAL chunk's (they ARE the first-token logits) — so a
    long prompt streams in with zero dispatch-pipeline bubbles between
    its chunks and the interleaved decode steps.

    Prefix caching composes for free: a warm hit advances prefill_pos
    past the matched span at admission, so fully-matched chunks are
    simply never planned — the first dispatched chunk starts at the
    first unmatched token, reading the aliased prefix pages through
    the page table like any other prefix.  The only new obligation is
    COW safety: the donated in-trace scatter must never write a shared
    page (see the pre-dispatch guard in `run`)."""

    def __init__(self, model, cache, metrics, chunk_tokens,
                 use_kernel=False, mesh=None, tp_axis=None,
                 quant_collectives=False):
        import jax

        self._cache = cache
        self._chunk = int(chunk_tokens)
        if self._chunk < 1:
            raise ValueError("chunk_tokens must be >= 1")
        self._num_layers = int(cache.num_layers)
        self._tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        self._d_model = int(model.num_heads) * int(model.head_dim)
        self._quant = bool(getattr(cache, "quantized", False))
        self._quant_collectives = bool(quant_collectives) and self._tp > 1
        self._n_groups = 4 if self._quant else 2
        self._param_leaves, self._param_tree = _shard_params(
            model, mesh, tp_axis, jax)
        pages_menu = ShapeBucketer.geometric_menu(cache.num_pages, start=1)
        self._bucketer = ShapeBucketer(batch_buckets=(1,),
                                       length_buckets=pages_menu)
        chunk_kw = ({"mesh": mesh, "tp_axis": tp_axis}
                    if mesh is not None else {})
        if self._quant:
            chunk_kw["kv_quant"] = True
        if self._quant_collectives:
            chunk_kw["quant_collectives"] = True
        fn = model.prefill_chunk_fn(
            cache.page_size, cache.num_pages, use_kernel=use_kernel,
            pool_layout=cache.pool_layout, **chunk_kw)
        self.last_collective_bytes = 0
        # fixed args: (tokens, start, length, page_table); pool state
        # donated exactly like the fused decode step (state groups
        # contiguous in the model fn's *rest order); compiles/hits
        # land under the PREFILL cache metrics (a chunk executable IS
        # a prefill executable)
        wrapped = _wrap_donating(
            self._num_layers, self._param_tree, jax,
            lambda params, f, *gs: fn(params, f[0], f[1], f[2],
                                      *gs, f[3]),
            n_groups=self._n_groups)
        self._exec = CompiledModelCache(
            wrapped, metrics=metrics, aot=True,
            donate_argnums=_pool_donate_plan(self._num_layers,
                                             n_groups=self._n_groups),
            name=lambda args: f"prefill_chunk_p{args[3].shape[0]}")

    @property
    def compile_count(self):
        """Distinct (pages bucket) signatures compiled — O(log
        num_pages), independent of prompt length."""
        return self._exec.compile_count

    def run(self, seq_id, tokens, start):
        """Dispatch one chunk: `tokens` (<= chunk_tokens of them, already
        reserved at positions [start, start+len)) are padded to the
        fixed chunk shape, the sequence's page table to its pages
        bucket, pools donated in, returned pools installed.  Returns the
        chunk's last-position logits [V] UNMATERIALIZED — no host sync;
        the engine fetches only the final chunk's (mid-prompt chunks
        stay fully async)."""
        n = len(tokens)
        if n > self._chunk:
            raise ValueError(f"chunk of {n} tokens > chunk_tokens="
                             f"{self._chunk}")
        # COW-safe donation chain: the scatter below runs IN-TRACE on
        # donated pools, where a write to a prefix-shared page would
        # silently corrupt every sequence (and cached run) aliasing it.
        # reserve() privatized the span via copy-on-write before this
        # chunk was planned; verify host-side, pre-dispatch, while the
        # pools are still alive
        self._cache.check_span_writable(seq_id, start, n)
        tok = np.zeros((self._chunk,), np.int32)
        tok[:n] = tokens
        pt_row, _ = self._cache.gather_block_tables([seq_id])
        bucket_p = self._bucketer.length_bucket(pt_row.shape[1])
        pt = np.zeros((bucket_p,), np.int32)
        pt[:pt_row.shape[1]] = pt_row[0]
        state = self._cache.take_pool_state()
        args = [tok, np.int32(start), np.int32(n), pt,
                *state, *self._param_leaves]
        self.last_collective_bytes = _collective_bytes_estimate(
            self._num_layers, self._chunk, self._d_model, self._tp,
            quantized=self._quant_collectives)
        # chunk-axis padding rows (chunk - n) are masked dummy work
        # inside this sequence's dispatch (generation.padded_token_waste)
        self.last_rows_useful = n
        self.last_rows_dispatched = self._chunk
        return _dispatch_donating(self._cache, self._exec, args,
                                  self._num_layers)


def hand_over_tokens(tokens, src, prev_ids):
    """The packed token row of a step enqueued behind another: row r
    takes descriptor ``src[r]``'s id of the previous step where
    ``src[r] >= 0`` (a decode row whose sequence sampled there, the id
    still on the device), and the host's `tokens[r]` elsewhere."""
    import jax.numpy as jnp

    return jnp.where(src >= 0, prev_ids[jnp.maximum(src, 0)], tokens)


def handing_over(fn, n_fixed):
    """A model's `ragged_step_fn` behind the token hand-over, as
    `RaggedStep` compiles it: ``(params, *fixed, src, prev_ids,
    *state_groups)``, `fixed` the model's own `n_fixed` arguments with
    the packed tokens first.  Done here, once, so no model's step
    function knows of it."""
    def step(params, *args):
        fixed, (src, prev_ids) = args[:n_fixed], args[n_fixed:n_fixed + 2]
        with step_scope("hand_over"):
            tokens = hand_over_tokens(fixed[0], src, prev_ids)
        return fn(params, tokens, *fixed[1:], *args[n_fixed + 2:])

    return step


class RaggedStep:
    """ONE mixed-batch executable per engine step — the Ragged Paged
    Attention serving model (PAPERS.md): the decode batch's single-token
    rows AND the step's prefill chunk ride one PACKED token axis of
    fixed size `max_tokens`, described by per-sequence
    ``[start, len, kv_len]`` descriptors, through one pool-donating
    dispatch.

    This collapses the legacy pair (FusedDecodeStep + ChunkedPrefillStep
    = one executable per (decode-batch bucket, pages bucket, greedy)
    signature PLUS one per pages bucket) into ONE executable per pages
    bucket TOTAL:

    - the token axis is fixed at `max_tokens` forever, so batch size,
      chunk length, and the decode/prefill mix never retrace;
    - the descriptor axis is fixed at `max_seqs`;
    - greedy is folded in: the trace computes BOTH the on-device argmax
      ids [S] and the logits [S, V] and returns them unmaterialized —
      the engine fetches ids for an all-greedy step, logits when any
      sampler is stochastic, and nothing for a mid-prompt chunk-only
      step, so every step stays at exactly 1 dispatch and <= 1 host
      sync.

    No dummy sequences exist in this design: every descriptor is a real
    sequence and packed slots past the real rows belong to none — no
    pool write (sentinel page), no attention (descriptor-skipped), no
    logits row.  That is the zero of `generation.padded_token_waste`;
    the inert-slot fraction of the fixed axis is reported honestly by
    `generation.step_row_utilization` instead.

    A step may be enqueued BEHIND another whose ids the host has not
    read: the previous dispatch's `ids` output stays on the device
    (`_prev_ids`, never donated) and rides the next dispatch as one more
    argument beside `src` [max_tokens], which says for each packed row
    whether its token is the host's (-1) or descriptor `src`'s id of
    that previous step (`hand_over_tokens`).  With nothing in flight
    every `src` is -1 and the ids are zeros (`forget_ids`): the same
    executable either way.

    Compiles/hits land under the DECODE cache metrics — the ragged
    executable IS the step executable (the prefill counters keep
    meaning what they always did on the legacy path)."""

    def __init__(self, model, cache, metrics, max_tokens, max_seqs,
                 use_kernel=False, mesh=None, tp_axis=None,
                 quant_collectives=False, spec_tokens=0):
        import jax

        self._jax = jax
        self._cache = cache
        self._num_layers = int(cache.num_layers)
        self.max_tokens = int(max_tokens)
        self.max_seqs = int(max_seqs)
        # speculative decoding: > 0 compiles the accept/reject epilogue
        # into the ONE executable (model.ragged_step_fn spec_tokens) —
        # the outputs become (ints [S, 3], logits_aug [S, V + 3]); the
        # signature axis stays the pages bucket alone, so the compile
        # menu is EXACTLY the non-speculative step's
        self.spec_tokens = int(spec_tokens)
        if self.max_tokens < 1 or self.max_seqs < 1:
            raise ValueError("max_tokens and max_seqs must be >= 1")
        self._mesh = mesh
        self._tp_axis = tp_axis
        self._tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        self._d_model = int(model.num_heads) * int(model.head_dim)
        self._use_kernel = bool(use_kernel)
        self._quant = bool(getattr(cache, "quantized", False))
        self._quant_collectives = bool(quant_collectives) and self._tp > 1
        self._param_leaves, self._param_tree = _shard_params(
            model, mesh, tp_axis, jax)
        pages_menu = ShapeBucketer.geometric_menu(cache.num_pages, start=1)
        self._bucketer = ShapeBucketer(batch_buckets=(1,),
                                       length_buckets=pages_menu)
        step_kw = ({"mesh": mesh, "tp_axis": tp_axis}
                   if mesh is not None else {})
        if self._quant:
            step_kw["kv_quant"] = True
        if self._quant_collectives:
            step_kw["quant_collectives"] = True
        # a model that counts inside its step (`step_counters`, the
        # names of one more [n] int32 output) gets a third output, which
        # `dispatch` hands back unread beside the other two
        self.step_counters = tuple(getattr(model, "step_counters", ()))
        self._n_out = 2 + bool(self.step_counters)
        if self.spec_tokens:
            # only spec-aware models see the kwarg: the plain ragged
            # protocol keeps working unchanged for models without it
            step_kw["spec_tokens"] = self.spec_tokens
        fn = model.ragged_step_fn(
            cache.page_size, cache.num_pages, use_kernel=use_kernel,
            pool_layout=cache.pool_layout, **step_kw)
        # fixed args: (tokens, positions, pages, rows, page_tables,
        #              starts, lens, kv_lens); pool state donated after
        # them (scale groups trail the pools for quantized caches).  A
        # cache with a window group adds that group's (pages,
        # page_tables): its rows are written, and its layers read,
        # through a table of their own; a cache with state layers adds
        # each descriptor's state slot, and its tails ride the donation
        # chain as a group of their own behind the pools.  Behind the
        # model's `_n_fixed` arguments sit the hand-over's two (`src`,
        # the previous step's ids), which the model never sees
        self._window_group = cache.window_group
        self._state_slots = (cache.state_slots
                             if cache.slot_state is not None else None)
        self._n_fixed = (8 + 2 * (self._window_group is not None)
                         + (self._state_slots is not None))
        sizes = cache.state_group_sizes
        step = handing_over(fn, self._n_fixed)
        wrapped = _wrap_donating(
            self._num_layers, self._param_tree, jax,
            lambda params, f, *gs: step(params, *f, *gs),
            n_fixed=self._n_fixed + 2, n_out=self._n_out,
            group_sizes=sizes)
        self._exec = CompiledModelCache(
            wrapped, metrics=DecodeCacheMetrics(metrics), aot=True,
            donate_argnums=_pool_donate_plan(
                self._num_layers, self._n_fixed + 2, group_sizes=sizes),
            name=lambda args: f"ragged_step_p{args[4].shape[1]}")
        # the ids of no previous step: zeros in the shape — and, under a
        # mesh, the replicated placement — of a step's own `ids`
        self._zero_ids = np.zeros((self.max_seqs,), np.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._zero_ids = jax.device_put(
                self._zero_ids, NamedSharding(mesh, PartitionSpec()))
        self._prev_ids = self._zero_ids
        self.last_dispatches = 0
        self.last_collective_bytes = 0
        self.last_rows_useful = 0
        self.last_rows_dispatched = 0
        # FLOP-proxy accounting of the query-tiled kernel (the host-side
        # mirror of its skip rule — ops/pallas ragged_score_blocks):
        # score blocks this dispatch computed vs what the untiled
        # kernel would have, in the same [q_block, page_size] units
        self.last_score_blocks = 0
        self.last_score_blocks_untiled = 0
        # ... and the steps the kernel's grid walked for them (ops/pallas
        # ragged_grid_cells), the same units (0 off the kernel path)
        self.last_grid_cells = 0
        self.last_pages_bucket = 0

    @property
    def compile_count(self):
        """Distinct signatures compiled — exactly the pages buckets
        touched, independent of batch size, chunk length, and greedy
        (the acceptance bound tests/test_ragged_step.py pins)."""
        return self._exec.compile_count

    def cached_buckets(self):
        return self._exec.cached_buckets()

    def _fixed_structs(self, bucket_p):
        sds = self._jax.ShapeDtypeStruct
        i32 = np.dtype(np.int32)
        t, s = self.max_tokens, self.max_seqs
        fixed = [sds((t,), i32), sds((t,), i32), sds((t,), i32),
                 sds((t,), i32), sds((s, bucket_p), i32),
                 sds((s,), i32), sds((s,), i32), sds((s,), i32)]
        if self._window_group is not None:
            fixed += [sds((t,), i32), sds((s, bucket_p), i32)]
        if self._state_slots is not None:
            fixed.append(sds((s,), i32))
        # the hand-over's two: `src`, and the previous step's ids
        fixed += [sds((t,), i32),
                  sds((s,), i32, sharding=getattr(self._zero_ids,
                                                  "sharding", None))]
        return fixed

    def prewarm(self, pages_cols):
        """AOT-compile the executable for a pages bucket WITHOUT
        dispatching (pure ShapeDtypeStructs; under a mesh they carry
        the pool and param NamedShardings, exactly like
        FusedDecodeStep.prewarm).  The ragged menu has no batch or
        greedy axis, so this is the WHOLE pre-warm surface.  Returns
        True when this call actually compiled."""
        bucket_p = self._bucketer.length_bucket(max(int(pages_cols), 1))
        args = (self._fixed_structs(bucket_p)
                + _state_structs(self._jax, self._cache, self._mesh,
                                 self._num_layers, self._quant)
                + _param_structs(self._jax, self._mesh,
                                 self._param_leaves))
        before = self._exec.compile_count
        self._exec.get(args)
        return self._exec.compile_count > before

    def pad(self, tokens, positions, pages, rows, page_tables, starts,
            lens, kv_lens, window=None, state_slots=None, src=None):
        """The executable's eight fixed arguments from the PACKED host
        arrays (the engine built them at exact sizes): the token axis
        padded to `max_tokens` with inert slots (sentinel page,
        position 0), the descriptor axis to `max_seqs` with len-0
        descriptors, and the page-table axis to its pages bucket.  Host
        work only — what `dispatch` takes.  `window`: the window
        group's ``(pages, page_tables)`` of the same rows and
        descriptors, padded alike into a ninth and tenth argument.
        `state_slots`: each descriptor's decode slot, for the state
        layers; descriptors past the real ones point at the row behind
        the last slot, which belongs to no sequence.  `src`: for each
        packed row the descriptor of the PREVIOUS dispatch whose id is
        its token, -1 (all of them when None) for the host's own: the
        last argument, which `dispatch` completes with those ids."""
        t_real = len(tokens)
        s_real = len(starts)
        if t_real > self.max_tokens:
            raise ValueError(
                f"{t_real} packed rows > max_tokens={self.max_tokens}")
        if s_real > self.max_seqs:
            raise ValueError(
                f"{s_real} descriptors > max_seqs={self.max_seqs}")
        t, s = self.max_tokens, self.max_seqs
        tok = np.zeros((t,), np.int32)
        tok[:t_real] = tokens
        pos = np.zeros((t,), np.int32)
        pos[:t_real] = positions
        pg = np.full((t,), self._cache.num_pages, np.int32)  # sentinel
        pg[:t_real] = pages
        rw = np.zeros((t,), np.int32)
        rw[:t_real] = rows
        page_tables = np.asarray(page_tables, np.int32)
        bucket_p = self._bucketer.length_bucket(
            max(page_tables.shape[1] if page_tables.size else 1, 1))
        pt = np.zeros((s, bucket_p), np.int32)
        if page_tables.size:
            pt[:s_real, :page_tables.shape[1]] = page_tables
        st = np.zeros((s,), np.int32)
        st[:s_real] = starts
        ln = np.zeros((s,), np.int32)
        ln[:s_real] = lens
        kv = np.zeros((s,), np.int32)
        kv[:s_real] = kv_lens
        extra = []
        if window is not None:
            w_pages, w_tables = window
            wpg = np.full((t,), self._window_group.num_pages, np.int32)
            wpg[:t_real] = w_pages
            wpt = np.zeros((s, bucket_p), np.int32)
            w_tables = np.asarray(w_tables, np.int32)
            if w_tables.size:
                wpt[:s_real, :w_tables.shape[1]] = w_tables
            extra = [wpg, wpt]
        if state_slots is not None:
            sl = np.full((s,), self._state_slots, np.int32)
            sl[:s_real] = state_slots
            extra.append(sl)
        sr = np.full((t,), -1, np.int32)
        if src is not None:
            sr[:t_real] = src
        extra.append(sr)
        self.last_pages_bucket = bucket_p
        self.last_rows_useful = t_real
        self.last_rows_dispatched = t
        self.last_collective_bytes = _collective_bytes_estimate(
            self._num_layers, t, self._d_model, self._tp,
            quantized=self._quant_collectives)
        return [tok, pos, pg, rw, pt, st, ln, kv, *extra]

    def dispatch(self, fixed):
        """The ONE donated dispatch of a step over `pad`'s arguments,
        behind the previous one's ids.  Returns ``(ids [S], logits
        [S, V], counters)`` UNMATERIALIZED — with spec_tokens ``ints
        [S, 3]`` and ``logits_aug [S, V + 3]`` carrying the accept/bonus
        columns (model.ragged_step_fn) — of which the caller fetches at
        most one of the first two (its single host sync); `counters` is
        the block of a model with `step_counters` (None without), read
        by the accounting of the step once it has been retired.  The
        ids stay here too, for the next dispatch's `src` rows (a
        speculative step's block is no such row: it hands none over)."""
        args = [*fixed, self._prev_ids, *self._cache.take_pool_state(),
                *self._param_leaves]
        out = _dispatch_donating(
            self._cache, self._exec, args, self._num_layers,
            n_out=self._n_out)
        self.last_dispatches = 1
        if not self.spec_tokens:
            self._prev_ids = out[0]
        return (*out, None)[:3]

    def forget_ids(self):
        """Drop the previous step's ids: the engine calls this whenever
        no step is in flight any more (nothing will be enqueued behind
        them), and where a step of its pipeline failed (they are
        poisoned like the pools `_dispatch_donating` reset)."""
        self._prev_ids = self._zero_ids

    def count_kernel_cells(self, fixed):
        """The dispatch's grid and the part of it that computes, per
        head and layer, into last_grid_cells / last_score_blocks /
        last_score_blocks_untiled (the grid in page SLOTS: G a cell of
        the kernel that runs: `generation.step_grid_cells`).  The
        FLOP proxy mirrors the TILED KERNEL's skip rule — only meaningful (and only paid) when the
        kernel path actually dispatched; the jnp reference computes
        dense masked blocks, and reporting kernel skip statistics for
        it would make the gen_bench /ref-vs-/kernel score_blocks column
        path-blind.  Host work the engine runs while the device does
        the step."""
        if not self._use_kernel:
            return
        from ..ops.pallas import paged_attention as pa

        st, ln, kv = fixed[5:8]
        bucket_p = self.last_pages_bucket
        page_size = self._cache.page_size
        rows = self._cache.rows
        if getattr(rows, "kv_heads", None):
            self._count_gqa_cells(st, ln, kv, bucket_p, page_size)
            return
        # both kernels walk GROUPS of pages: the grid in the score
        # blocks' unit is the page slots of the steps it takes, so blocks
        # over cells reads how full the groups are
        shape = (page_size, bucket_p, self.max_tokens)
        if rows is None:    # the per-head kernel, under its own tile
            per, _, qb = pa.ragged_cell_shape(*shape)
            steps = pa.ragged_grid_cells(
                self.max_seqs, bucket_p, self.max_tokens, page_size,
                live=pa.ragged_score_groups(st, ln, kv, *shape))
        else:               # the latent kernel
            per, qb = pa.latent_pages_per_cell(page_size, bucket_p), None
            steps = pa.latent_grid_cells(
                self.max_seqs, bucket_p, self.max_tokens, page_size,
                live=pa.latent_score_groups(st, ln, kv, *shape))
        self.last_score_blocks, self.last_score_blocks_untiled = \
            pa.ragged_score_blocks(st, ln, kv, *shape, qb)
        self.last_grid_cells = per * steps

    def _count_gqa_cells(self, st, ln, kv, bucket_p, page_size):
        """The grouped-query kernel's two lists (window, full), each
        weighed by the layers that walk it and the sum brought back to
        one layer: `last_score_blocks` the (tile, page) pairs between a
        tile's horizons, `last_grid_cells` the page SLOTS of the cells
        walked for them (G a cell), so blocks over cells reads how full
        the groups are; the untiled count is what the full list would
        be were every layer a full one."""
        from ..ops.pallas import gqa_paged_attention as gq

        per = gq.gqa_pages_per_cell(page_size, bucket_p)
        kinds = self._cache.layer_kinds or ("full",) * self._num_layers
        blocks = cells = 0
        for kind, window in (("full", None),
                             ("window", getattr(self._window_group,
                                                "window", None))):
            layers = kinds.count(kind)
            if not layers and kind == "window":
                continue
            pages, live = gq.gqa_score_cells(
                st, ln, kv, page_size, bucket_p, self.max_tokens, window)
            if kind == "full":
                self.last_score_blocks_untiled = pages
            blocks += layers * pages
            cells += layers * per * gq.gqa_grid_cells(
                self.max_seqs, bucket_p, self.max_tokens, page_size, window,
                live=live)
        self.last_score_blocks = blocks // len(kinds)
        self.last_grid_cells = max(cells // len(kinds), 1)


class LoopedRaggedStep:
    """N ragged decode steps in ONE dispatch — the host-free decode
    loop (model.ragged_loop_fn, docs/GENERATION.md "Host-free decode
    loop").

    Where RaggedStep pays one dispatch + <= 1 host sync PER TOKEN, this
    wraps the same ragged core in an in-trace ``lax.while_loop``:
    on-device sampling (the host sampler's hash-uniform twin), on-device
    stop-token and stop-sequence matching, per-row done masks with
    early exit, drafts verified at iteration 0, pools carried through
    the loop body on the SAME donation chain — and exactly ONE
    ``[S, N+K+6]`` host fetch per N steps (token ids + done/stop
    metadata + advanced RNG counters + final positions).

    Decode-only by construction: descriptor s statically owns packed
    rows ``[s*(1+K), s*(1+K)+len)``, so the token axis is
    ``max_seqs * (1 + spec_tokens)`` and the compile menu stays ONE
    executable per pages bucket — the engine falls back to the
    single-step path whenever the boundary isn't decode-only (prefill
    planned, a row's stop config exceeds the static caps, or a row is
    too close to its page/position budget), and admits/joins between
    loops, which is what makes `loop_steps` a latency-vs-admission
    knob rather than a correctness concern."""

    def __init__(self, model, cache, metrics, max_seqs, loop_steps,
                 use_kernel=False, mesh=None, tp_axis=None,
                 quant_collectives=False, spec_tokens=0,
                 max_stop_ids=8, max_stop_seqs=4, max_stop_len=8):
        import jax

        self._jax = jax
        self._cache = cache
        self._num_layers = int(cache.num_layers)
        self.max_seqs = int(max_seqs)
        self.loop_steps = int(loop_steps)
        self.spec_tokens = int(spec_tokens)
        self.max_stop_ids = int(max_stop_ids)
        self.max_stop_seqs = int(max_stop_seqs)
        self.max_stop_len = max(int(max_stop_len), 1)
        if self.max_seqs < 1:
            raise ValueError("max_seqs must be >= 1")
        if self.loop_steps < 1:
            raise ValueError("loop_steps must be >= 1")
        self._kd = max(self.spec_tokens, 1)
        self.max_emit = self.loop_steps + self.spec_tokens
        self._mesh = mesh
        self._tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        self._d_model = int(model.num_heads) * int(model.head_dim)
        self._quant = bool(getattr(cache, "quantized", False))
        self._quant_collectives = bool(quant_collectives) and self._tp > 1
        self._n_groups = 4 if self._quant else 2
        self._param_leaves, self._param_tree = _shard_params(
            model, mesh, tp_axis, jax)
        pages_menu = ShapeBucketer.geometric_menu(cache.num_pages, start=1)
        self._bucketer = ShapeBucketer(batch_buckets=(1,),
                                       length_buckets=pages_menu)
        step_kw = ({"mesh": mesh, "tp_axis": tp_axis}
                   if mesh is not None else {})
        if self._quant:
            step_kw["kv_quant"] = True
        if self._quant_collectives:
            step_kw["quant_collectives"] = True
        fn = model.ragged_loop_fn(
            cache.page_size, cache.num_pages, use_kernel=use_kernel,
            pool_layout=cache.pool_layout, spec_tokens=self.spec_tokens,
            loop_steps=self.loop_steps, max_stop_ids=self.max_stop_ids,
            max_stop_seqs=self.max_stop_seqs,
            max_stop_len=self.max_stop_len, **step_kw)
        # fixed args: (cur_tok, cur_pos, live, page_tables, temps,
        #              top_ks, top_ps, seeds, counters, remaining,
        #              stop_ids, stop_seqs, stop_seq_lens, tail,
        #              drafts, draft_lens); pool state donated after
        # them, exactly the RaggedStep convention
        self._n_fixed = 16
        wrapped = _wrap_donating(
            self._num_layers, self._param_tree, jax,
            lambda params, f, *gs: fn(params, *f, *gs),
            n_fixed=self._n_fixed, n_out=1, n_groups=self._n_groups)
        self._exec = CompiledModelCache(
            wrapped, metrics=DecodeCacheMetrics(metrics), aot=True,
            donate_argnums=_pool_donate_plan(self._num_layers,
                                             self._n_fixed,
                                             n_groups=self._n_groups),
            name=lambda args: f"ragged_loop_p{args[3].shape[1]}")
        self.last_dispatches = 0
        self.last_syncs = 0
        self.last_iters = 0
        self.last_rows_useful = 0
        self.last_rows_dispatched = 0
        self.last_collective_bytes = 0

    @property
    def compile_count(self):
        """Distinct signatures compiled — exactly the pages buckets
        touched (the loop adds NO signature axis: loop_steps and the
        stop caps are baked static)."""
        return self._exec.compile_count

    def cached_buckets(self):
        return self._exec.cached_buckets()

    def _fixed_structs(self, bucket_p):
        sds = self._jax.ShapeDtypeStruct
        i32 = np.dtype(np.int32)
        f32 = np.dtype(np.float32)
        s = self.max_seqs
        ms, ns, ls = self.max_stop_ids, self.max_stop_seqs, \
            self.max_stop_len
        return [sds((s,), i32), sds((s,), i32), sds((s,), i32),
                sds((s, bucket_p), i32), sds((s,), f32), sds((s,), i32),
                sds((s,), f32), sds((s,), i32), sds((s,), i32),
                sds((s,), i32), sds((s, ms), i32), sds((s, ns, ls), i32),
                sds((s, ns), i32), sds((s, ls - 1), i32),
                sds((s, self._kd), i32), sds((s,), i32)]

    def prewarm(self, pages_cols):
        """AOT-compile the loop executable for a pages bucket without
        dispatching (pure ShapeDtypeStructs — RaggedStep.prewarm's
        contract).  Returns True when this call actually compiled."""
        bucket_p = self._bucketer.length_bucket(max(int(pages_cols), 1))
        args = (self._fixed_structs(bucket_p)
                + _state_structs(self._jax, self._cache, self._mesh,
                                 self._num_layers, self._quant)
                + _param_structs(self._jax, self._mesh,
                                 self._param_leaves))
        before = self._exec.compile_count
        self._exec.get(args)
        return self._exec.compile_count > before

    def step(self, cur_tok, cur_pos, page_tables, temps, top_ks, top_ps,
             seeds, counters, remaining, stop_ids, stop_seqs,
             stop_seq_lens, tail, drafts, draft_lens):
        """Dispatch one N-step loop for ``len(cur_tok)`` live rows.

        All inputs are host arrays at exact sizes; this pads the row
        axis to `max_seqs` with dead rows (live == 0: zero-length
        descriptors, sentinel writes, no draws), the page-table axis to
        its pages bucket, runs the ONE donated dispatch, and fetches
        the ``[S, N+K+6]`` result in the ONE host sync.  Returns the
        real rows of that array (see model.ragged_loop_fn for the
        column layout)."""
        s_real = len(cur_tok)
        if s_real > self.max_seqs:
            raise ValueError(
                f"{s_real} loop rows > max_seqs={self.max_seqs}")
        s = self.max_seqs
        ms, ns, ls = self.max_stop_ids, self.max_stop_seqs, \
            self.max_stop_len

        def pad1(vals, fill, dtype=np.int32):
            a = np.full((s,), fill, dtype)
            a[:s_real] = vals
            return a

        page_tables = np.asarray(page_tables, np.int32)
        bucket_p = self._bucketer.length_bucket(
            max(page_tables.shape[1] if page_tables.size else 1, 1))
        pt = np.zeros((s, bucket_p), np.int32)
        if page_tables.size:
            pt[:s_real, :page_tables.shape[1]] = page_tables
        live = np.zeros((s,), np.int32)
        live[:s_real] = 1
        sids = np.full((s, ms), -1, np.int32)
        sids[:s_real] = stop_ids
        sseqs = np.full((s, ns, ls), -1, np.int32)
        sseqs[:s_real] = stop_seqs
        slens = np.zeros((s, ns), np.int32)
        slens[:s_real] = stop_seq_lens
        tl = np.full((s, ls - 1), -1, np.int32)
        tl[:s_real] = tail
        dr = np.zeros((s, self._kd), np.int32)
        dr[:s_real] = drafts
        args = [pad1(cur_tok, 0), pad1(cur_pos, 0), live, pt,
                pad1(temps, 0.0, np.float32), pad1(top_ks, 0),
                pad1(top_ps, 1.0, np.float32), pad1(seeds, 0),
                pad1(counters, 0), pad1(remaining, 0), sids, sseqs,
                slens, tl, dr, pad1(draft_lens, 0),
                *self._cache.take_pool_state(), *self._param_leaves]
        out = _dispatch_donating(self._cache, self._exec, args,
                                 self._num_layers, n_out=1)
        host = np.asarray(out)                 # the single host sync
        self.last_dispatches = 1
        self.last_syncs = 1
        self.last_iters = int(host[0, -1]) if s else 0
        self.last_rows_useful = s_real
        self.last_rows_dispatched = s
        # two allreduces per layer per ITERATION over the packed axis
        self.last_collective_bytes = _collective_bytes_estimate(
            self._num_layers, s * (1 + self.spec_tokens), self._d_model,
            self._tp, quantized=self._quant_collectives) \
            * max(self.last_iters, 0)
        return host[:s_real]
