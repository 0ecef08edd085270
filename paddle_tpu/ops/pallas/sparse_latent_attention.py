"""Attention over the tokens a learned indexer selects, as Pallas TPU
kernels: DeepSeek-V3.2's sparse attention ("DSA") over a paged latent
pool, as `SparseLatentMoELM` serves it.  Two calls, beside
`paged_attention.py`'s dense latent kernel (which this module leaves
alone):

INDEXER (`indexer_scores_kernel`, the call named "dsa_indexer_scores"):
every packed row's score of every token of its context,

    I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))      j over the index heads

with k(s) read from a layer's pool of index keys ``[P, page_size, D]``
through the page tables.  A cell is up to `INDEX_ROWS` consecutive rows
of ONE descriptor (`index_cells`): their heads are one ``[rows x heads,
D]`` block against a group of ``G = INDEX_CELL_TOKENS / page_size``
pages of keys, double-buffered by the kernel's own DMAs from the pool in
HBM, for every group up to the last row's position.  Each row's scores
go straight to HBM, one copy a row and group, so a row of another
descriptor in the same tile of the packed axis is never written over.
Columns past a row's position are whatever the last group's pages hold:
the caller masks them (`decode_attention.dsa_index_scores`).

SPARSE LATENT (`sparse_latent_attention_kernel`, "sparse_latent_paged_
attention"): one packed row's heads over the latent rows of ITS selected
tokens, fetched one token a copy from the pool through their (page,
row) slots, with a sink logit a head in the softmax's denominator:

    p_s = exp(z_s - m) / (exp(sink - m) + sum_{s in S} exp(z_s - m))

A token's row is one copy only if Mosaic takes a single row of the pool:
a 32-bit row.  A two-byte row is therefore STORED two numbers to a word
(`pack_rows`: number i in the low half of word i, number lanes + i in its
high half), which the kernel opens with two shifts; a float32 pool is
read as it lies.  The bytes a token moves are the stored row's.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, resolve_interpret

INDEX_ROWS = 8             # packed rows a cell of the indexer scores at once
INDEX_CELL_TOKENS = 512    # keys of a context a group of the indexer reads
SPARSE_VMEM_LIMIT = 64 << 20
COPY_UNROLL = 8            # token copies a turn of the issuing loop
WAIT_ROWS = 8              # token rows one semaphore wait covers
_HIGH = 0xFFFF0000


def pack_rows(rows, lanes):
    """``[..., 2 * lanes]`` bfloat16 -> ``[..., lanes]`` uint32: number i
    in the low half of word i, number ``lanes + i`` in its high half."""
    if rows.dtype != jnp.bfloat16:
        raise ValueError(f"pack_rows takes bfloat16 rows, not {rows.dtype}")
    bits = jax.lax.bitcast_convert_type(rows, jnp.uint16).astype(jnp.uint32)
    return bits[..., :lanes] | (bits[..., lanes:] << 16)


def _halves(words):
    """The two numbers of each word as float32, exactly: bfloat16 is
    float32's upper half."""
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & jnp.uint32(_HIGH), jnp.float32)
    return lo, hi


def unpack_rows(words):
    """`pack_rows` undone: ``[..., lanes]`` uint32 -> ``[..., 2 *
    lanes]`` float32; a float32 pool's rows as they are."""
    if words.dtype != jnp.uint32:
        return words.astype(jnp.float32)
    return jnp.concatenate(_halves(words), axis=-1)


# ------------------------------- indexer -------------------------------
def index_cells(starts, lens, n_rows):
    """The indexer's grid, in the trace: ``(desc, row0, n)`` int32 of
    every cell, each up to `INDEX_ROWS` consecutive rows of one
    descriptor, a descriptor's cells in order.  Capacity ``ceil(T /
    INDEX_ROWS) + S`` (a descriptor adds at most one partial cell);
    cells past the live ones have ``n == 0``."""
    qb = INDEX_ROWS
    st = jnp.asarray(starts, jnp.int32)
    ln = jnp.asarray(lens, jnp.int32)
    n_seqs = st.shape[0]
    per = (ln + qb - 1) // qb
    upto = jnp.cumsum(per)
    capacity = -(-n_rows // qb) + n_seqs
    c = jnp.arange(capacity, dtype=jnp.int32)
    desc = jnp.sum((c[:, None] >= upto[None, :]).astype(jnp.int32), axis=1)
    live = desc < n_seqs
    desc = jnp.minimum(desc, n_seqs - 1)
    k = c - (upto[desc] - per[desc])
    n = jnp.where(live, jnp.clip(ln[desc] - k * qb, 0, qb), 0)
    return desc, st[desc] + k * qb, n


def index_pages_per_cell(page_size, n_pages):
    """G, the pages of one group of the indexer."""
    return max(1, min(INDEX_CELL_TOKENS // int(page_size), int(n_pages)))


def _indexer_kernel(desc_ref, row_ref, n_ref, pt_ref, st_ref, ln_ref, kv_ref,
                    q_hbm, w_hbm, keys_hbm, out_hbm, qbuf, wbuf, kbuf, sbuf,
                    in_sem, key_sem, out_sem, *, page_size, per, n_pages,
                    heads, q_rows):
    c = pl.program_id(0)
    n = n_ref[c]
    n_keys = per * page_size

    @pl.when(n > 0)
    def _cell():
        s = desc_ref[c]
        r0 = row_ref[c]
        horizon = kv_ref[s] - ln_ref[s] + (r0 + n - 1 - st_ref[s])
        seen = jnp.minimum(horizon // page_size + 1, n_pages)
        groups = (seen + per - 1) // per

        def rows(act):
            def body(i, carry):
                act(pltpu.make_async_copy(
                    q_hbm.at[r0 + i], qbuf.at[pl.ds(i * heads, heads)],
                    in_sem.at[0]))
                act(pltpu.make_async_copy(
                    w_hbm.at[r0 + i], wbuf.at[pl.ds(i * heads, heads)],
                    in_sem.at[1]))
                return carry
            jax.lax.fori_loop(0, n, body, 0)

        def keys(g, act):
            for j in range(per):
                page = pt_ref[s * n_pages + jnp.minimum(g * per + j, seen - 1)]
                act(pltpu.make_async_copy(
                    keys_hbm.at[page],
                    kbuf.at[g % 2, pl.ds(j * page_size, page_size)],
                    key_sem.at[g % 2]))

        def written(slot, act):
            def body(i, carry):
                act(pltpu.make_async_copy(
                    sbuf.at[slot * q_rows + i],
                    out_hbm.at[r0 + i, :, pl.ds(0, n_keys)],
                    out_sem.at[slot]))
                return carry
            jax.lax.fori_loop(0, n, body, 0)

        rows(lambda cp: cp.start())
        keys(0, lambda cp: cp.start())
        rows(lambda cp: cp.wait())

        def group(g, carry):
            slot = g % 2

            @pl.when(g + 1 < groups)
            def _next():
                keys(g + 1, lambda cp: cp.start())

            keys(g, lambda cp: cp.wait())

            @pl.when(g >= 2)
            def _free():      # this slot's rows went out two groups ago
                written(slot, lambda cp: cp.wait())

            sc = jax.lax.dot_general(qbuf[...], kbuf[slot],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc = jnp.maximum(sc, 0.0) * wbuf[:, 0:1]
            for i in range(q_rows):
                sbuf[slot * q_rows + i] = jnp.sum(
                    sc[i * heads:(i + 1) * heads], axis=0, keepdims=True)

            def out(i, carry):
                pltpu.make_async_copy(
                    sbuf.at[slot * q_rows + i],
                    out_hbm.at[r0 + i, :, pl.ds(g * n_keys, n_keys)],
                    out_sem.at[slot]).start()
                return carry
            jax.lax.fori_loop(0, n, out, 0)
            return carry

        jax.lax.fori_loop(0, groups, group, 0)

        @pl.when(groups >= 2)
        def _drain_before_last():
            written((groups - 2) % 2, lambda cp: cp.wait())

        @pl.when(groups >= 1)
        def _drain_last():
            written((groups - 1) % 2, lambda cp: cp.wait())


def indexer_scores_kernel(q, w, keys, page_tables, starts, lens, kv_lens,
                          interpret=None):
    """Every packed row's index score of every slot of its page table.

    q: [T, Hi, D] index queries in the pool's dtype; w: [T, Hi] float32,
    each head's weight with every constant factor in it; keys: one
    layer's index-key pool [P, page_size, D], this step's keys written;
    page_tables [S, MP], starts / lens / kv_lens [S]: the ragged step's
    descriptors.  Returns [T, MP * page_size] float32: row t's column p
    is its score of position p of its sequence wherever p lies at or
    under the row's own position; every other entry is undefined (the
    caller masks it), and so is every row no descriptor owns.  The
    kernel writes each row's scores as a ``[1, width]`` tile of a
    ``[T, 1, width]`` array: a copy of one row is one Mosaic takes only
    where the row is a tile of its own."""
    t, heads, dim = q.shape
    page_size = keys.shape[1]
    pt = jnp.asarray(page_tables, jnp.int32)
    n_seqs, n_pages = pt.shape
    per = index_pages_per_cell(page_size, n_pages)
    n_groups = -(-n_pages // per)
    width = n_groups * per * page_size
    desc, row0, n = index_cells(starts, lens, t)
    prefetch = [desc, row0, n, pt.reshape(-1)] + [
        jnp.asarray(x, jnp.int32) for x in (starts, lens, kv_lens)]
    wide = jnp.broadcast_to(w.astype(jnp.float32)[:, :, None],
                            (t, heads, 128))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    q = q.astype(keys.dtype)
    interpret = resolve_interpret(interpret)
    if not interpret:
        q, wide, keys = (pltpu.with_memory_space_constraint(x, pltpu.HBM)
                         for x in (q, wide, keys))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(desc.shape[0],),
        in_specs=[any_space, any_space, any_space],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[
            pltpu.VMEM((INDEX_ROWS * heads, dim), q.dtype),
            pltpu.VMEM((INDEX_ROWS * heads, 128), jnp.float32),
            pltpu.VMEM((2, per * page_size, dim), keys.dtype),
            pltpu.VMEM((2 * INDEX_ROWS, 1, per * page_size), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, page_size=page_size, per=per,
                          n_pages=n_pages, heads=heads, q_rows=INDEX_ROWS),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SPARSE_VMEM_LIMIT),
        name="dsa_indexer_scores",
        interpret=interpret,
    )(*prefetch, q, wide, keys)
    return out[:, 0, :n_pages * page_size]


# --------------------------- sparse attention ---------------------------
def _sparse_kernel(cnt_ref, q_ref, sink_ref, slots_hbm, pool_hbm, o_ref, idx,
                   buf, sem, *, v_width, packed, page_size):
    """One packed row a grid step; its tokens' copies were started by
    the step before (the first step starts its own), and this step
    starts the next row's before it waits for its own: one row's copies
    are in flight while another's are issued and a third is computed."""
    r = pl.program_id(0)
    rows = pl.num_programs(0)
    k = buf.shape[1]
    n = cnt_ref[r]

    def start(row):
        slot = row % 2
        cp = pltpu.make_async_copy(slots_hbm.at[row], idx.at[slot],
                                   sem.at[2])
        cp.start()
        cp.wait()

        def turn(i, carry):
            for u in range(COPY_UNROLL):
                j = i * COPY_UNROLL + u
                at = idx[slot, 0, j]
                pltpu.make_async_copy(
                    pool_hbm.at[at // page_size, pl.ds(at % page_size, 1)],
                    buf.at[slot, pl.ds(j, 1)], sem.at[slot]).start()
            return carry

        # all K: the slots past a row's count name a valid row, and a
        # fixed count is copies issued COPY_UNROLL to a turn
        jax.lax.fori_loop(0, k // COPY_UNROLL, turn, 0)

    @pl.when((r == 0) & (n > 0))
    def _first():
        start(r)

    @pl.when((r + 1 < rows) & (cnt_ref[jnp.minimum(r + 1, rows - 1)] > 0))
    def _next():
        start(r + 1)

    slot = r % 2

    @pl.when(n > 0)
    def _wait():
        def group(j, carry):   # one wait for WAIT_ROWS rows' bytes
            pltpu.make_async_copy(
                pool_hbm.at[0, pl.ds(0, WAIT_ROWS)],
                buf.at[slot, pl.ds(0, WAIT_ROWS)], sem.at[slot]).wait()
            return carry

        jax.lax.fori_loop(0, k // WAIT_ROWS, group, 0)

    q = q_ref[0]                                   # [H, numbers]
    words = buf[slot][:, 0, :]                     # [K, lanes]
    held = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0) < n
    if packed:
        lanes = words.shape[1]
        parts = [jnp.where(held, half, 0.0).astype(q.dtype)
                 for half in _halves(words)]
        qs = [q[:, :lanes], q[:, lanes:]]
    else:
        lanes = q.shape[1]
        parts = [jnp.where(held, words, 0.0).astype(q.dtype)]
        qs = [q]
    sc = sum(jax.lax.dot_general(qh, part, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for qh, part in zip(qs, parts))       # [H, K]
    seen = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) < n
    sc = jnp.where(seen, sc, NEG_INF)
    sink = sink_ref[:, 0:1]
    m = jnp.maximum(jnp.max(sc, axis=1, keepdims=True), sink)
    p = jnp.where(seen, jnp.exp(sc - m), 0.0)
    denom = jnp.sum(p, axis=1, keepdims=True) + jnp.exp(sink - m)
    p = p.astype(q.dtype)
    outs, left = [], v_width
    for part in parts:
        take = min(left, lanes)
        if take > 0:
            outs.append(jax.lax.dot_general(
                p, part[:, :take], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        left -= take
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    o_ref[0] = (o / denom).astype(o_ref.dtype)


def sparse_latent_attention_kernel(q, pool, slots, counts, sink, v_width,
                                   interpret=None):
    """Each packed row's heads over its selected latent rows.

    q: [T, H, N] queries, scaled, in the model's dtype, N the numbers a
    stored row carries (zeros past the row's own width); pool: one
    layer's latent pool [P, page_size, 1, lanes] (uint32 of packed
    bfloat16 pairs, or float32: a row is a tile of its own, so that one
    token's row is one copy); slots: [T, K] int32, row t's selected
    tokens as ``page * page_size + row``, the first ``counts[t]`` of
    them meaningful; sink: [H] float32.  Returns [T, H, v_width] in q's
    dtype: the weighted sum of the first `v_width` numbers of the
    selected rows, 0 for a row with no selection.  One grid step a row
    (in order: the copies of the next row are started by this one): its
    slots into SMEM, one copy a slot (K of them, in flight together, the
    ones past the count fetching a row that is masked), then one ``[H,
    K]`` block of scores.  K is a multiple of `WAIT_ROWS` and of
    `COPY_UNROLL`."""
    t, h, numbers = q.shape
    packed = pool.dtype == jnp.uint32
    page_size, lanes = pool.shape[1], pool.shape[-1]
    if numbers != lanes * (2 if packed else 1):
        raise ValueError(f"queries of {numbers} numbers over a pool of "
                         f"{lanes} {pool.dtype} lanes")
    k = slots.shape[1]
    if k % WAIT_ROWS or k % COPY_UNROLL:
        raise ValueError(f"{k} selected tokens a row: a multiple of "
                         f"{WAIT_ROWS} and of {COPY_UNROLL}")
    slots = jnp.asarray(slots, jnp.int32)[:, None, :]
    interpret = resolve_interpret(interpret)
    if not interpret:
        # both in HBM by name: a row of either is a copy only from there
        pool = pltpu.with_memory_space_constraint(pool, pltpu.HBM)
        slots = pltpu.with_memory_space_constraint(slots, pltpu.HBM)
    sink = jnp.broadcast_to(jnp.asarray(sink, jnp.float32)[:, None], (h, 128))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[pl.BlockSpec((1, h, numbers), lambda r, c: (r, 0, 0)),
                  pl.BlockSpec((h, 128), lambda r, c: (0, 0)),
                  any_space, any_space],
        out_specs=pl.BlockSpec((1, h, v_width), lambda r, c: (r, 0, 0)),
        scratch_shapes=[pltpu.SMEM((2, 1, k), jnp.int32),
                        pltpu.VMEM((2, k, 1, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((3,))])
    return pl.pallas_call(
        functools.partial(_sparse_kernel, v_width=v_width, packed=packed,
                          page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT),
        name="sparse_latent_paged_attention",
        interpret=interpret,
    )(jnp.asarray(counts, jnp.int32), q, sink, slots, pool)


# ------------------------------ row writes ------------------------------
_WRITE_WINDOW = 64     # rows whose copies are in flight together


def _row_write_kernel(at_ref, x_hbm, pool_in, pool_out, sem, *, num_pages):
    del pool_in         # aliased to pool_out: nothing but the rows moves
    n = x_hbm.shape[0]

    def each(lo, hi, act):
        def body(t, carry):
            @pl.when(at_ref[t] < num_pages)      # the padding sentinel
            def _():
                act(pltpu.make_async_copy(
                    x_hbm.at[t], pool_out.at[at_ref[t], at_ref[n + t]], sem))
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    def window(w, carry):
        lo = w * _WRITE_WINDOW
        hi = jnp.minimum(lo + _WRITE_WINDOW, n)
        each(lo, hi, lambda cp: cp.start())
        each(lo, hi, lambda cp: cp.wait())
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, _WRITE_WINDOW), window, 0)


def pool_row_write(pool, pages, rows, x, interpret=None):
    """``x[i]`` ([lanes], in the pool's dtype) lands at ``pool[pages[i],
    rows[i], 0]``, in place; a page ``== P`` (the padding sentinel) is
    dropped.  XLA:TPU serves a scatter into a pool of one-row tiles by
    copying the pool (0.7 GB a layer at the cell's shapes, compile-only
    for v5e): here one copy a row, HBM to HBM, `_WRITE_WINDOW` of them
    in flight.  Returns the pool, aliased to its operand."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    at = jnp.concatenate([jnp.asarray(pages, jnp.int32),
                          jnp.asarray(rows, jnp.int32)])
    return pl.pallas_call(
        functools.partial(_row_write_kernel, num_pages=pool.shape[0]),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), any_space,
                  any_space],
        out_specs=any_space,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        name="sparse_pool_row_write",
        interpret=resolve_interpret(interpret),
    )(at, x.astype(pool.dtype)[:, None, :], pool)
