"""Paged decode attention: one query token per sequence over a paged KV
cache, with two interchangeable implementations:

- the Pallas TPU kernel (ops/pallas/paged_attention.py) — page-table DMA
  via scalar prefetch, online softmax across the page axis;
- a pure-jnp gather reference — gathers each sequence's pages into a
  padded [B, Kmax, H, D] view and runs masked dense attention.

The reference is not just a fallback: it IS the correctness oracle.  Its
masking is built so that padded positions contribute *exactly* zero
(``exp(NEG_INF - m)`` underflows to 0.0, and ``x + 0.0 == x`` in floats),
which makes its fp32 output bit-comparable to a dense causal
full-recompute over the real tokens — the property
tests/test_generation.py asserts.  Tier-1 CPU tests therefore exercise
the same semantics the TPU kernel implements.

Both paths take the pools AS-IS: a host numpy pool is uploaded whole
(the O(pool) cost PagedKVCache.layer_pools charges), while a
DeviceKVPool hands its resident jax.Arrays straight through —
``jnp.asarray`` on a device array is a no-op, so nothing is re-uploaded
and a decode step's transfer cost is O(tokens).  Low-precision pools
(``kv_dtype=bfloat16``) are upcast to the query dtype after the gather:
storage saves HBM, the softmax math stays fp32.
"""
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather_pool(pool, pt, b, h, d, layout, dtype, scale=None):
    """Gather a [B, Kmax, H, D] contiguous view of each sequence's pages
    from either pool layout.  The kernel layout's gathered view is
    transposed AFTER the gather — a value-preserving permutation of the
    O(tokens) view, never the pool — so the downstream einsums see
    byte-identical operands in both layouts (the bitwise re-proof
    tests/test_fused_decode.py pins).

    `scale` (int8 pools): the [P, H] per-page per-head abs-max scale
    array — the gathered int8 view dequantizes elementwise with the
    SAME ``value * (scale * 1/127)`` expression the Pallas kernels
    apply in-block (quantized_kv.dequant_factor), so kernel and
    reference see bitwise-equal operands, exactly like the bf16
    upcast."""
    if scale is None and pool.dtype == jnp.int8:
        # raw int8 codes decoded as values are finite and
        # plausible-looking (up to 127x wrong) — fail loudly instead
        raise ValueError(
            "int8 KV pool reached attention without its scale array — "
            "thread the cache's layer_scales() through k_scale/v_scale")
    if scale is not None and pool.dtype != jnp.int8:
        # the converse misuse corrupts just as silently: float values
        # multiplied by scale/127
        raise ValueError(
            f"k_scale/v_scale passed with a {pool.dtype} pool — scales "
            "belong to int8 pools only")
    if layout == "kernel":
        # pool [H, P, ps, D] -> gather [H, B, MP, ps, D] -> [B, MP, ps, H, D]
        g = jnp.transpose(pool[:, pt], (1, 2, 3, 0, 4))
    else:
        # pool [P, ps, H, D] -> gather [B, MP, ps, H, D]
        g = pool[pt]
    if scale is not None:
        from .quantized_kv import dequant_factor

        # scale[pt]: [B, MP, H] -> broadcast over page rows and D
        g = g.astype(dtype) * dequant_factor(
            jnp.asarray(scale)[pt][:, :, None, :, None])
    return g.reshape(b, -1, h, d).astype(dtype)


def paged_decode_attention_reference(q, k_pool, v_pool, page_tables,
                                     seq_lens, scale=None, layout="token",
                                     k_scale=None, v_scale=None):
    """Pure-jnp paged decode attention.

    q: [B, H, D] — the single query token per sequence.
    k_pool, v_pool: one layer's pool — [P, page_size, H, D] for the
        token layout, [H, P, page_size, D] for layout="kernel".
    page_tables: [B, max_pages] int32, unused slots padded with 0.
    seq_lens: [B] int32 live token counts.
    k_scale, v_scale: [P, H] per-page per-head abs-max scales for int8
        pools (None otherwise) — the gathered view dequantizes with the
        kernels' exact factor.
    Returns [B, H, D].
    """
    q = jnp.asarray(q)
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    pt = jnp.asarray(page_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    b, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # gather pages into [B, Kmax, H, D]; the upcast (bf16 pools) and the
    # int8 dequant happen on the gathered O(tokens) view, never on the
    # whole pool
    k = _gather_pool(k_pool, pt, b, h, d, layout, q.dtype, k_scale)
    v = _gather_pool(v_pool, pt, b, h, d, layout, q.dtype, v_scale)
    kmax = k.shape[1]
    logits = jnp.einsum("bhd,bkhd->bhk", q, k) * scale
    live = jnp.arange(kmax, dtype=jnp.int32)[None, :] < lens[:, None]
    logits = jnp.where(live[:, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    # an empty sequence (len 0) has every key masked: softmax over the
    # all-NEG_INF row is uniform garbage — emit zeros instead, matching
    # the kernel's safe_l guard (where() selects, so len>0 rows keep
    # their weights bitwise)
    weights = jnp.where(lens[:, None, None] > 0, weights, 0.0)
    return jnp.einsum("bhk,bkhd->bhd", weights, v)


def paged_decode_attention(q, k_pool, v_pool, page_tables, seq_lens,
                           scale=None, use_kernel=None, interpret=None,
                           layout="token", mesh=None, tp_axis=None,
                           k_scale=None, v_scale=None):
    """Dispatch: the Pallas kernel on TPU (or when forced, e.g. interpret
    mode in tests), the jnp reference elsewhere.  `layout` names the
    pool storage layout ("token" or "kernel", see DeviceKVPool) — with
    layout="kernel" the Pallas path consumes the pools as stored, with
    no per-call whole-pool transpose.  `mesh`/`tp_axis` make the kernel
    path mesh-native: the kernel runs as a shard_map over the
    head-sharded mesh (per-shard program = the same kernel on
    num_heads/tp heads over that shard's pool slice); the reference
    path ignores them — GSPMD partitions it over heads on its own."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return paged_decode_attention_reference(
            q, k_pool, v_pool, page_tables, seq_lens, scale=scale,
            layout=layout, k_scale=k_scale, v_scale=v_scale)
    from ..ops.pallas.paged_attention import paged_decode_attention_kernel

    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        page_tables, seq_lens, scale, interpret=interpret, layout=layout,
        mesh=mesh, tp_axis=tp_axis, k_scale=k_scale, v_scale=v_scale)


def ragged_paged_attention_reference(q, k_pool, v_pool, page_tables,
                                     starts, lens, kv_lens, scale=None,
                                     layout="token", k_scale=None,
                                     v_scale=None):
    """Pure-jnp RAGGED paged attention: one mixed batch of variable-
    length query runs — decode rows (1 query), prefill chunks (many),
    and SPECULATIVE verify runs (a decode row with len = 1 + k: its
    committed token plus k drafts, verified with the same per-row
    causal masking and no new signature — the primitive speculation
    rides, docs/GENERATION.md "Speculative decoding") — packed into
    ONE token axis, attending through per-sequence page tables (the
    Ragged Paged Attention serving model, PAPERS.md).

    q: [T, H, D] — the packed query rows of every sequence in the step,
        sequence s owning rows ``[starts[s], starts[s] + lens[s])``.
    k_pool, v_pool: one layer's pool — [P, page_size, H, D] for the
        token layout, [H, P, page_size, D] for layout="kernel".
    page_tables: [S, max_pages] int32, unused slots padded with 0.
    starts, lens: [S] int32 — each descriptor's query-row span in the
        packed axis; ``lens[s] == 0`` marks an UNUSED descriptor
        (skipped entirely).
    kv_lens: [S] int32 — tokens resident in the cache for sequence s
        AFTER this step's writes, so query row r of sequence s sits at
        global position ``kv_lens[s] - lens[s] + r`` and attends keys
        ``[0, position]`` (per-row causal).
    Returns [T, H, D]; rows owned by no descriptor come back exactly 0.

    Exactness follows the decode reference's construction: masked keys
    are NEG_INF, ``exp(NEG_INF - m)`` underflows to exactly 0.0, and a
    row's weights are zeroed post-softmax only where already exactly 0
    — so padding the key axis or the descriptor axis never changes a
    live row's values.  Like the chunk reference, the end-to-end oracle
    contract is TOKEN identity against the eager path (XLA picks
    reduction strategies per shape), the fused-decode standard.
    """
    q = jnp.asarray(q)
    t, h, d = q.shape
    pt = jnp.asarray(page_tables, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    s_n = pt.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # gather each descriptor's pages into [S, Kmax, H, D]; bf16 pools
    # upcast (and int8 pools dequantize) on the gathered view, never
    # the pool
    k = _gather_pool(jnp.asarray(k_pool), pt, s_n, h, d, layout, q.dtype,
                     k_scale)
    v = _gather_pool(jnp.asarray(v_pool), pt, s_n, h, d, layout, q.dtype,
                     v_scale)
    kmax = k.shape[1]
    logits = jnp.einsum("thd,skhd->sthk", q, k) * scale
    row = jnp.arange(t, dtype=jnp.int32)[None, :]            # [1, T]
    mine = (row >= starts[:, None]) & (row < (starts + lens)[:, None])
    # global position of row r within its owner: kv_len - len + (r-start)
    qpos = (kv_lens - lens)[:, None] + (row - starts[:, None])
    col = jnp.arange(kmax, dtype=jnp.int32)[None, None, :]   # [1, 1, K]
    visible = mine[:, :, None] & (col <= qpos[:, :, None])
    logits = jnp.where(visible[:, :, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    # rows a descriptor doesn't own softmax over all-NEG_INF (uniform
    # garbage): zero them post-softmax.  Owned rows' masked entries are
    # already exactly 0, so where() is bitwise-neutral there — the same
    # safe-row construction as the decode reference's empty-sequence
    # guard.
    weights = jnp.where(visible[:, :, None, :], weights, 0.0)
    # each packed row is owned by at most one descriptor: summing over
    # the descriptor axis selects its one live contribution
    return jnp.einsum("sthk,skhd->thd", weights, v)


def ragged_work_list(page_tables, starts, lens, kv_lens, page_size, n_rows,
                     use_kernel=None):
    """What `ragged_paged_attention(work=...)` takes: the Pallas
    kernel's grid for these descriptors (ops/pallas `ragged_work_list`,
    pure jnp, loop-body safe), for a caller that attends layer after
    layer over the same descriptors and builds it ONCE.  None where the
    jnp reference runs: it has no grid."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return None
    from ..ops.pallas.paged_attention import ragged_work_list as build

    return build(starts, lens, kv_lens, page_size,
                 jnp.asarray(page_tables).shape[1], n_rows)


def ragged_paged_attention(q, k_pool, v_pool, page_tables, starts, lens,
                           kv_lens, scale=None, use_kernel=None,
                           interpret=None, layout="token", mesh=None,
                           tp_axis=None, k_scale=None, v_scale=None,
                           work=None):
    """Dispatch for the ragged mixed-batch path: the Pallas kernel on
    TPU (or when forced), the jnp gather reference elsewhere — the
    exact contract of paged_decode_attention, grown from one query row
    per sequence to a ragged run of rows per descriptor.  `mesh`/
    `tp_axis` run the kernel as a shard_map over the head-sharded mesh
    (the reference path ignores them — GSPMD partitions it on its
    own).  `work` is `ragged_work_list` of the same descriptors (the
    kernel builds its own when None; the reference ignores it).

    LOOP-BODY SAFE (the host-free decode loop's protocol,
    model.ragged_loop_fn): both paths are pure functions of their
    operands with shapes fixed by the operand shapes alone — no host
    callbacks, no data-dependent output shapes, `use_kernel` resolved
    at TRACE time — so one call per ``lax.while_loop`` iteration
    re-reads the carried pools with zero re-trace.  Descriptor
    VALUES (starts/lens/kv_lens and the page-table rows) are ordinary
    traced data and may change freely between iterations; only the
    descriptor COUNT is baked into the executable.  The rank guard
    below turns a mis-packed loop carry into a named error instead of
    a shape mismatch deep inside lax."""
    starts = jnp.asarray(starts)
    lens = jnp.asarray(lens)
    kv_lens = jnp.asarray(kv_lens)
    pt_arr = jnp.asarray(page_tables)
    if (pt_arr.ndim != 2 or starts.ndim != 1 or lens.ndim != 1
            or kv_lens.ndim != 1
            or not (pt_arr.shape[0] == starts.shape[0] == lens.shape[0]
                    == kv_lens.shape[0])):
        raise ValueError(
            f"ragged descriptors must be [S]-shaped with a [S, P] page "
            f"table: page_tables {pt_arr.shape}, starts {starts.shape}, "
            f"lens {lens.shape}, kv_lens {kv_lens.shape}")
    page_tables = pt_arr
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ragged_paged_attention_reference(
            q, k_pool, v_pool, page_tables, starts, lens, kv_lens,
            scale=scale, layout=layout, k_scale=k_scale, v_scale=v_scale)
    from ..ops.pallas.paged_attention import ragged_paged_attention_kernel

    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return ragged_paged_attention_kernel(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        page_tables, starts, lens, kv_lens, scale, interpret=interpret,
        layout=layout, mesh=mesh, tp_axis=tp_axis, k_scale=k_scale,
        v_scale=v_scale, work=work)


def chunk_prefill_attention_reference(q, k, v, start, scale=None):
    """Causal attention for ONE prefill chunk over prefix + chunk keys.

    q: [n, H, D] — the chunk's queries; row i sits at global position
        ``start + i``.
    k, v: [K, H, D] — keys/values in position order: the already-written
        prefix occupies rows [0, start), the chunk's own keys rows
        [start, start + n).  K may exceed start + n (a padded gather);
        rows past a query's position are masked and contribute exactly
        zero, so padding never changes a value.
    Returns [n, H, D].

    Exactness: the masking construction is the decode oracle's (masked
    logits are NEG_INF, ``exp(NEG_INF - m)`` underflows to exactly 0.0,
    and ``x + 0.0 == x``), so masked keys contribute EXACTLY zero and
    padding the key axis never changes which values enter a row's
    reductions.  What chunking does change is einsum SHAPES (n query
    rows instead of the full prefix), and XLA picks reduction strategies
    per shape — values agree with full prefill at the reassociation ulp
    level (~1e-7 fp32), not bit for bit.  The oracle contract is
    therefore TOKEN identity: chunked prefill must reproduce full
    prefill token for token, greedy and seeded-stochastic, which
    tests/test_chunked_prefill.py pins — the same standard the fused
    decode step is held to (fused.py).
    Low-precision K/V (bf16 pools) are upcast to the query dtype before
    the einsums, exactly like the paged decode reference.
    """
    q = jnp.asarray(q)
    k = jnp.asarray(k).astype(q.dtype)
    v = jnp.asarray(v).astype(q.dtype)
    n, _, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("qhd,khd->hqk", q, k) * scale
    visible = (jnp.arange(k.shape[0], dtype=jnp.int32)[None, :]
               <= (start + jnp.arange(n, dtype=jnp.int32))[:, None])
    logits = jnp.where(visible[None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, v)


def chunk_prefill_attention(q, k_pool, v_pool, page_table, start,
                            scale=None, use_kernel=None, interpret=None,
                            layout="token", mesh=None, tp_axis=None,
                            k_scale=None, v_scale=None):
    """Paged chunked-prefill attention for ONE sequence: the chunk's K/V
    have ALREADY been scattered into the pools (positions
    [start, start + n)), so every key — prefix and chunk alike — is read
    through the page table.  Dispatch mirrors paged_decode_attention:
    the Pallas kernel on TPU (or when forced), the jnp gather reference
    elsewhere.

    q: [n, H, D]; k_pool/v_pool: one layer's pool (either layout);
    page_table: [max_pages] int32 (pad with 0); start: the chunk's first
    global position (prefix length).  Rows of q past the chunk's real
    length are bucket padding — their output is garbage-but-finite and
    the caller discards it.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    q = jnp.asarray(q)
    n, h, d = q.shape
    pt = jnp.asarray(page_table, jnp.int32)
    if not use_kernel:
        k = _gather_pool(jnp.asarray(k_pool), pt[None], 1, h, d, layout,
                         q.dtype, k_scale)[0]
        v = _gather_pool(jnp.asarray(v_pool), pt[None], 1, h, d, layout,
                         q.dtype, v_scale)[0]
        return chunk_prefill_attention_reference(q, k, v, start,
                                                 scale=scale)
    from ..ops.pallas.paged_attention import chunk_prefill_attention_kernel

    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return chunk_prefill_attention_kernel(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool), pt, start, scale,
        interpret=interpret, layout=layout, mesh=mesh, tp_axis=tp_axis,
        k_scale=k_scale, v_scale=v_scale)


def dense_causal_reference(q, k, v, scale=None):
    """Dense causal full-recompute attention — the oracle the paged path
    is measured against.  q, k, v: [T, H, D] for ONE sequence; returns
    [T, H, D] where row t attends over keys [0, t]."""
    q = jnp.asarray(q)
    t, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("qhd,khd->hqk", q, jnp.asarray(k)) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    logits = jnp.where(causal[None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, jnp.asarray(v))


# ------------------------- latent (MLA) pools -------------------------
def latent_ragged_attention_reference(q, pool, page_tables, starts, lens,
                                      kv_lens, scale, v_width):
    """Pure-jnp absorbed-form latent attention: the CPU path and the
    oracle of `latent_ragged_attention_kernel`, built like
    `ragged_paged_attention_reference` (masked keys contribute exactly
    0).  q: [T, H, W] absorbed queries; pool: [P, page_size, W] latent
    rows ``[c | k_rope]``, the key of every head whole and their value
    in the first `v_width` lanes.  Returns [T, H, v_width] float32;
    rows owned by no descriptor come back exactly 0."""
    q = jnp.asarray(q)
    t = q.shape[0]
    pt = jnp.asarray(page_tables, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    rows = jnp.asarray(pool)[pt]                  # [S, MP, page, W]
    rows = rows.reshape(pt.shape[0], -1, rows.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("thw,skw->sthk", q.astype(jnp.float32), rows,
                        precision="highest") * scale
    row = jnp.arange(t, dtype=jnp.int32)[None, :]
    mine = (row >= starts[:, None]) & (row < (starts + lens)[:, None])
    qpos = (kv_lens - lens)[:, None] + (row - starts[:, None])
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :]
    visible = (mine[:, :, None] & (col <= qpos[:, :, None]))[:, :, None, :]
    weights = jax.nn.softmax(jnp.where(visible, logits, NEG_INF), axis=-1)
    weights = jnp.where(visible, weights, 0.0)
    return jnp.einsum("sthk,skv->thv", weights, rows[..., :v_width],
                      precision="highest")


def latent_work_list(page_tables, starts, lens, kv_lens, page_size, n_rows,
                     use_kernel):
    """`ragged_work_list`'s sibling for a latent pool: the latent
    kernel's grid, built once a step for all layers; None where the jnp
    form runs."""
    if not use_kernel:
        return None
    from ..ops.pallas.paged_attention import latent_work_list as build

    return build(page_tables, starts, lens, kv_lens, page_size, n_rows)


def latent_ragged_attention(q, pool, page_tables, starts, lens, kv_lens,
                            scale, v_width, use_kernel, interpret=None,
                            work=None):
    """Absorbed-form attention over one layer's latent pool: the Pallas
    kernel, or the jnp form of the same (`use_kernel` resolved by the
    caller at trace time, as the engine resolves it once)."""
    if not use_kernel:
        return latent_ragged_attention_reference(
            q, pool, page_tables, starts, lens, kv_lens, scale, v_width)
    from ..ops.pallas.paged_attention import latent_ragged_attention_kernel

    return latent_ragged_attention_kernel(
        jnp.asarray(q), jnp.asarray(pool),
        jnp.asarray(page_tables, jnp.int32), starts, lens, kv_lens, scale,
        v_width, interpret=interpret, work=work)


# ---------------- grouped-query heads over a row pool -----------------
def gqa_ragged_attention_reference(q, pool, page_tables, starts, lens,
                                   kv_lens, scale, kv_heads, window=None):
    """Pure-jnp grouped-query attention over a paged row pool: the CPU
    path and the oracle of `gqa_ragged_attention_kernel`, built like
    `latent_ragged_attention_reference`.  q: [T, H, D], query head h
    reading KV head ``h // (H / kv_heads)``; pool: [P, page_size, lanes]
    rows ``[k_0 .. k_{n-1} | v_0 .. v_{n-1}]``; window: the keys a query
    sees counting its own, ``qpos - window + 1 .. qpos`` (None: from 0).
    A table entry behind a row's window may name a page that has gone
    back to its free list: what is gathered from it is selected away
    BEFORE any product, so nothing it holds (another sequence's rows, a
    NaN) reaches an output.  Returns [T, H, D] float32; rows owned by no
    descriptor come back exactly 0."""
    q = jnp.asarray(q)
    t, h, d = q.shape
    rep = h // kv_heads
    pt = jnp.asarray(page_tables, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    rows = jnp.asarray(pool)[pt]                  # [S, MP, page, lanes]
    rows = rows.reshape(pt.shape[0], -1, rows.shape[-1])
    row = jnp.arange(t, dtype=jnp.int32)[None, :]
    mine = (row >= starts[:, None]) & (row < (starts + lens)[:, None])
    qpos = (kv_lens - lens)[:, None] + (row - starts[:, None])   # [S, T]
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :]
    visible = mine[:, :, None] & (col <= qpos[:, :, None])       # [S, T, K]
    if window is not None:
        visible = visible & (col > qpos[:, :, None] - window)
    # a key some row of its descriptor sees; all else reads as zeros
    used = jnp.any(visible, axis=1)[:, :, None]                  # [S, K, 1]
    rows = jnp.where(used, rows, 0).astype(jnp.float32)
    k = rows[..., :kv_heads * d].reshape(*rows.shape[:2], kv_heads, d)
    v = rows[..., kv_heads * d:2 * kv_heads * d].reshape(k.shape)
    qg = q.astype(jnp.float32).reshape(t, kv_heads, rep, d)
    logits = jnp.einsum("tgrd,skgd->stgrk", qg, k,
                        precision="highest") * scale
    seen = visible[:, :, None, None, :]
    weights = jax.nn.softmax(jnp.where(seen, logits, NEG_INF), axis=-1)
    weights = jnp.where(seen, weights, 0.0)
    return jnp.einsum("stgrk,skgd->tgrd", weights, v,
                      precision="highest").reshape(t, h, d)


def gqa_work_lists(starts, lens, kv_lens, page_size, n_pages, n_rows,
                   window, use_kernel):
    """`latent_work_list`'s sibling for a row pool with window and full
    layers: ``{"window": list, "full": list}`` of the kernel's two
    grids, each built once a step for all layers of its kind; Nones
    where the jnp form runs."""
    if not use_kernel:
        return {"window": None, "full": None}
    from ..ops.pallas.gqa_paged_attention import gqa_work_list as build

    return {"window": build(starts, lens, kv_lens, page_size, n_pages,
                            n_rows, window),
            "full": build(starts, lens, kv_lens, page_size, n_pages,
                          n_rows)}


def gqa_ragged_attention(q, pool, page_tables, starts, lens, kv_lens, scale,
                         kv_heads, window, use_kernel, interpret=None,
                         work=None):
    """Grouped-query attention over one layer's row pool: the Pallas
    kernel, or the jnp form of the same."""
    if not use_kernel:
        return gqa_ragged_attention_reference(
            q, pool, page_tables, starts, lens, kv_lens, scale, kv_heads,
            window)
    from ..ops.pallas.gqa_paged_attention import gqa_ragged_attention_kernel

    return gqa_ragged_attention_kernel(
        jnp.asarray(q), jnp.asarray(pool), page_tables, starts, lens,
        kv_lens, scale, kv_heads, window, interpret=interpret, work=work)


# ------------- selected tokens of a latent pool (learned indexer) -------------
def row_positions(starts, lens, kv_lens, n_rows):
    """(desc [T], qpos [T]) int32 of the packed rows: each row's
    descriptor and its position in its sequence, -1 for a row no
    descriptor owns."""
    starts, lens, kv_lens = (jnp.asarray(x, jnp.int32)
                             for x in (starts, lens, kv_lens))
    row = jnp.arange(n_rows, dtype=jnp.int32)[None, :]
    mine = (row >= starts[:, None]) & (row < (starts + lens)[:, None])
    desc = jnp.argmax(mine, axis=0).astype(jnp.int32)
    qpos = kv_lens[desc] - lens[desc] + (row[0] - starts[desc])
    return desc, jnp.where(jnp.any(mine, axis=0), qpos, -1)


def dsa_index_scores_reference(q, w, keys, page_tables, desc):
    """Pure-jnp index scores: every row against every slot of its
    descriptor's page table, ``sum_j w_j relu(q_j . k)``.  q: [T, Hi, D],
    w: [T, Hi] float32, keys: [P, page_size, D].  [T, MP * page_size]
    float32, unmasked."""
    pt = jnp.asarray(page_tables, jnp.int32)
    k = jnp.asarray(keys)[pt].reshape(pt.shape[0], -1, keys.shape[-1])
    s = jnp.einsum("thd,tkd->thk", q.astype(jnp.float32),
                   k[desc].astype(jnp.float32), precision="highest")
    return jnp.einsum("thk,th->tk", jnp.maximum(s, 0.0), w,
                      precision="highest")


def dsa_index_scores(q, w, keys, page_tables, starts, lens, kv_lens, qpos,
                     use_kernel, interpret=None):
    """The learned indexer's scores [T, MP * page_size] float32 of each
    packed row's context, NEG_INF past the row's position and in rows no
    descriptor owns: the Pallas kernel, or the jnp form of the same."""
    if use_kernel:
        from ..ops.pallas.sparse_latent_attention import (
            indexer_scores_kernel)

        scores = indexer_scores_kernel(q, w, keys, page_tables, starts,
                                       lens, kv_lens, interpret=interpret)
    else:
        desc, _ = row_positions(starts, lens, kv_lens, q.shape[0])
        scores = dsa_index_scores_reference(q, w, keys, page_tables, desc)
    col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(col <= qpos[:, None], scores, NEG_INF)


# columns a block of the two-stage top-k (`exact_top_k`); rows a step
# selects over when few of its rows need a sort (`dsa_select`)
TOPK_BLOCK = 8
FEW_LONG_ROWS = 64


def exact_top_k(scores, k):
    """[T, k] int32: the columns of each row's k largest scores, exactly,
    in two sorts of an eighth of the row's width each: the k blocks of
    `TOPK_BLOCK` columns with the largest maxima hold every one of the k
    largest (a block that holds one and is left out would leave k other
    blocks whose maxima, k more scores, stand at or above it), so the k
    largest of those blocks' columns are the row's.  Ties at the k-th
    score may be broken otherwise than one sort over the row breaks
    them."""
    t, n = scores.shape
    if n % TOPK_BLOCK or n <= 2 * k * TOPK_BLOCK:
        return jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    blocks = scores.reshape(t, n // TOPK_BLOCK, TOPK_BLOCK)
    _, chosen = jax.lax.top_k(jnp.max(blocks, axis=-1), k)         # [T, k]
    candidates = jnp.take_along_axis(blocks, chosen[:, :, None], axis=1)
    _, best = jax.lax.top_k(candidates.reshape(t, k * TOPK_BLOCK), k)
    block = jnp.take_along_axis(chosen, best // TOPK_BLOCK, axis=1)
    return (block * TOPK_BLOCK + best % TOPK_BLOCK).astype(jnp.int32)


def dsa_select(scores_fn, qpos, top_k, n_cols):
    """(positions [T, K], counts [T]) int32: each row's selected tokens.
    A row whose context holds at most K tokens selects all of them,
    0 .. qpos, without a sort; otherwise the exact top K of its scores
    (`scores_fn()`, computed only in a step that has such a row; the
    order of the K is the sort's), sorted over those rows alone where
    they are at most `FEW_LONG_ROWS` (a step of decode rows), else over
    all.  counts = min(qpos + 1, K), 0 for a row no descriptor owns."""
    t = qpos.shape[0]
    every = jnp.broadcast_to(jnp.arange(top_k, dtype=jnp.int32), (t, top_k))
    counts = jnp.clip(qpos + 1, 0, top_k)
    if n_cols <= top_k:          # no context of this bucket is longer
        return every, counts
    long = qpos + 1 > top_k
    few = min(FEW_LONG_ROWS, t)

    def over_all(scores):
        return exact_top_k(scores, top_k)

    def over_few(scores):
        rows = jnp.nonzero(long, size=few, fill_value=0)[0]
        return every.at[rows].set(exact_top_k(scores[rows], top_k))

    def pick():
        scores = scores_fn()
        if few == t:
            return over_all(scores)
        return jax.lax.cond(jnp.sum(long) <= few, over_few, over_all,
                            scores)

    picked = jax.lax.cond(jnp.any(long), pick, lambda: every)
    return jnp.where(long[:, None], picked, every), counts


def selected_slots(positions, counts, page_tables, desc, page_size):
    """[T, K] int32: each selected position's pool slot ``page *
    page_size + row`` through its row's page table; 0 past the count."""
    pt = jnp.asarray(page_tables, jnp.int32)
    page = jnp.take_along_axis(pt[desc], positions // page_size, axis=1)
    held = jnp.arange(positions.shape[1], dtype=jnp.int32)[None, :] \
        < counts[:, None]
    return jnp.where(held, page * page_size + positions % page_size, 0)


def sparse_latent_attention_reference(q, pool, slots, counts, sink, v_width):
    """Pure-jnp attention over selected latent rows, with a sink: the
    CPU path and the oracle of `sparse_latent_attention_kernel`.  Returns
    [T, H, v_width] float32."""
    from ..ops.pallas.sparse_latent_attention import unpack_rows

    rows = unpack_rows(jnp.asarray(pool).reshape(-1, pool.shape[-1])[slots])
    held = (jnp.arange(slots.shape[1], dtype=jnp.int32)[None, :]
            < counts[:, None])                              # [T, K]
    rows = jnp.where(held[:, :, None], rows, 0.0)
    z = jnp.einsum("thn,tkn->thk", q.astype(jnp.float32), rows,
                   precision="highest")
    z = jnp.where(held[:, None, :], z, NEG_INF)
    sink = jnp.asarray(sink, jnp.float32)[None, :, None]
    m = jnp.maximum(jnp.max(z, axis=-1, keepdims=True), sink)
    p = jnp.where(held[:, None, :], jnp.exp(z - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m)
    return jnp.einsum("thk,tkv->thv", p / denom, rows[..., :v_width],
                      precision="highest")


def sparse_latent_attention(q, pool, slots, counts, sink, v_width,
                            use_kernel, interpret=None):
    """Attention of each packed row over its selected latent rows: the
    Pallas kernel, or the jnp form of the same."""
    if not use_kernel:
        return sparse_latent_attention_reference(q, pool, slots, counts,
                                                 sink, v_width)
    from ..ops.pallas.sparse_latent_attention import (
        sparse_latent_attention_kernel)

    return sparse_latent_attention_kernel(q, pool, slots, counts, sink,
                                          v_width, interpret=interpret)
