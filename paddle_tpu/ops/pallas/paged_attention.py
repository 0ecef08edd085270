"""Paged attention over a paged KV cache as Pallas TPU kernels.

The decode-side counterpart of flash_attention.py (Ragged Paged
Attention, arxiv 2604.15464).  No kernel here materializes a
per-sequence contiguous KV copy — page numbers ride in as scalar-prefetch
operands and the BlockSpec index_map DMAs each sequence's pages straight
out of the pool.  Three kernels:

DECODE (`paged_decode_attention_kernel`, Lq == 1) and CHUNK
(`chunk_prefill_attention_kernel`, one sequence's prefill chunk), the
legacy step modes' pair — dense grids over the page table:

    grid = (B, H, max_pages)          # pages innermost, sequential
    k block = pool_t[h, page_table[b, i]]       # [1, 1, page_size, D]

Pages past a sequence's length are skipped via @pl.when on the
prefetched seq_lens; the page table pads unused slots with page 0, which
is always a valid DMA target.

RAGGED (`ragged_paged_attention_kernel`), the serving engine's one
kernel: decode rows, prefill chunks and speculative verify runs in one
packed token axis under ``[start, len, kv_len]`` descriptors.  Its grid
is NOT the bounding box descriptors x pages x query tiles (of which 96 %
did nothing at the benchmark's shapes: PERF.md, PR 25) but a COMPACTED
list of the cells that compute, built in the trace from the descriptors
(`ragged_work_list`) and walked under a traced bound, and a cell is not
one 16-token page of one head (0.3 us a grid step whatever it computes,
a sixteenth of an MXU pass: 5 % of the memory roofline, PERF.md, PR 35)
but a GROUP of pages x a BLOCK of heads (`ragged_cell_shape`):

    grid = (H / Hb, live cells)       # count traced, <= ragged_grid_cells
    cell w = (descriptor s, page group g, query tile qt)   # one SMEM word
    k block = pool_t[h0:h0 + Hb, pages of group g]   # G strided DMAs from
                                      # the pool in HBM, double-buffered,
                                      # once for all the tiles of (s, g)
    order: descriptors as given, a descriptor's groups ascending, the
           tiles that see a group innermost

A 1-token decode row costs one grid step per G pages of its context; a
padding descriptor, a group past a row's horizon and a tile outside a
descriptor's rows cost none.

Online softmax state (m, l, acc) lives in VMEM scratch across the page
axis exactly like the flash forward kernel.

Layouts are chosen Mosaic tile-legal by construction: pools are read as
[H, P, page_size, D] so every block's trailing two dims are full array
dims (page_size, D); decode q/out ride as [B, H, 1, D] with (1, 1, 1, D)
blocks, ragged q/out as one whole-axis [Hb, T, D] block a head block.

INT8 POOLS: every public kernel takes optional ``k_scale``/``v_scale``
[P, H] per-page per-head abs-max arrays (generation.quantized_kv).
In the decode and chunk kernels they ride as two more blocked VMEM
operands, re-laid per call as one 128-lane row per 128 pages
(``_scale_rows``) and indexed through the page table like K/V, so their
footprint does not grow with the pool (as scalar-prefetch operands the
two [P, H] arrays overflowed the 1 MiB SMEM at num_pages >= 1024), and
each live grid cell dequantizes its page block in-kernel — ``int8 *
(scale * 1/127)`` with the exact expression the jnp gather references
use — before the score matmul.  The ragged kernel gathers the scales of
each descriptor's pages in front of the call (``_group_scales``) and
multiplies a page's COLUMNS of the scores and of the weights by them:
the same numbers up to rounding, no int8 block is rewritten.  The jnp
references dequantize their gathered O(tokens) views; nobody ever
materializes a dequantized pool.

SMEM holds what the kernels look pages up in: the page tables, the
ragged kernel's work list (one word a cell; its limit is in
`ragged_paged_attention_kernel`'s docstring) and the descriptors.

MESH-NATIVE dispatch: every public kernel takes ``mesh`` / ``tp_axis``.
Heads are fully independent in all three grids, so under a head-sharded
tensor-parallel mesh the kernel runs as a ``shard_map`` whose per-shard
program is the SAME single-device kernel on ``num_heads / tp`` heads
over that shard's slice of the pool — q/out split on the head axis,
pools split per ``kv_pool_spec``, page tables, descriptors and the work
list replicated.  NO collective enters the kernel: the generation
stack's two per-layer Megatron allreduces stay XLA-placed outside it
(exactly where GSPMD puts them on the jnp reference path), which is the
layout the EQuARX-style quantized-collective follow-on assumes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, resolve_interpret

# int8 KV dequant factor: MUST stay bit-equal to
# generation.quantized_kv.INV_QMAX — the jnp gather references multiply
# by the same constant, which is what keeps kernel and reference
# operands bitwise identical (kept as a literal here so the kernel
# module never imports the generation package)
INV_QMAX = np.float32(1.0 / 127.0)


def _require_scales(pool, k_scale, v_scale):
    """int8 pools MUST arrive with their [P, H] scale arrays — and only
    int8 pools: raw int8 codes decoded as values, or float values
    multiplied by scale/127, are both finite and plausible-looking
    corruption, so a call site that forgot (or half-threaded, or
    wrongly threaded) the cache's layer_scales() fails loudly here
    instead of mis-attending."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "k_scale and v_scale must be passed together — got one "
            "without the other (thread BOTH of the cache's "
            "layer_scales() arrays)")
    if k_scale is None and pool.dtype == jnp.int8:
        raise ValueError(
            "int8 KV pool passed to a paged-attention kernel without "
            "k_scale/v_scale — thread the cache's layer_scales() through")
    if k_scale is not None and pool.dtype != jnp.int8:
        raise ValueError(
            f"k_scale/v_scale passed with a {pool.dtype} pool — scales "
            "belong to int8 pools only (float values would be silently "
            "multiplied by scale/127)")


# int8 scales reach the kernels as [H, ceil(P / 128), 1, 128]: a
# (1, 1, 1, 128) block — tile-legal, its trailing dims are full dims —
# holds the scales of 128 consecutive pages of one head
_SCALE_LANES = 128


def _scale_rows(scale):
    """[P, H] per-page per-head scales -> [H, ceil(P/128), 1, 128]."""
    p, h = scale.shape
    rows = -(-p // _SCALE_LANES)
    st = jnp.transpose(jnp.asarray(scale, jnp.float32))
    if rows * _SCALE_LANES != p:
        st = jnp.pad(st, ((0, 0), (0, rows * _SCALE_LANES - p)))
    return st.reshape(h, rows, 1, _SCALE_LANES)


def _pool_specs(page_of, page_size, d, n_scales):
    """in_specs of the k and v page blocks plus, for int8 pools, their
    `_scale_rows` operands — all indexed by
    ``page_of(*grid_ids, *prefetch_refs) -> (head, page)``, so a scale
    block is the lane row that holds its page block's scale."""
    def lane_row(*ids_and_refs):
        head, page = page_of(*ids_and_refs)
        return (head, page // _SCALE_LANES, 0, 0)

    page = pl.BlockSpec(
        (1, 1, page_size, d),
        lambda *ids_and_refs: (*page_of(*ids_and_refs), 0, 0))
    scale = pl.BlockSpec((1, 1, 1, _SCALE_LANES), lane_row)
    return [page, page] + [scale] * n_scales


def _dequant_page(block, s_ref, page):
    """int8 page block -> f32 values: ``int8 * (scale * 1/127)``, the
    jnp references' expression.  `page`'s scale comes out of its lane
    row by a one-hot select and a sum of exact zeros, bit for bit."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _SCALE_LANES), 1)
    scale = jnp.sum(
        jnp.where(lane == page % _SCALE_LANES, s_ref[0, 0], 0.0),
        axis=1, keepdims=True)                       # [1, 1]
    return block.astype(jnp.float32) * (scale * INV_QMAX)


def _split_refs(refs, quantized):
    """Kernel operand refs past the scalar-prefetch ones, as ``(q, k, v,
    ks, vs, o, acc, m, l)`` — ks/vs None unless quantized."""
    if quantized:
        return refs
    return (*refs[:3], None, None, *refs[3:])


_STATE_ROWS = 8  # scratch rows; every row holds the same value so all
# scratch traffic is full-width vector ops (the Mosaic-proven layout)

# query-axis tile of the LATENT kernel and the default of
# `ragged_query_tiles`: 8 is the Mosaic sublane width (the flash
# kernels' proven minor-axis tile).  The per-head ragged kernel states
# its own tile in `ragged_cell_shape`.
RAGGED_Q_BLOCK = 8

# ---- the per-head RAGGED kernel's cell (`ragged_cell_shape`) ----------
# Keys of one context a cell multiplies: a grid step costs ~0.35 us
# whatever it computes and a 16-key product fills an eighth of an MXU
# pass (PERF.md, PRs 25, 33), so a cell holds a GROUP of pages.
RAGGED_CELL_TOKENS = 128
# Query rows of a tile.  A product's cost is loading the K tile into
# the MXU, not streaming the rows through it, so a wider tile costs a
# decode row little and saves a chunk whole cells.
RAGGED_CELL_ROWS = 8
# The most heads whose pages one strided DMA brings, and the VMEM a
# cell's blocks may take (the double-buffered K and V groups, the q and
# output blocks Pallas double-buffers, the online-softmax state over
# the packed axis); the call raises Mosaic's scoped limit to
# `RAGGED_VMEM_LIMIT`, under v5e's 128 MiB.
RAGGED_CELL_HEADS = 32
RAGGED_VMEM_BUDGET = 40 << 20
RAGGED_VMEM_LIMIT = 96 << 20


def _reject_mesh_sharded_pool(pool):
    """Loud failure over silent corruption: the raw kernel is a
    single-device program — handed a pool committed to a multi-device
    NamedSharding (the tensor-parallel generation mesh) WITHOUT the
    matching ``mesh=`` argument, pallas_call would either fail opaquely
    or compute over one shard as if it were the whole pool.  Passing
    ``mesh=``/``tp_axis=`` runs the shard_map'd form instead (the
    supported mesh path); this guard catches direct callers that forgot
    to.  Tracers (pools inside a jit or shard_map trace) pass through
    untouched — the in-trace caller's own sharding machinery governs
    there."""
    try:
        sharding = getattr(pool, "sharding", None)
    except Exception:
        return  # tracer without a committed sharding: not our problem
    from jax.sharding import NamedSharding

    if (isinstance(sharding, NamedSharding)
            and len(sharding.device_set) > 1):
        raise NotImplementedError(
            "Pallas paged attention over a mesh-sharded KV pool needs "
            "the mesh spelled out: pass mesh=/tp_axis= to run the "
            "shard_map'd kernel (per-shard program over num_heads/tp "
            "heads), or use the jnp reference path (use_kernel=False) — "
            "GSPMD partitions it over the head axis.  Calling the raw "
            "single-device kernel on a sharded pool would compute over "
            "one shard as if it were the whole pool.")


def _head_shard_map(body, mesh, tp_axis, layout, q, k_pool, v_pool,
                    *scalars, scales=None):
    """Run `body` (a single-device kernel call) as a shard_map over the
    head-sharded tensor-parallel mesh: q and the output split on their
    head axis (axis 1 in all three kernels), the pools split per
    ``kv_pool_spec``, page tables / descriptors / lengths replicated.
    Heads are fully independent in every grid, so the per-shard program
    is exactly the existing kernel on num_heads/tp heads over that
    shard's slice of the pool — no collective is issued here or inside
    the kernel.

    `scales` (int8 pools): the ``(k_scale, v_scale)`` [P, H] arrays —
    sharded on THEIR head axis (kv_scale_spec), so each shard
    dequantizes its own heads with its own scale slice; body then
    receives ``(q, k_pool, v_pool, k_scale, v_scale, *scalars)``."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.collective import shard_map
    from ...parallel.sharding_annotations import kv_pool_spec

    if tp_axis is None:
        tp_axis = tuple(mesh.axis_names)[0]
    tp = int(mesh.shape[tp_axis])
    h = q.shape[1]
    if h % tp:
        raise ValueError(
            f"num_heads={h} is not divisible by tp_degree={tp} (axis "
            f"{tp_axis!r} of the mesh): the shard_map'd kernel splits "
            f"the head axis, so heads must divide evenly")
    qspec = P(None, tp_axis, None)
    pspec = P(*kv_pool_spec(layout, tp_axis))
    args = (q, k_pool, v_pool)
    specs = (qspec, pspec, pspec)
    if scales is not None:
        args += tuple(scales)
        specs += (P(None, tp_axis),) * len(scales)
    fn = shard_map(body, mesh=mesh,
                   in_specs=specs + (P(),) * len(scalars),
                   out_specs=qspec)
    return fn(*args, *scalars)


def ragged_query_tiles(n_rows, q_block=None):
    """``(q_block, n_tiles)`` a ragged kernel cuts a packed axis of
    `n_rows` rows into: `q_block` rows a tile (RAGGED_Q_BLOCK, the
    latent kernel's, when None), the whole axis when it is shorter."""
    qb = max(1, min(int(q_block or RAGGED_Q_BLOCK), int(n_rows)))
    return qb, -(-int(n_rows) // qb)


def ragged_cell_shape(page_size, n_pages, n_rows, n_heads=1, head_dim=128,
                      itemsize=4):
    """``(G, Hb, q_block)`` — what one cell of the per-head ragged
    kernel holds, from what the call can observe: G consecutive logical
    pages of one descriptor (`RAGGED_CELL_TOKENS` in pages, at least
    one, never more than the page tables hold: the pages bucket), a
    tile of `q_block` packed rows, and a block of Hb heads: the largest
    divisor of `n_heads` (after a mesh has split them) up to
    `RAGGED_CELL_HEADS` whose blocks fit `RAGGED_VMEM_BUDGET` —

        K and V groups, double-buffered   4 x G x page_size x lanes x itemsize
        q, output (x 2 each), acc, m, l   7 x padded rows x lanes x 4

    a head, lanes the head's width in whole 128-lane rows.  The ONE
    statement of the rule: the work list and the grid's capacity (which
    read G and the tile alone: they do not depend on the heads), the
    kernel, the engine's counters and gauges and the tests read it
    here.  Nothing else sets it: no option, no keyword."""
    per = max(1, min(RAGGED_CELL_TOKENS // int(page_size), int(n_pages)))
    qb, n_tiles = ragged_query_tiles(n_rows, RAGGED_CELL_ROWS)
    lanes = -(-int(head_dim) // 128) * 128
    a_head = (4 * per * int(page_size) * lanes * int(itemsize)
              + 7 * n_tiles * qb * lanes * 4)
    fits = [hb for hb in range(1, min(int(n_heads), RAGGED_CELL_HEADS) + 1)
            if n_heads % hb == 0 and hb * a_head <= RAGGED_VMEM_BUDGET]
    return per, max(fits, default=1), qb


def _held_to(capacity, live):
    """A list's capacity, or its `live` count (traced or not) held to
    [1, capacity]: the steps a grid walks."""
    if live is None:
        return capacity
    if isinstance(live, jax.Array):
        return jnp.clip(live, 1, capacity)
    return min(max(int(live), 1), capacity)


def ragged_grid_cells(n_seqs, n_pages, n_rows, page_size, live=None):
    """Grid steps a head block of the ragged kernel: the CAPACITY of
    its work list, or, given the `live` (descriptor, page group, tile)
    cells of a step's descriptors, the steps the kernel walks for them
    — exactly those (the grid's bound is traced), but never fewer than
    the one step that writes the output and never more than the list
    holds.  The ONE home of the grid's size: the kernel's `grid=` (on
    the list's traced count), the engine's `generation.step_grid_cells`
    (G x this, on `ragged_score_groups`, the host's mirror of that
    count) and the tests call it.

    THE CAPACITY, and what it assumes.  Descriptors own DISJOINT row
    ranges of the packed axis (`RaggedStep.pad` hands the engine's
    back-to-back packing over; the host-free loop's layout is the
    static ``s * (1 + K)``).  Every descriptor but the first begins
    inside exactly one tile, so two of them share at most the tile the
    later one begins in: the (descriptor, tile) pairs that intersect
    number at most ``n_tiles + n_seqs - 1``, each meets at most
    ``ceil(n_pages / G)`` groups, and the list is sized to that
    product.  Ranges that overlap can exceed it; the kernel then
    returns NaN, not the attention of the cells that fitted."""
    per, _, qb = ragged_cell_shape(page_size, n_pages, n_rows)
    capacity = ((ragged_query_tiles(n_rows, qb)[1] + n_seqs - 1)
                * -(-n_pages // per))
    return _held_to(capacity, live)


def _cell_bits(n_seqs, n_pages, n_tiles):
    """Shifts of a packed work-list cell ``descriptor | page | tile``
    (tile in the low bits; `n_pages` counts page GROUPS where a list's
    cells are groups).  One int32 a cell keeps the list at one SMEM
    word per entry."""
    tile_bits = (n_tiles - 1).bit_length()
    page_bits = (n_pages - 1).bit_length()
    if tile_bits + page_bits + (n_seqs - 1).bit_length() > 31:
        raise ValueError(
            f"ragged work list: {n_seqs} descriptors x {n_pages} pages x "
            f"{n_tiles} query tiles do not pack into one int32 cell")
    return tile_bits, tile_bits + page_bits


def ragged_work_list(starts, lens, kv_lens, page_size, n_pages, n_rows):
    """The per-head ragged kernel's grid, IN THE TRACE: every (descriptor,
    page group, query tile) cell that computes, in the order the kernel
    walks them — descriptors as given, a descriptor's groups ascending,
    the tiles that see a group innermost (so the kernel fetches a
    group's pages once for all the tiles of a chunk, and each row meets
    its pages in ascending order).  A cell is live by the rule
    `ragged_score_groups` mirrors on the host: the tile meets the
    descriptor's rows and the group's FIRST page starts at or under the
    horizon of the tile's last in-span row.  Tiles are monotone in that
    horizon, so the tiles of a (descriptor, group) are a suffix
    ``[first, t1]`` of the descriptor's tiles: a count per (descriptor,
    group) and a running sum give the w-th cell in closed form.

    Returns ``(cells [W], count [1])`` int32, W the capacity
    `ragged_grid_cells` states: cell w's packed ``descriptor | group |
    tile`` word (`_cell_bits` over the groups).  Its physical pages are
    not on the list: the kernel looks them up in the page tables, which
    ride whole in SMEM beside it.  Entries past `count` repeat the last
    live one, whose pages are already resident; the kernel never
    computes them.  Pure jnp over traced descriptors: built once a step
    (model `_ragged_core_fn`) or once an iteration of the host-free
    loop, and shared by the layers."""
    n_seqs = jnp.asarray(starts).shape[0]
    per, _, qb = ragged_cell_shape(page_size, n_pages, n_rows)
    n_tiles = ragged_query_tiles(n_rows, qb)[1]
    n_groups = -(-n_pages // per)
    tile_bits, group_bits = _cell_bits(n_seqs, n_groups, n_tiles)
    st, ln, kv = (jnp.asarray(x, jnp.int32)[:, None]
                  for x in (starts, lens, kv_lens))            # [S, 1]
    end = st + ln
    t1 = jnp.minimum((end - 1) // qb, n_tiles - 1)
    # group g's first page starts under the horizon of tile qt's last
    # in-span row iff min((qt + 1) * qb, end) >= need
    need = (jnp.arange(n_groups, dtype=jnp.int32)[None, :]
            * (per * page_size) - (kv - ln) + st + 1)          # [S, G]
    first = jnp.maximum(st // qb, -(-need // qb) - 1)
    tiles = jnp.where((ln > 0) & (need <= end),
                      jnp.maximum(t1 - first + 1, 0), 0).reshape(-1)
    upto = jnp.cumsum(tiles)
    count = upto[-1]
    capacity = ragged_grid_cells(n_seqs, n_pages, n_rows, page_size)
    w = jnp.minimum(jnp.arange(capacity, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the (descriptor, group) of cell w is the last one that starts at
    # or under w: a mark at every pair's start and a running sum, not a
    # search (`latent_work_list` says what the search cost)
    marks = jnp.zeros((capacity,), jnp.int32).at[upto - tiles].add(
        1, mode="drop")
    pair = jnp.clip(jnp.cumsum(marks)[w] - 1, 0, n_seqs * n_groups - 1)
    tile = jnp.clip(first.reshape(-1)[pair]
                    + w - (upto[pair] - tiles[pair]), 0, n_tiles - 1)
    cells = ((pair // n_groups) << group_bits
             | (pair % n_groups) << tile_bits | tile)
    return cells.astype(jnp.int32), count.reshape(1).astype(jnp.int32)


def _pages_seen(starts, lens, kv_lens, page_size, n_pages, n_rows, q_block):
    """[S, Q] in numpy: the pages of descriptor s that query tile q
    sees — those that start at or under the position of the tile's last
    in-span row — and 0 where the tile misses the descriptor's rows.
    The host's mirror of the lists' skip rule."""
    qb, n_tiles = ragged_query_tiles(n_rows, q_block)
    st, ln, kv = (np.asarray(x, np.int64)[:, None]
                  for x in (starts, lens, kv_lens))            # [S, 1]
    end = st + ln
    qt = np.arange(n_tiles)[None, :]                           # [1, Q]
    meets = (ln > 0) & (qt >= st // qb) & (qt <= (end - 1) // qb)
    horizon = kv - ln + (np.minimum((qt + 1) * qb, end) - 1 - st)
    return np.where(meets, np.clip(horizon // int(page_size) + 1, 0,
                                   int(n_pages)), 0)


def ragged_score_blocks(starts, lens, kv_lens, page_size, n_pages, n_rows,
                        q_block=RAGGED_Q_BLOCK):
    """Host-side mirror of the tiled kernels' skip rule — the FLOP-proxy
    counter `generation.step_score_blocks` is set from.

    Returns ``(tiled, untiled)``: the (query tile, page) pairs a kernel
    with `q_block`-row tiles has to multiply for these descriptors per
    head, and the number an UNTILED kernel (full packed token axis per
    live (descriptor, page)) would, in the same tile units, so "tiled <
    untiled" is the measured statement that out-of-span work was
    skipped.  The engine passes the tile of the kernel it runs
    (`ragged_cell_shape` for the per-head one)."""
    seen = _pages_seen(starts, lens, kv_lens, page_size, n_pages, n_rows,
                       q_block)
    lens = np.asarray(lens, np.int64)
    kv_lens = np.asarray(kv_lens, np.int64)
    pages_live = np.minimum(-(-kv_lens // int(page_size)), int(n_pages))
    untiled = int((seen.shape[1] * pages_live)[(lens > 0)
                                               & (kv_lens > 0)].sum())
    return int(seen.sum()), untiled


def ragged_score_groups(starts, lens, kv_lens, page_size, n_pages, n_rows):
    """Host-side mirror of `ragged_work_list`'s count: the live
    (descriptor, page group, tile) cells of these descriptors, which is
    what the engine's `generation.step_grid_cells` is set from on a
    per-head pool (times G: the page SLOTS walked, full or padded)."""
    per, _, qb = ragged_cell_shape(page_size, n_pages, n_rows)
    seen = _pages_seen(starts, lens, kv_lens, page_size, n_pages, n_rows, qb)
    return int((-(-seen // per)).sum())


def _decode_kernel(pt_ref, sl_ref, *refs, page_size, n_pages,
                   quantized=False):
    """refs: q/k/v + ``[ks_ref, vs_ref]`` (quantized only — the
    `_scale_rows` lane rows holding this cell's page) + o + the three
    scratch buffers.  In-kernel dequant: the int8 page block multiplies
    by its ONE per-(page, head) factor ``scale * (1/127)`` before the
    score matmul — the same elementwise expression the jnp reference
    applies to its gathered view."""
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = \
        _split_refs(refs, quantized)
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = sl_ref[b]
    # page i covers positions [i*page_size, (i+1)*page_size): it runs iff
    # its first position is live; later positions are masked below
    @pl.when(i * page_size < seq_len)
    def _compute():
        q = q_ref[0, 0]                            # [1, D] (scale folded)
        k = k_ref[0, 0]                            # [page_size, D]
        v = v_ref[0, 0]
        if quantized:
            page = pt_ref[b, i]
            k = _dequant_page(k, ks_ref, page)
            v = _dequant_page(v, vs_ref, page)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)   # ragged tail of page
        m_prev = jnp.max(m_ref[...])
        m_cur = jnp.maximum(m_prev, jnp.max(s))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                     # [1, page_size]
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)    # masked rows: exactly 0
        l_cur = jnp.max(l_ref[...]) * alpha + jnp.sum(p)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.broadcast_to(
            pv, acc_ref.shape)
        m_ref[...] = jnp.full_like(m_ref, m_cur)
        l_ref[...] = jnp.full_like(l_ref, l_cur)

    @pl.when(i == n_pages - 1)
    def _finalize():
        l = jnp.max(l_ref[...])
        safe_l = jnp.where(l > 0.0, l, 1.0)        # empty sequence: zeros
        o_ref[0, 0] = (acc_ref[...] / safe_l)[0:1].astype(o_ref.dtype)


def _chunk_kernel(pt_ref, info_ref, *refs, page_size, n_pages, n_rows,
                  quantized=False):
    """Chunked-prefill attention for ONE sequence: n_rows chunk queries
    (query row r at global position start + r) attend over every key the
    page table holds — the already-written prefix AND the chunk's own
    freshly scattered keys — with a per-row causal mask.  Online-softmax
    state is [n_rows, ...] (the decode kernel's, grown from 1 query row
    to the chunk), accumulated across the page axis.  Quantized pools
    add the scale lane-row refs after q/k/v and dequantize each page
    block in-kernel (see _decode_kernel)."""
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = \
        _split_refs(refs, quantized)
    i = pl.program_id(1)
    start = info_ref[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # page i covers positions [i*page_size, (i+1)*page_size): it runs iff
    # its first position is visible to SOME query (the last row sees the
    # most: positions <= start + n_rows - 1)
    @pl.when(i * page_size <= start + n_rows - 1)
    def _compute():
        q = q_ref[0]                               # [n_rows, D]
        k = k_ref[0, 0]                            # [page_size, D]
        v = v_ref[0, 0]
        if quantized:
            page = pt_ref[i]
            k = _dequant_page(k, ks_ref, page)
            v = _dequant_page(v, vs_ref, page)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, page_size), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, page_size), 0)
        s = jnp.where(pos <= qpos, s, NEG_INF)     # causal, per query row
        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)   # [n, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                     # [n, page_size]
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)    # masked keys: exactly 0
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(i == n_pages - 1)
    def _finalize():
        l = jnp.max(l_ref[...], axis=1, keepdims=True)
        safe_l = jnp.where(l > 0.0, l, 1.0)  # fully masked pad rows
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _in_hbm(pool, interpret):
    """The pool held in HBM by name.  Left to itself XLA:TPU's
    memory-space assignment copies a whole pool that fits v5e's 128 MiB
    of VMEM there in front of the call: a pool-sized operation a layer
    a step (compile-only for v5e, PR 34)."""
    if resolve_interpret(interpret):
        return pool
    return pltpu.with_memory_space_constraint(pool, pltpu.HBM)


def _ragged_kernel(pt_ref, cell_ref, cnt_ref, st_ref, ln_ref, kv_ref, *refs,
                   page_size, per, heads, n_pages, q_block, tile_bits,
                   group_bits, capacity, quantized=False):
    """RAGGED mixed-batch paged attention, QUERY-TILED (the RPA paper's
    kernel shape), over a COMPACTED grid: packed query rows (decode
    singletons AND prefill-chunk runs in one token axis) attend through
    per-descriptor page tables.  Descriptor s owns packed rows
    [st_ref[s], st_ref[s] + ln_ref[s]); row r of s sits at global
    position kv_ref[s] - ln_ref[s] + (r - st_ref[s]) and sees keys
    [0, position].

    The grid is (head block, w): step w is the w-th LIVE (descriptor,
    page group, query tile) cell of `ragged_work_list` — cell_ref[w]
    names it — for `heads` heads at once: one batched
    ``[heads, q_block, D] x [heads, per * page_size, D]`` score product,
    one softmax update and one value product over the group's keys.
    The pools stay in HBM: a cell's `per` pages are not neighbours
    there, so each is brought by one strided DMA ``pool[h0:h0 + heads,
    page]`` (a page of every head of the block) into its slot of one
    half of `kbuf` / `vbuf`, while the group before multiplies out of
    the other half.  The tiles of one (descriptor, group) are
    consecutive on the list and share the fetch: a cell whose
    predecessor names the same group starts and waits for no copy, so a
    chunk's context is read once a call, not once a tile.  For a slot
    past the descriptor's last page nothing is fetched: its columns,
    reckoned from the slot's own logical page, lie past every row's
    horizon and are masked, and its V rows are zeroed (`_arrived`).
    `slot_ref` holds which half the current group lives in.  The second
    grid bound is TRACED (cnt_ref[0], held to [1, capacity]): steps at
    or past the count — the lone step of an all-padding batch — compute
    nothing.
    Online-softmax state spans the whole (tile-padded) token axis in
    scratch; each cell updates ITS tile's row slice.  Rows of a tile
    the descriptor doesn't own see an all-NEG_INF score row, whose
    update is the exact identity (alpha == exp(0) == 1, sum(p) == 0),
    so tiles straddling a descriptor boundary stay exact.  int8 pools
    add two refs after q/k/v: the group's per-(head, page) scales
    ``[heads, 1, per]``, gathered through the page tables in front of
    the call; a scale is constant over a page's keys, so it multiplies
    the page's COLUMNS of the scores (K) and of the weights (V) instead
    of the int8 blocks."""
    if quantized:
        q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref, *scratch = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref = scratch
    h0 = pl.program_id(0) * heads
    w = pl.program_id(1)
    count = cnt_ref[0]
    n_keys = per * page_size
    group_mask = (1 << (group_bits - tile_bits)) - 1

    def group_of(step):
        """The (descriptor | group) bits of the list's cell `step`."""
        return cell_ref[step] >> tile_bits

    fresh = (w == 0) | (group_of(w) != group_of(jnp.maximum(w - 1, 0)))

    def slots(step, live, dead=None):
        """`live(g, page)` for the slots of the list's cell `step` that
        hold a page of its descriptor's context, `dead(g)` for the
        slots past its last page: nothing is fetched for those."""
        word = cell_ref[step]
        s = word >> group_bits
        first = ((word >> tile_bits) & group_mask) * per
        last = jnp.clip((kv_ref[s] - 1) // page_size, 0, n_pages - 1)
        for g in range(per):
            # slot 0 is live on every cell: a group is on the list by
            # its first page, and an all-padding batch's lone step
            # fetches page 0 of its descriptor's table
            @pl.when(first + g <= last)
            def _():
                live(g, pt_ref[s * n_pages + first + g])

            if dead is not None:
                @pl.when(first + g > last)
                def _():
                    dead(g)

    def copies(half, act):
        """`slots`' `live`: start, or wait for, a slot's K and V pages
        of the head block into `half` of the buffers."""
        def live(g, page):
            for pool, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                act(pltpu.make_async_copy(
                    pool.at[pl.ds(h0, heads), page],
                    buf.at[half, :, pl.ds(g * page_size, page_size)],
                    sem.at[half]))

        return live

    @pl.when(w == 0)
    def _first():
        slot_ref[0] = 0
        slots(w, copies(0, lambda copy: copy.start()))
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(fresh & (w > 0))
    def _flip():
        slot_ref[0] = 1 - slot_ref[0]

    half = slot_ref[0]

    @pl.when((w + 1 < pl.num_programs(1))
             & (group_of(jnp.minimum(w + 1, capacity - 1)) != group_of(w)))
    def _next():
        slots(w + 1, copies(1 - half, lambda copy: copy.start()))

    @pl.when(fresh)
    def _arrived():
        def dead(g):
            # what an earlier group (or no one) left in a slot nothing
            # was fetched for: its columns are masked, but a weight of
            # exactly 0 times a stale NaN is no 0.  The scores need no
            # such care: the mask SELECTS them away
            vbuf[half, :, g * page_size:(g + 1) * page_size, :] = jnp.zeros(
                (heads, page_size, vbuf.shape[3]), vbuf.dtype)

        slots(w, copies(half, lambda copy: copy.wait()), dead)

    @pl.when(w < count)
    def _compute():
        cell = cell_ref[w]
        s = cell >> group_bits
        group = (cell >> tile_bits) & group_mask
        row0 = (cell & ((1 << tile_bits) - 1)) * q_block
        if q_block % 8 == 0:
            row0 = pl.multiple_of(row0, 8)
        start = st_ref[s]
        ln = ln_ref[s]
        kv_len = kv_ref[s]
        rows_sl = pl.ds(row0, q_block)
        q = q_ref[:, rows_sl, :]                   # [heads, q_block, D]
        k = kbuf[half]                             # [heads, n_keys, D]
        v = vbuf[half]
        if quantized:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_keys),
                                        2) // page_size

        def key_scales(s_ref):
            """[heads, 1, n_keys]: each key's page's ``scale * 1/127``,
            by a one-hot select a slot and a sum of exact zeros."""
            scales = s_ref[0, 0, 0] * INV_QMAX     # [heads, 1, per]
            return sum(jnp.where(slot == g, scales[:, :, g:g + 1], 0.0)
                       for g in range(per))

        if quantized:
            sc = sc * key_scales(ks_ref)
        row = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, n_keys), 0)
        col = group * n_keys + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, n_keys), 1)
        mine = (row >= start) & (row < start + ln)
        # held under the page tables' width, past which a slot repeats
        # a page the row has already met
        qpos = jnp.minimum(kv_len - ln + (row - start),
                           n_pages * page_size - 1)
        visible = (mine & (col <= qpos))[None]     # [1, q_block, n_keys]
        sc = jnp.where(visible, sc, NEG_INF)
        m_prev = jnp.max(m_ref[:, rows_sl, :], axis=2, keepdims=True)
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(visible, jnp.exp(sc - m_cur), 0.0)  # masked: exactly 0
        l_prev = jnp.max(l_ref[:, rows_sl, :], axis=2, keepdims=True)
        l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            p = p * key_scales(vs_ref)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[:, rows_sl, :] = acc_ref[:, rows_sl, :] * alpha + pv
        state = (heads, q_block, m_ref.shape[2])
        m_ref[:, rows_sl, :] = jnp.broadcast_to(m_cur, state)
        l_ref[:, rows_sl, :] = jnp.broadcast_to(l_cur, state)

    @pl.when(w == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.max(l_ref[...], axis=2, keepdims=True)
        safe_l = jnp.where(l > 0.0, l, 1.0)  # unclaimed rows: zeros
        # more live cells than the list holds (descriptors that overlap:
        # ragged_grid_cells) must not pass for attention
        fits = jnp.where(count > capacity, jnp.nan, 1.0)
        o_ref[...] = (acc_ref[...] / safe_l * fits).astype(o_ref.dtype)


def _group_scales(scale, page_tables, per, heads):
    """[P, H] per-page per-head scales -> ``[S, groups, H / heads,
    heads, 1, per]``: the scales of each descriptor's logical pages a
    group, a head block at a time (the kernel's `key_scales` reads one
    ``[heads, 1, per]`` block a cell).  O(descriptors x pages x heads)
    numbers, gathered through the page tables."""
    n_seqs, n_pages = page_tables.shape
    n_groups = -(-n_pages // per)
    by_page = jnp.asarray(scale, jnp.float32)[page_tables]   # [S, P', H]
    by_page = jnp.pad(by_page,
                      ((0, 0), (0, n_groups * per - n_pages), (0, 0)))
    h = by_page.shape[2]
    return jnp.transpose(
        by_page.reshape(n_seqs, n_groups, per, h // heads, heads),
        (0, 1, 3, 4, 2)).reshape(n_seqs, n_groups, h // heads, heads, 1, per)


def ragged_paged_attention_kernel(q, k_pool, v_pool, page_tables, starts,
                                  lens, kv_lens, scale, interpret=None,
                                  layout="token", mesh=None, tp_axis=None,
                                  k_scale=None, v_scale=None, work=None):
    """q: [T, H, D] — the step's PACKED query rows (decode rows, the
    prefill chunks, and speculative verify runs — a decode row with
    len = 1 + k drafts is just a chunk-shaped descriptor to this
    kernel — in one ragged token axis; rows owned by no descriptor
    come back 0).  k_pool/v_pool: one layer's pool, the
    chunks' and the decode tokens' K/V already scattered —
    [P, page_size, H, D] (layout="token") or [H, P, page_size, D]
    (layout="kernel").  page_tables: [S, max_pages] int32 (pad with 0).
    starts/lens/kv_lens: [S] int32 descriptors (lens == 0 marks padding
    descriptors), owning DISJOINT row ranges (`ragged_grid_cells`).
    work: `ragged_work_list` of these descriptors, for a caller that
    builds it once for all its layers; built here when None.
    Returns [T, H, D].

    ONE call a layer whatever the step carries.  The grid is (head
    blocks, live cells): the work list names each step's (descriptor,
    page group, query tile) and rides with the flat page tables and the
    descriptors as scalar-prefetch operands; the kernel's own DMAs
    bring each group's pages of a head block out of the pools, which
    stay in HBM (see _ragged_kernel; `ragged_cell_shape` states the
    cell, ragged_score_groups mirrors the list's count host-side).

    LIMIT: SMEM (1 MiB on v5e) holds the page tables (S x max_pages
    words) and the list (one word a cell, ``(n_tiles + S - 1) x
    ceil(max_pages / G)`` of them).  The benchmark's 17 descriptors x
    80 rows take 10 KiB at 2k context (128 pages) and 325 KiB at 64k
    (4,096 pages); 128k (8,192 pages: 650 KiB) fits where the one-page
    list did not.  VMEM holds the blocks `ragged_cell_shape` counts.

    mesh / tp_axis runs the shard_map'd form: the same kernel per shard
    on num_heads/tp heads over that shard's pool slice (_head_shard_map),
    the list replicated like the descriptors.

    Kernel-layout pools (what the engine stores for this kernel: its
    auto pool_layout) are consumed as stored; token-layout ones cost a copy."""
    _require_scales(k_pool, k_scale, v_scale)
    quantized = k_scale is not None
    t, h, d = q.shape
    page_size = k_pool.shape[2 if layout == "kernel" else 1]
    starts, lens, kv_lens = (jnp.asarray(x, jnp.int32)
                             for x in (starts, lens, kv_lens))
    page_tables = jnp.asarray(page_tables, jnp.int32)
    n_seqs, n_pages = page_tables.shape
    if work is None:
        work = ragged_work_list(starts, lens, kv_lens, page_size, n_pages, t)
    if mesh is not None:
        def body(q_, kp_, vp_, *rest):
            # rest: (k_scale, v_scale) when quantized, then the scalars
            *scales_, pt_, st_, ln_, kv_, cells_, count_ = rest
            return ragged_paged_attention_kernel(
                q_, kp_, vp_, pt_, st_, ln_, kv_, scale,
                interpret=interpret, layout=layout,
                work=(cells_, count_),
                **dict(zip(("k_scale", "v_scale"), scales_)))

        return _head_shard_map(
            body, mesh, tp_axis, layout, q, k_pool, v_pool,
            page_tables, starts, lens, kv_lens,
            *work, scales=((k_scale, v_scale) if quantized else None))
    _reject_mesh_sharded_pool(k_pool)
    per, heads, qb = ragged_cell_shape(page_size, n_pages, t, h, d,
                                       k_pool.dtype.itemsize)
    n_tiles = ragged_query_tiles(t, qb)[1]
    tpad = n_tiles * qb
    qs = jnp.transpose((q * scale).astype(q.dtype), (1, 0, 2))  # [H, T, D]
    if tpad != t:
        # pad the token axis to whole tiles so the kernel's per-tile
        # row slices stay in bounds; padded rows belong to no
        # descriptor (exact zeros) and are sliced off below
        qs = jnp.pad(qs, ((0, 0), (0, tpad - t), (0, 0)))
    if layout == "kernel":
        kt, vt = k_pool, v_pool          # stored kernel-ready: no copy
    else:
        kt = jnp.transpose(k_pool, (2, 0, 1, 3))
        vt = jnp.transpose(v_pool, (2, 0, 1, 3))
    if d % 128 and not resolve_interpret(interpret):
        # Mosaic cuts a DMA out of an HBM pool in whole 128-lane rows:
        # a narrower head rides zero lanes (in the copy a token-layout
        # pool costs anyway), which add exact zeros to every score
        widen = [(0, 0)] * 3 + [(0, -d % 128)]
        qs = jnp.pad(qs, widen[1:])
        kt, vt = jnp.pad(kt, widen), jnp.pad(vt, widen)
    out = _ragged_call(page_tables, *work, starts, lens, kv_lens, qs, kt, vt,
                       (k_scale, v_scale) if quantized else (),
                       cell=(per, heads, qb),
                       interpret=resolve_interpret(interpret))
    return jnp.transpose(out[:, :t, :d], (1, 0, 2))


@functools.partial(jax.jit, static_argnames=("cell", "interpret"))
def _ragged_call(page_tables, cells, count, starts, lens, kv_lens, qs, kt, vt,
                 scales, *, cell, interpret):
    """The `pallas_call` of `ragged_paged_attention_kernel`: qs [H, Tpad,
    W] scaled, padded to whole tiles and lanes, kt / vt [H, P,
    page_size, W], `cell` = `ragged_cell_shape`.  Jitted, so that the
    layers of one step, which call it on the same shapes, trace and
    lower the kernel ONCE (a step program's tracing and lowering went
    from 1.0 to 2.6 s with the grouped cell's unrolled copies a layer,
    +11 s of set-up over a cell's pages buckets: PERF.md, PR 35); the
    cell rides as a static argument because the trace depends on it."""
    per, heads, qb = cell
    h, tpad, width = qs.shape
    page_size = kt.shape[2]
    n_seqs, n_pages = page_tables.shape
    capacity = cells.shape[0]
    quantized = bool(scales)
    n_groups = -(-n_pages // per)
    tile_bits, group_bits = _cell_bits(n_seqs, n_groups, tpad // qb)

    # scalar-prefetch operands (SMEM): the page tables, the work list
    # and the descriptors
    prefetch = [page_tables.reshape(-1), cells, count, starts, lens,
                kv_lens]
    group_mask = (1 << (group_bits - tile_bits)) - 1

    def scales_of(hb, w, pt_ref, cell_ref, *_):
        word = cell_ref[w]
        return (word >> group_bits, (word >> tile_bits) & group_mask, hb,
                0, 0, 0)

    whole = pl.BlockSpec((heads, tpad, width),
                         lambda hb, w, *refs: (hb, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    scales = [_group_scales(x, page_tables, per, heads) for x in scales]
    lanes = max(width, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        # one step per live cell (a traced bound); q/out ride whole-axis
        # blocks fetched once per head block
        grid=(h // heads, _held_to(capacity, count[0])),
        in_specs=[whole, in_hbm, in_hbm] + [
            pl.BlockSpec((1, 1, 1, heads, 1, per), scales_of)] * len(scales),
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, heads, per * page_size, width), kt.dtype),
            pltpu.VMEM((2, heads, per * page_size, width), vt.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, tpad, width), jnp.float32),
            pltpu.VMEM((heads, tpad, lanes), jnp.float32),
            pltpu.VMEM((heads, tpad, lanes), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size, per=per,
                          heads=heads, n_pages=n_pages, q_block=qb,
                          tile_bits=tile_bits, group_bits=group_bits,
                          capacity=capacity, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, tpad, width), qs.dtype),
        name="ragged_paged_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=RAGGED_VMEM_LIMIT),
        interpret=interpret,
    )(*prefetch, qs, _in_hbm(kt, interpret), _in_hbm(vt, interpret), *scales)


def chunk_prefill_attention_kernel(q, k_pool, v_pool, page_table, start,
                                   scale, interpret=None, layout="token",
                                   mesh=None, tp_axis=None, k_scale=None,
                                   v_scale=None):
    """q: [n, H, D] — one sequence's prefill-chunk queries (row r at
    global position start + r; rows past the real chunk length are
    bucket padding whose output the caller discards).  k_pool/v_pool:
    one layer's pool, already holding the chunk's scattered K/V —
    [P, page_size, H, D] (layout="token") or [H, P, page_size, D]
    (layout="kernel").  page_table: [max_pages] int32 (pad with 0).
    start: int32 scalar (traced OK — rides as a scalar-prefetch
    operand).  Returns [n, H, D].

    mesh / tp_axis runs the shard_map'd form (heads independent, page
    table and start replicated — _head_shard_map).

    Kernel-layout pools (the engine's auto pool_layout on this path) are
    consumed as stored; a token-layout pool is transposed whole per call."""
    _require_scales(k_pool, k_scale, v_scale)
    quantized = k_scale is not None
    if mesh is not None:
        if quantized:
            def body(q_, kp_, vp_, ks_, vs_, pt_, st_):
                return chunk_prefill_attention_kernel(
                    q_, kp_, vp_, pt_, st_, scale, interpret=interpret,
                    layout=layout, k_scale=ks_, v_scale=vs_)
        else:
            def body(q_, kp_, vp_, pt_, st_):
                return chunk_prefill_attention_kernel(
                    q_, kp_, vp_, pt_, st_, scale, interpret=interpret,
                    layout=layout)

        return _head_shard_map(
            body, mesh, tp_axis, layout, q, k_pool, v_pool,
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(start, jnp.int32),
            scales=((k_scale, v_scale) if quantized else None))
    _reject_mesh_sharded_pool(k_pool)
    n, h, d = q.shape
    qs = jnp.transpose((q * scale).astype(q.dtype), (1, 0, 2))  # [H, n, D]
    if layout == "kernel":
        page_size = k_pool.shape[2]
        kt, vt = k_pool, v_pool
    else:
        page_size = k_pool.shape[1]
        kt = jnp.transpose(k_pool, (2, 0, 1, 3))
        vt = jnp.transpose(v_pool, (2, 0, 1, 3))
    n_pages = page_table.shape[0]
    info = jnp.asarray(start, jnp.int32).reshape(1)

    prefetch = [jnp.asarray(page_table, jnp.int32), info]

    def page_of(h_, i, pt_ref, *_):
        return h_, pt_ref[i]

    scales = ([_scale_rows(k_scale), _scale_rows(v_scale)]
              if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(h, n_pages),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda h_, i, *refs: (h_, 0, 0)),
            *_pool_specs(page_of, page_size, d, len(scales)),
        ],
        out_specs=pl.BlockSpec((1, n, d), lambda h_, i, *refs:
                               (h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, d), jnp.float32),
            pltpu.VMEM((n, 128), jnp.float32),
            pltpu.VMEM((n, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, page_size=page_size,
                          n_pages=n_pages, n_rows=n,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, n, d), q.dtype),
        name="chunk_prefill_attention",
        interpret=resolve_interpret(interpret),
    )(*prefetch, qs, kt, vt, *scales)
    return jnp.transpose(out, (1, 0, 2))


def paged_decode_attention_kernel(q, k_pool, v_pool, page_tables, seq_lens,
                                  scale, interpret=None, layout="token",
                                  mesh=None, tp_axis=None, k_scale=None,
                                  v_scale=None):
    """q: [B, H, D].  k_pool/v_pool: one layer's pool —
    [P, page_size, H, D] (layout="token") or [H, P, page_size, D]
    (layout="kernel", DeviceKVPool's kernel-layout storage).
    page_tables: [B, max_pages] int32 (pad with 0).  seq_lens: [B] int32.
    Returns [B, H, D] attention output.

    mesh / tp_axis runs the shard_map'd form (heads independent, page
    tables and seq_lens replicated — _head_shard_map).

    The kernel itself always consumes [H, P, page_size, D]: the layout
    the engine stores a pool in when this path reads it (pool_layout=None).
    A token-layout pool is transposed here per call — O(pool) HBM traffic
    per layer per step (half the busy time at 335 MB a pool: PERF.md)."""
    _require_scales(k_pool, k_scale, v_scale)
    quantized = k_scale is not None
    if mesh is not None:
        if quantized:
            def body(q_, kp_, vp_, ks_, vs_, pt_, sl_):
                return paged_decode_attention_kernel(
                    q_, kp_, vp_, pt_, sl_, scale, interpret=interpret,
                    layout=layout, k_scale=ks_, v_scale=vs_)
        else:
            def body(q_, kp_, vp_, pt_, sl_):
                return paged_decode_attention_kernel(
                    q_, kp_, vp_, pt_, sl_, scale, interpret=interpret,
                    layout=layout)

        return _head_shard_map(
            body, mesh, tp_axis, layout, q, k_pool, v_pool,
            jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(seq_lens, jnp.int32),
            scales=((k_scale, v_scale) if quantized else None))
    _reject_mesh_sharded_pool(k_pool)
    b, h, d = q.shape
    qs = (q * scale).astype(q.dtype).reshape(b, h, 1, d)
    if layout == "kernel":
        page_size = k_pool.shape[2]
        kt, vt = k_pool, v_pool          # stored kernel-ready: no copy
    else:
        page_size = k_pool.shape[1]
        # [P, ps, H, D] -> [H, P, ps, D]: trailing block dims full dims
        kt = jnp.transpose(k_pool, (2, 0, 1, 3))
        vt = jnp.transpose(v_pool, (2, 0, 1, 3))
    n_pages = page_tables.shape[1]

    prefetch = [jnp.asarray(page_tables, jnp.int32),
                jnp.asarray(seq_lens, jnp.int32)]

    def page_of(b_, h_, i, pt_ref, *_):
        return h_, pt_ref[b_, i]

    scales = ([_scale_rows(k_scale), _scale_rows(v_scale)]
              if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, h, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b_, h_, i, *refs:
                         (b_, h_, 0, 0)),
            *_pool_specs(page_of, page_size, d, len(scales)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda b_, h_, i, *refs:
                               (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_STATE_ROWS, d), jnp.float32),
            pltpu.VMEM((_STATE_ROWS, 128), jnp.float32),
            pltpu.VMEM((_STATE_ROWS, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size,
                          n_pages=n_pages, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        name="paged_decode_attention",
        interpret=resolve_interpret(interpret),
    )(*prefetch, qs, kt, vt, *scales)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------
# LATENT (MLA, absorbed form) ragged attention.
#
# A latent-attention model caches one row a token a layer — the
# normalised compressed kv `c` followed by the rotated shared key
# `k_rope` — and every head of a query row attends the SAME row: as the
# key whole, as the value in its leading `v_width` lanes.  There is no
# head axis to put on the grid, so a cell here holds a tile's rows x
# ALL heads, which is what lets a page be read once.  That changes what
# stays resident: state for every row of the packed axis x every head
# does not fit VMEM, so the list is walked TILE-major (a tile's pages
# innermost) and the online-softmax state is one tile's.
#
# A CELL is a GROUP of `latent_pages_per_cell` consecutive logical
# pages of one descriptor for one query tile, fetched and multiplied
# together: one score product over ~LATENT_CELL_TOKENS keys, one value
# product and one softmax update a grid step.  A grid step costs ~0.35
# us whatever it computes and a one-page product fills half an MXU
# (PERF.md, PRs 25, 28, 33), so a 16.5 k-token row walks 17 cells, not
# 260.  The live (descriptor, page, tile) cells are `ragged_work_list`'s
# set, grouped; this list has its own capacity and grid rule below, and
# the per-head kernel's functions above keep theirs.
# ---------------------------------------------------------------------
# Keys of one context a grid step multiplies.  Settled by a sweep of the
# kernel alone at glm-4.7-flash-d7's shapes and by the cell (PERF.md
# section 6, PR 33: 512 keys a cell were 8 % fewer tokens/s, 2,048 no
# more); the double-buffered page block it implies, 2 x 1,024 rows of
# 1,280 B, is 2.5 MiB of VMEM.
LATENT_CELL_TOKENS = 1024


def latent_pages_per_cell(page_size, n_pages):
    """G, the pages of one cell of the latent list: what the pool's
    page size makes of `LATENT_CELL_TOKENS`, at least one page and never
    more than the page tables hold (the pages bucket).  The ONE
    statement of the grouping rule, as `ragged_query_tiles` is of the
    tiling: the list, the kernel, the engine's counters and gauge and
    the tests read it here."""
    return max(1, min(LATENT_CELL_TOKENS // int(page_size), int(n_pages)))


def latent_grid_cells(n_seqs, n_pages, n_rows, page_size, live=None):
    """Grid steps of the latent kernel: the CAPACITY of its list, or,
    given the `live` (descriptor, page group, tile) cells of a step,
    the steps the kernel walks for them, held to [1, capacity] like
    `ragged_grid_cells`.  The (descriptor, tile) pairs that intersect
    number at most ``n_tiles + n_seqs - 1`` (disjoint ascending row
    ranges: the argument is `ragged_grid_cells`'), and a pair meets at
    most ``ceil(n_pages / G)`` groups."""
    per = latent_pages_per_cell(page_size, n_pages)
    capacity = (ragged_query_tiles(n_rows)[1] + n_seqs - 1) * -(-n_pages
                                                                // per)
    return _held_to(capacity, live)


def latent_score_groups(starts, lens, kv_lens, page_size, n_pages, n_rows):
    """Host-side mirror of `latent_work_list`'s count, by its rule in
    numpy: the live (descriptor, page group, tile) cells of these
    descriptors, which is what the engine's
    `generation.step_grid_cells` is set from on a latent pool (times G:
    the page SLOTS walked, full or padded)."""
    seen = _pages_seen(starts, lens, kv_lens, page_size, n_pages, n_rows,
                       None)
    return int((-(-seen // latent_pages_per_cell(page_size, n_pages))).sum())


def latent_work_list(page_tables, starts, lens, kv_lens, page_size, n_rows):
    """The latent kernel's grid, in the trace: every live (descriptor,
    page group, query tile) cell — descriptors as given, a descriptor's
    query tiles ascending, the groups a tile sees innermost.
    Descriptors own disjoint ASCENDING row ranges (`RaggedStep.pad`'s
    packing), so the tile of the cells is monotone along the list and
    each tile's cells are one run: the kernel opens its state at a
    run's first cell and writes the tile's output at its last.

    Returns ``(pages [W * G], cells [W], count [1])`` int32, W the
    capacity `latent_grid_cells` states and G `latent_pages_per_cell`:
    cell w's packed ``descriptor | group | tile`` word (`_cell_bits`
    over the groups) and, at ``pages[w * G + g]``, the physical page of
    its g-th slot, logical page ``group * G + g``.  A tile's LAST group
    is filled by repeating its last visible page: the fetch stays valid
    and the kernel's ``col <= qpos`` mask drops those columns, since a
    column is reckoned from its slot's logical page, which starts past
    the tile's horizon.  Entries past `count` repeat the last live
    cell.  Built once a step and shared by the layers."""
    pt = jnp.asarray(page_tables, jnp.int32)
    n_seqs, n_pages = pt.shape
    per = latent_pages_per_cell(page_size, n_pages)
    n_groups = -(-n_pages // per)
    qb, n_tiles = ragged_query_tiles(n_rows)
    tile_bits, group_bits = _cell_bits(n_seqs, n_groups, n_tiles)
    st, ln, kv = (jnp.asarray(x, jnp.int32)[:, None]
                  for x in (starts, lens, kv_lens))            # [S, 1]
    end = st + ln
    qt = jnp.arange(n_tiles, dtype=jnp.int32)[None, :]         # [1, Q]
    meets = (ln > 0) & (qt >= st // qb) & (qt <= (end - 1) // qb)
    # the tile's last in-span row sits at this position; it sees the
    # pages that start at or under it
    horizon = kv - ln + (jnp.minimum((qt + 1) * qb, end) - 1 - st)
    seen = jnp.where(meets, jnp.clip(horizon // page_size + 1, 0, n_pages),
                     0).reshape(-1)                            # [S * Q]
    steps = -(-seen // per)
    upto = jnp.cumsum(steps)
    count = upto[-1]
    capacity = latent_grid_cells(n_seqs, n_pages, n_rows, page_size)
    w = jnp.minimum(jnp.arange(capacity, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the (descriptor, tile) pair of cell w is the last one that starts
    # at or under w: a mark at every pair's start and a running sum, not
    # a search (a `searchsorted` over 42 k entries was a 3-4 ms loop a
    # step on the chip: PERF.md, PR 28); marks at or past the count
    # (trailing empty pairs) fall off the end
    marks = jnp.zeros((capacity,), jnp.int32).at[upto - steps].add(
        1, mode="drop")
    pair = jnp.clip(jnp.cumsum(marks)[w] - 1, 0, n_seqs * n_tiles - 1)
    group = jnp.clip(w - (upto[pair] - steps[pair]), 0, n_groups - 1)
    desc = pair // n_tiles
    cells = desc << group_bits | group << tile_bits | pair % n_tiles
    slot = group[:, None] * per + jnp.arange(per, dtype=jnp.int32)[None, :]
    slot = jnp.minimum(slot, jnp.maximum(seen[pair] - 1, 0)[:, None])
    return pt[desc[:, None], slot].reshape(-1), cells, count.reshape(1)


def _latent_ragged_kernel(pg_ref, cell_ref, cnt_ref, st_ref, ln_ref, kv_ref,
                          q_ref, pool_ref, o_ref, buf_ref, sem, acc_ref,
                          m_ref, l_ref, *, page_size, per, n_pages, q_block,
                          tile_bits, group_bits, capacity, v_width):
    """One (descriptor, page group, query tile) cell of
    `latent_work_list`: the tile's ``q_block`` rows x every head
    (head-major rows of the q block: row ``h * q_block + r``) against
    the group's ``per`` latent pages as ONE ``[per * page_size, W]``
    block, which is the key whole and the value in its first `v_width`
    lanes.  The pool stays in HBM (`pool_ref`): a group's pages are not
    neighbours there, so each is copied into its slot of one half of
    `buf_ref` while the cell before multiplies the other half (Pallas's
    own pipeline over G index maps of the pool read 1.27 ms a call at
    the benchmark's shapes whether G was 8 or 16, this 1.31 and 1.13:
    PERF.md, PR 33).  The masks and the online-softmax update are
    `_ragged_kernel`'s, a group at a time; the state is one tile's,
    opened where the list's tile changes and written out where it
    changes next."""
    w = pl.program_id(1)
    count = cnt_ref[0]
    tile_mask = (1 << tile_bits) - 1
    cell = cell_ref[w]
    tile = cell & tile_mask
    live = w < count
    opens = (w == 0) | ((cell_ref[jnp.maximum(w - 1, 0)] & tile_mask)
                        != tile)
    closes = (w >= count - 1) | (
        (cell_ref[jnp.minimum(w + 1, capacity - 1)] & tile_mask) != tile)
    rows = acc_ref.shape[0]
    n_keys = per * page_size

    def copies(step, act):
        half = step % 2
        for g in range(per):
            act(pltpu.make_async_copy(
                pool_ref.at[pg_ref[step * per + g]],
                buf_ref.at[half, pl.ds(g * page_size, page_size)],
                sem.at[half]))

    @pl.when(w == 0)
    def _first():
        copies(w, lambda copy: copy.start())

    @pl.when(w + 1 < pl.num_programs(1))
    def _next():
        copies(w + 1, lambda copy: copy.start())

    copies(w, lambda copy: copy.wait())

    @pl.when(opens)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        s = cell >> group_bits
        group = (cell >> tile_bits) & ((1 << (group_bits - tile_bits)) - 1)
        start = st_ref[s]
        ln = ln_ref[s]
        kv_len = kv_ref[s]
        q = q_ref[0]                               # [H * q_block, W]
        c = buf_ref[w % 2]                         # [n_keys, W]
        sc = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        row = tile * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) % q_block
        mine = (row >= start) & (row < start + ln)
        # a row's last visible position, -1 for rows of other
        # descriptors; held under the page tables' width, past which a
        # slot repeats a page the row has already met
        qpos = jnp.where(mine, jnp.minimum(kv_len - ln + (row - start),
                                           n_pages * page_size - 1), -1)
        col = group * n_keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, n_keys), 1)
        visible = col <= qpos                      # [rows, n_keys]
        sc = jnp.where(visible, sc, NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(visible, jnp.exp(sc - m_cur), 0.0)  # masked: exactly 0
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(c.dtype), c[:, :v_width],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(closes)
    def _finalize():
        l = jnp.max(l_ref[...], axis=1, keepdims=True)
        safe_l = jnp.where(l > 0.0, l, 1.0)  # unclaimed rows: zeros
        fits = jnp.where(count > capacity, jnp.nan, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l * fits).astype(o_ref.dtype)


def latent_ragged_attention_kernel(q, pool, page_tables, starts, lens,
                                   kv_lens, scale, v_width, interpret=None,
                                   work=None):
    """Absorbed-form latent attention over a paged latent pool.

    q: [T, H, W] — the packed rows' absorbed queries, ``[q_nope W_k^T |
    q_rope]`` a head, W the pool's row width.  pool: one layer's latent
    pool [P, page_size, W], this step's rows already scattered: a row
    is ``[c | k_rope]``, the key of every head whole and their value in
    its first `v_width` lanes.  page_tables / starts / lens / kv_lens:
    the ragged kernel's descriptors, owning disjoint ASCENDING row
    ranges.  work: `latent_work_list` of them (built here when None).
    Returns [T, H, v_width] in q's dtype: ``sum_s p c(s)`` a head, for
    the caller to carry through the value projection.  Rows of a tile
    that no descriptor claims come back 0; a tile no cell touches is
    never written, so the result is masked to the descriptors' rows.

    ONE call a layer whatever the step carries, its grid ``(1, live
    cells)`` under a traced bound (`latent_grid_cells`), the list and
    the descriptors its scalar-prefetch operands.  The list lives whole
    in SMEM: ``W * (G + 1)`` words, 55 KiB at the benchmark's 17
    descriptors x 80 rows x 512 pages (the limit is the per-head
    kernel's, stated in `ragged_paged_attention_kernel`)."""
    t, h, width = q.shape
    page_size = pool.shape[1]
    starts, lens, kv_lens = (jnp.asarray(x, jnp.int32)
                             for x in (starts, lens, kv_lens))
    if work is None:
        work = latent_work_list(page_tables, starts, lens, kv_lens,
                                page_size, t)
    _reject_mesh_sharded_pool(pool)
    qb, n_tiles = ragged_query_tiles(t)
    tpad = n_tiles * qb
    qs = (q * scale).astype(q.dtype)
    if tpad != t:
        qs = jnp.pad(qs, ((0, tpad - t), (0, 0), (0, 0)))
    # [tiles, H * q_block, W], head-major inside a tile: one 2-D block a
    # cell, no in-kernel reshape across the (unaligned) head count
    qs = jnp.transpose(qs.reshape(n_tiles, qb, h, width),
                       (0, 2, 1, 3)).reshape(n_tiles, h * qb, width)
    n_seqs, n_pages = page_tables.shape
    per = latent_pages_per_cell(page_size, n_pages)
    tile_bits, group_bits = _cell_bits(n_seqs, -(-n_pages // per), n_tiles)
    capacity = latent_grid_cells(n_seqs, n_pages, t, page_size)
    pages, cells, count = work
    prefetch = [pages, cells, count, starts, lens, kv_lens]
    tile_mask = (1 << tile_bits) - 1

    def tile_of(_, w, pg_ref, cell_ref, *rest):
        return cell_ref[w] & tile_mask, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1, latent_grid_cells(n_seqs, n_pages, t, page_size,
                                   live=count[0])),
        in_specs=[pl.BlockSpec((1, h * qb, width), tile_of),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h * qb, v_width), tile_of),
        scratch_shapes=[
            pltpu.VMEM((2, per * page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h * qb, v_width), jnp.float32),
            pltpu.VMEM((h * qb, 128), jnp.float32),
            pltpu.VMEM((h * qb, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_ragged_kernel, page_size=page_size,
                          per=per, n_pages=n_pages, q_block=qb,
                          tile_bits=tile_bits, group_bits=group_bits,
                          capacity=capacity, v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, h * qb, v_width), q.dtype),
        name="latent_paged_attention",
        interpret=resolve_interpret(interpret),
    )(*prefetch, qs, pool)
    out = jnp.transpose(out.reshape(n_tiles, h, qb, v_width),
                        (0, 2, 1, 3)).reshape(tpad, h, v_width)[:t]
    row = jnp.arange(t, dtype=jnp.int32)[None, :]
    claimed = jnp.any((row >= starts[:, None])
                      & (row < (starts + lens)[:, None]), axis=0)
    return jnp.where(claimed[:, None, None], out, 0)


# ---- the kernel-layout pool's row write (kept last: the kernels above
# carry their line numbers into their lowered text) ----

# tokens whose row copies are in flight together in `kernel_pool_scatter`
_SCATTER_WINDOW = 64


def pool_scatter_in_place(pool_shape, dtype):
    """Whether `kernel_pool_scatter` serves a kernel-layout pool of this
    shape ``[H, P, page_size, D]`` and dtype: one token's row of one head
    has to be a copy Mosaic takes — 128 four-byte lanes (narrower types
    pack several rows into a word, a narrower or wider head is not one
    lane row) in pages of whole sublane tiles.  The engine stores a pool
    in kernel layout of its own accord only where this holds: XLA's own
    scatter into ``[H, P, page_size, D]`` copies the whole pool twice a
    call (it wants the scattered axes major: PERF.md, PR 29)."""
    _, _, page_size, d = pool_shape
    return (jnp.dtype(dtype).itemsize == 4 and d == 128
            and page_size % 8 == 0)


def _pool_scatter_kernel(x_ref, pool_in, at_ref, pool_out, sem, *,
                         num_pages):
    # x_ref [T, H, D] and the pool [H, P, page_size, D] both stay in HBM;
    # pool_in is pool_out (aliased): nothing but the rows moves.
    # at_ref (SMEM): the T target pages, then the T target rows
    del pool_in
    n = x_ref.shape[0]

    def each(lo, hi, act):
        def body(t, carry):
            # the padding sentinel (page == num_pages) is dropped
            @pl.when(at_ref[t] < num_pages)
            def _():
                act(pltpu.make_async_copy(
                    x_ref.at[t],
                    pool_out.at[:, at_ref[t], at_ref[n + t], :], sem))
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    def window(w, carry):
        lo = w * _SCATTER_WINDOW
        hi = jnp.minimum(lo + _SCATTER_WINDOW, n)
        each(lo, hi, lambda copy: copy.start())
        each(lo, hi, lambda copy: copy.wait())
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, _SCATTER_WINDOW), window, 0)


def kernel_pool_scatter(pool, pages, rows, x, interpret=None, mesh=None,
                        tp_axis=None):
    """Write token rows into a KERNEL-layout pool in place: ``x[i]``
    ([H, D]) lands at ``pool[:, pages[i], rows[i], :]``; an out-of-range
    page (the padding sentinel ``num_pages``) is dropped.  pool:
    [H, P, page_size, D] (`pool_scatter_in_place` says which); pages,
    rows: [T] int32, distinct targets; x: [T, H, D] in the pool's dtype.
    Returns the pool, aliased to its operand: under a donating jit the
    call moves T x H rows of 512 B and nothing else.

    One strided DMA a token, HBM to HBM (H pieces of one lane row),
    `_SCATTER_WINDOW` of them in flight.  It stands where
    ``pool.at[:, pages, rows].set`` stood, which XLA:TPU serves by
    copying the pool into a layout with the page axis major and back.
    The targets ride last, as one SMEM operand: a trace reader that
    tells the attention kernels by their leading int32 operand
    (benchmarks/trace/kernels.py) does not take this call for one.

    mesh / tp_axis: the shard_map'd form, as the attention kernels have
    it — each shard writes its heads' pieces into its slice of the pool,
    pages and rows replicated."""
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from ...parallel.collective import shard_map
        from ...parallel.sharding_annotations import kv_pool_spec

        if tp_axis is None:
            tp_axis = tuple(mesh.axis_names)[0]
        pspec = P(*kv_pool_spec("kernel", tp_axis))
        return shard_map(
            functools.partial(kernel_pool_scatter, interpret=interpret),
            mesh=mesh, in_specs=(pspec, P(), P(), P(None, tp_axis, None)),
            out_specs=pspec)(pool, pages, rows, x)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_pool_scatter_kernel, num_pages=pool.shape[1]),
        in_specs=[any_space, any_space,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=any_space,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},
        name="pool_row_write",
        interpret=resolve_interpret(interpret),
    )(x.astype(pool.dtype), pool,
      jnp.concatenate([jnp.asarray(pages, jnp.int32),
                       jnp.asarray(rows, jnp.int32)]))
