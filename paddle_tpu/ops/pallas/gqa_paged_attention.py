"""Grouped-query ragged paged attention over a ROW pool, full or windowed.

The third of the serving engine's attention kernels, a sibling of
`paged_attention.latent_ragged_attention_kernel`: the same packed token
axis under ``[start, len, kv_len]`` descriptors, the same compacted list
of (descriptor, page group, query tile) cells walked under a traced
bound, the same G page DMAs from a pool left in HBM into a
double-buffered block.  What differs:

- a cached token's row is ``[k_0 .. k_{n-1} | v_0 .. v_{n-1}]``
  (`generation.kv_cache.HeadRows`): n KV heads' keys, then their values,
  `head_dim` numbers each.  A cell fetches its pages ONCE and splits the
  block at head boundaries (whole 128-lane slices with heads of 128);
  for each KV head the tile's rows of ALL the query heads that share it
  (``num_heads / n`` of them, head-major rows of one q block) multiply
  that head's keys in one product, so a KV page is read once a cell for
  every query head of its group;
- a WINDOW layer sees keys ``qpos - window + 1 .. qpos``.  Its list
  starts, for each (descriptor, tile), at the page group that holds the
  lower horizon ``min over the tile's rows of (qpos - window + 1)``:
  groups wholly behind it are neither fetched nor multiplied, the kernel
  masks ``col <= qpos - window`` inside the edge group, and a slot of
  the edge group that lies under the horizon's page repeats that page
  (pages behind the window have gone back to their free list,
  `kv_cache.WindowPageGroup`: the kernel never reads one);
- the page tables ride whole in SMEM and a cell's physical pages are
  looked up by the kernel (a flat ``[S * n_pages]`` operand and one cell
  word, not G + 1 words a cell): at the 1,024-page bucket with 17
  descriptors and a 528-row packed axis the full list is 3,136 cells
  (12 KiB) beside 68 KiB of tables, where G + 1 words a cell would be
  208 KiB.

Two lists a step (`gqa_work_list` with and without `window`), each built
once for all layers of its kind.  `generation.decode_attention.
gqa_ragged_attention_reference` is the jnp form of the same attention:
the CPU path and this kernel's oracle.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, resolve_interpret
from .paged_attention import (_cell_bits, _held_to, _in_hbm,
                              _reject_mesh_sharded_pool, ragged_query_tiles)

# Query rows of a tile.  With 8 query heads a KV head a tile's product
# has 8 x 16 = 128 rows, a full MXU pass on v5e; a chunk's context is
# fetched once a tile, so 16 rows halve that traffic against the
# per-head kernel's 8.
GQA_Q_BLOCK = 16

# Keys of one context a grid step multiplies: `LATENT_CELL_TOKENS`'
# reasoning (a grid step costs ~0.35 us whatever it computes) at this
# row's 2 KiB a token: 2 x 2 MiB of VMEM double-buffered.
GQA_CELL_TOKENS = 1024


def gqa_pages_per_cell(page_size, n_pages):
    """G, the pages of one cell: `GQA_CELL_TOKENS` in pages, at least
    one and never more than the page tables hold."""
    return max(1, min(GQA_CELL_TOKENS // int(page_size), int(n_pages)))


def _groups_a_pair(n_pages, page_size, per, qb, window):
    """The most page groups one (descriptor, tile) pair can meet."""
    n_groups = -(-n_pages // per)
    if window is None:
        return n_groups
    # a tile's keys span at most window + qb - 1 tokens, which touch at
    # most this many aligned groups of per * page_size tokens
    return min(n_groups, (window + qb - 3) // (per * page_size) + 2)


def gqa_grid_cells(n_seqs, n_pages, n_rows, page_size, window=None,
                   live=None):
    """Grid steps of the kernel: the CAPACITY of its list (the
    (descriptor, tile) pairs that intersect number at most ``n_tiles +
    n_seqs - 1``, `ragged_grid_cells`' argument, times the groups a pair
    can meet), or, given the `live` cells of a step, the steps walked
    for them, held to [1, capacity]."""
    per = gqa_pages_per_cell(page_size, n_pages)
    qb, n_tiles = ragged_query_tiles(n_rows, GQA_Q_BLOCK)
    capacity = (n_tiles + n_seqs - 1) * _groups_a_pair(
        n_pages, page_size, per, qb, window)
    return _held_to(capacity, live)


def _pair_spans(xp, starts, lens, kv_lens, page_size, n_pages, n_rows,
                window):
    """For every (descriptor, tile) pair, by the one rule the list, the
    kernel's horizon and the host's counters share: ``(lo_page, hi_page,
    meets)`` [S, Q] — the pages that hold the lowest and the highest key
    any in-span row of the tile sees.  `xp` is numpy or jax.numpy."""
    qb, n_tiles = ragged_query_tiles(n_rows, GQA_Q_BLOCK)
    st, ln, kv = (xp.asarray(x)[:, None] for x in (starts, lens, kv_lens))
    end = st + ln
    qt = xp.arange(n_tiles)[None, :]
    meets = (ln > 0) & (qt >= st // qb) & (qt <= (end - 1) // qb)
    base = kv - ln - st       # a row's position is base + row
    hi_pos = base + xp.minimum((qt + 1) * qb, end) - 1
    hi_page = xp.clip(hi_pos // page_size, 0, n_pages - 1)
    if window is None:
        lo_page = xp.zeros_like(hi_page)
    else:
        lo_pos = base + xp.maximum(qt * qb, st) - (window - 1)
        lo_page = xp.clip(lo_pos // page_size, 0, n_pages - 1)
    return lo_page, hi_page, meets & (hi_pos >= 0)


def gqa_score_cells(starts, lens, kv_lens, page_size, n_pages, n_rows,
                    window=None):
    """Host-side mirror of `gqa_work_list`'s count, in numpy:
    ``(pages, cells)`` — the (tile, page) pairs between the horizons,
    which is what a step has to read, and the live (descriptor, page
    group, tile) cells the kernel walks for them.  The engine sets
    `generation.step_score_blocks` from the first and
    `generation.step_grid_cells` from G times the second."""
    per = gqa_pages_per_cell(page_size, n_pages)
    lo, hi, meets = _pair_spans(
        np, np.asarray(starts, np.int64), np.asarray(lens, np.int64),
        np.asarray(kv_lens, np.int64), int(page_size), int(n_pages),
        n_rows, window)
    pages = np.where(meets, hi - lo + 1, 0)
    cells = np.where(meets, hi // per - lo // per + 1, 0)
    return int(pages.sum()), int(cells.sum())


def gqa_work_list(starts, lens, kv_lens, page_size, n_pages, n_rows,
                  window=None):
    """The kernel's grid, in the trace: every live (descriptor, page
    group, query tile) cell — descriptors as given, a descriptor's tiles
    ascending, the groups a tile sees innermost, from the group of its
    lower horizon (0 without a window) to the group of its last visible
    page.  Descriptors own disjoint ASCENDING row ranges, so the tile of
    the cells is monotone along the list and each tile's cells are one
    run (`latent_work_list`'s argument).

    Returns ``(cells [W], count [1])`` int32, W `gqa_grid_cells`'
    capacity: cell w's packed ``descriptor | group | tile`` word.
    Entries past `count` repeat the last live cell.  Built once a step
    for all layers of one kind."""
    n_seqs = jnp.asarray(starts).shape[0]
    per = gqa_pages_per_cell(page_size, n_pages)
    n_groups = -(-n_pages // per)
    _, n_tiles = ragged_query_tiles(n_rows, GQA_Q_BLOCK)
    tile_bits, group_bits = _cell_bits(n_seqs, n_groups, n_tiles)
    lo, hi, meets = _pair_spans(
        jnp, jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.asarray(kv_lens, jnp.int32), page_size, n_pages, n_rows, window)
    first = (lo // per).reshape(-1)
    steps = jnp.where(meets, hi // per - lo // per + 1, 0).reshape(-1)
    upto = jnp.cumsum(steps)
    count = upto[-1]
    capacity = gqa_grid_cells(n_seqs, n_pages, n_rows, page_size, window)
    w = jnp.minimum(jnp.arange(capacity, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the pair of cell w is the last one that starts at or under w: a
    # mark at every pair's start and a running sum (`latent_work_list`)
    marks = jnp.zeros((capacity,), jnp.int32).at[upto - steps].add(
        1, mode="drop")
    pair = jnp.clip(jnp.cumsum(marks)[w] - 1, 0, n_seqs * n_tiles - 1)
    group = jnp.clip(first[pair] + w - (upto[pair] - steps[pair]), 0,
                     n_groups - 1)
    cells = (pair // n_tiles) << group_bits | group << tile_bits \
        | pair % n_tiles
    return cells.astype(jnp.int32), count.reshape(1).astype(jnp.int32)


def _gqa_kernel(pt_ref, cell_ref, cnt_ref, st_ref, ln_ref, kv_ref, q_ref,
                pool_ref, o_ref, buf_ref, sem, acc_ref, m_ref, l_ref, *,
                page_size, per, n_pages, q_block, tile_bits, group_bits,
                capacity, kv_heads, head_dim, window):
    """One (descriptor, page group, query tile) cell: the tile's
    ``q_block`` rows of every query head (q block ``[kv_heads, R *
    q_block, D]``: row ``r * q_block + i`` of KV head g is query head
    ``g * R + r``, tile row i) against the group's `per` pages as ONE
    ``[per * page_size, lanes]`` block, split a KV head at a time into
    its key and value slices.  Fetch, masks and the online-softmax
    update are `_latent_ragged_kernel`'s; the state is a tile's, one
    (m, l, acc) a KV head."""
    w = pl.program_id(1)
    count = cnt_ref[0]
    tile_mask = (1 << tile_bits) - 1
    group_mask = (1 << (group_bits - tile_bits)) - 1
    cell = cell_ref[w]
    tile = cell & tile_mask
    live = w < count
    opens = (w == 0) | ((cell_ref[jnp.maximum(w - 1, 0)] & tile_mask)
                        != tile)
    closes = (w >= count - 1) | (
        (cell_ref[jnp.minimum(w + 1, capacity - 1)] & tile_mask) != tile)
    n_keys = per * page_size
    rows = acc_ref.shape[1]

    def span(step):
        """(descriptor, group, the tile's lowest and highest visible
        page) of the list's cell `step`: `_pair_spans` in scalars."""
        word = cell_ref[step]
        s, qt = word >> group_bits, word & tile_mask
        start, ln = st_ref[s], ln_ref[s]
        base = kv_ref[s] - ln - start
        hi_pos = base + jnp.minimum((qt + 1) * q_block, start + ln) - 1
        hi = jnp.clip(hi_pos // page_size, 0, n_pages - 1)
        lo = 0
        if window is not None:
            lo_pos = base + jnp.maximum(qt * q_block, start) - (window - 1)
            lo = jnp.clip(lo_pos // page_size, 0, n_pages - 1)
        return s, (word >> tile_bits) & group_mask, lo, hi

    def copies(step, act):
        half = step % 2
        s, group, lo, hi = span(step)
        for g in range(per):
            # a slot outside the tile's horizons repeats the page at
            # the horizon: the fetch stays valid (a page behind the
            # window may be another sequence's by now, or poisoned) and
            # the masks drop its columns, which are reckoned from the
            # slot's own logical page
            page = pt_ref[s * n_pages + jnp.clip(group * per + g, lo, hi)]
            act(pltpu.make_async_copy(
                pool_ref.at[page],
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
        group = (cell >> tile_bits) & group_mask
        start = st_ref[s]
        ln = ln_ref[s]
        kv_len = kv_ref[s]
        row = tile * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) % q_block
        mine = (row >= start) & (row < start + ln)
        # a row's last visible position, -1 for rows of other
        # descriptors; held under the page tables' width
        qpos = jnp.where(mine, jnp.minimum(kv_len - ln + (row - start),
                                           n_pages * page_size - 1), -1)
        col = group * n_keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, n_keys), 1)
        visible = col <= qpos                      # [rows, n_keys]
        if window is not None:
            visible = visible & (col > qpos - window)
        block = buf_ref[w % 2]                     # [n_keys, lanes]
        for g in range(kv_heads):
            k = block[:, g * head_dim:(g + 1) * head_dim]
            v = block[:, (kv_heads + g) * head_dim:
                      (kv_heads + g + 1) * head_dim]
            sc = jax.lax.dot_general(q_ref[0, g], k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc = jnp.where(visible, sc, NEG_INF)
            m_prev = jnp.max(m_ref[g], axis=1, keepdims=True)
            m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(visible, jnp.exp(sc - m_cur), 0.0)  # exactly 0
            l_prev = jnp.max(l_ref[g], axis=1, keepdims=True)
            l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[g] = acc_ref[g] * alpha + pv
            m_ref[g] = jnp.broadcast_to(m_cur, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_cur, l_ref.shape[1:])

    @pl.when(closes)
    def _finalize():
        fits = jnp.where(count > capacity, jnp.nan, 1.0)
        for g in range(kv_heads):
            l = jnp.max(l_ref[g], axis=1, keepdims=True)
            safe_l = jnp.where(l > 0.0, l, 1.0)  # unclaimed rows: zeros
            o_ref[0, g] = (acc_ref[g] / safe_l * fits).astype(o_ref.dtype)


def gqa_ragged_attention_kernel(q, pool, page_tables, starts, lens, kv_lens,
                                scale, kv_heads, window=None, interpret=None,
                                work=None):
    """Grouped-query attention of the packed rows over a paged row pool.

    q: [T, H, D], query head h reading KV head ``h // (H / kv_heads)``.
    pool: one layer's row pool [P, page_size, lanes], this step's rows
    already scattered: a row is ``[k_0 .. | v_0 ..]``, `kv_heads` heads
    of D each.  page_tables [S, n_pages] / starts / lens / kv_lens: the
    ragged descriptors, owning disjoint ASCENDING row ranges.  window:
    keys a query sees counting its own (None: all of them).  work:
    `gqa_work_list` of the descriptors under the same `window` (built
    here when None).  Returns [T, H, D] in q's dtype; rows that no
    descriptor claims come back 0.

    ONE call a layer, grid ``(1, live cells)`` under a traced bound.
    SMEM holds the flat page tables, the cell words and the
    descriptors."""
    t, h, d = q.shape
    page_size = pool.shape[1]
    if h % kv_heads or pool.shape[2] < 2 * kv_heads * d:
        raise ValueError(
            f"{h} query heads over {kv_heads} KV heads of {d} do not fit "
            f"a pool row of {pool.shape[2]} lanes")
    starts, lens, kv_lens = (jnp.asarray(x, jnp.int32)
                             for x in (starts, lens, kv_lens))
    page_tables = jnp.asarray(page_tables, jnp.int32)
    n_seqs, n_pages = page_tables.shape
    if work is None:
        work = gqa_work_list(starts, lens, kv_lens, page_size, n_pages, t,
                             window)
    _reject_mesh_sharded_pool(pool)
    qb, n_tiles = ragged_query_tiles(t, GQA_Q_BLOCK)
    tpad = n_tiles * qb
    rep = h // kv_heads
    qs = (q * scale).astype(q.dtype)
    if tpad != t:
        qs = jnp.pad(qs, ((0, tpad - t), (0, 0), (0, 0)))
    # [tiles, kv_heads, rep * q_block, D]: one 2-D block a KV head
    qs = jnp.transpose(qs.reshape(n_tiles, qb, kv_heads, rep, d),
                       (0, 2, 3, 1, 4)).reshape(n_tiles, kv_heads, rep * qb,
                                                d)
    per = gqa_pages_per_cell(page_size, n_pages)
    tile_bits, group_bits = _cell_bits(n_seqs, -(-n_pages // per), n_tiles)
    capacity = gqa_grid_cells(n_seqs, n_pages, t, page_size, window)
    cells, count = work
    prefetch = [page_tables.reshape(-1), cells, count, starts, lens, kv_lens]
    tile_mask = (1 << tile_bits) - 1

    def tile_of(_, w, pt_ref, cell_ref, *rest):
        return cell_ref[w] & tile_mask, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1, gqa_grid_cells(n_seqs, n_pages, t, page_size, window,
                                live=count[0])),
        in_specs=[pl.BlockSpec((1, kv_heads, rep * qb, d), tile_of),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, kv_heads, rep * qb, d), tile_of),
        scratch_shapes=[
            pltpu.VMEM((2, per * page_size, pool.shape[2]), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((kv_heads, rep * qb, d), jnp.float32),
            pltpu.VMEM((kv_heads, rep * qb, 128), jnp.float32),
            pltpu.VMEM((kv_heads, rep * qb, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, page_size=page_size, per=per,
                          n_pages=n_pages, q_block=qb, tile_bits=tile_bits,
                          group_bits=group_bits, capacity=capacity,
                          kv_heads=kv_heads, head_dim=d, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, kv_heads, rep * qb, d),
                                       q.dtype),
        name="gqa_paged_attention",
        interpret=resolve_interpret(interpret),
    )(*prefetch, qs, _in_hbm(pool, interpret))
    out = jnp.transpose(out.reshape(n_tiles, kv_heads, rep, qb, d),
                        (0, 3, 1, 2, 4)).reshape(tpad, h, d)[:t]
    row = jnp.arange(t, dtype=jnp.int32)[None, :]
    claimed = jnp.any((row >= starts[:, None])
                      & (row < (starts + lens)[:, None]), axis=0)
    return jnp.where(claimed[:, None, None], out, 0)
