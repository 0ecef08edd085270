"""The ragged kernel's compacted grid: its work list, its bound, its packers.

The kernel (ops/pallas/paged_attention.py) walks a list of the live
(descriptor, page group, query tile) cells, built in the trace from the
step's descriptors, under a traced grid bound, a block of heads at a
time; `ragged_cell_shape` states what a cell holds.  Three things hold it
up:

(a) the in-trace list IS the plain enumeration of the live (descriptor,
    page, query tile) set, grouped, in the kernel's order, for every mix a
    step can pack and every size of group;
(b) the kernel over that list agrees with the jnp gather reference on the
    same mixes — float32, bfloat16 and int8 pools, both layouts, one and
    several head blocks, one and several groups a context, pages shared
    between descriptors (the prefix cache), 128-lane heads and the
    rehearsal's tiny ones;
(c) the list's capacity assumes descriptors that own disjoint row ranges:
    what `RaggedStep.pad` and the host-free loop hand over is checked here,
    and an overflow (ranges that overlap) comes back NaN, never a
    plausible partial attention.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import generation as gen
from paddle_tpu.generation import decode_attention
from paddle_tpu.generation.decode_attention import ragged_paged_attention
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import (
    _cell_bits, ragged_cell_shape, ragged_grid_cells, ragged_query_tiles,
    ragged_score_blocks, ragged_score_groups, ragged_work_list)

PAGE = 8            # page size of every mix below


@pytest.fixture(params=[8, 16, 128], ids=lambda n: f"cell{n}")
def cell_tokens(request, monkeypatch):
    """Keys a cell holds: one page, two, or (the module's own 128) every
    page of these mixes' tables in one group."""
    monkeypatch.setattr(pa, "RAGGED_CELL_TOKENS", request.param)
    return request.param


def _mix(n_rows, n_pages, descs):
    """descs: [(start, len, kv_len)] -> the descriptor arrays."""
    st, ln, kv = (np.array(c, np.int32) for c in zip(*descs))
    return dict(n_rows=n_rows, n_pages=n_pages, starts=st, lens=ln,
                kv_lens=kv)


# every shape of step the engine packs; n_rows a whole number of tiles
# (8 rows) or not
MIXES = {
    # 5 one-token decode rows, contexts of 1 to 4 pages
    "decode_only": _mix(12, 4, [(0, 1, 5), (1, 1, 32), (2, 1, 17),
                                (3, 1, 8), (4, 1, 9)]),
    # one 20-row chunk over a 12-token prefix: tiles 0-2, page horizon
    # growing with the tile
    "chunk_only": _mix(24, 4, [(0, 20, 32)]),
    # 3 decode rows, then a 13-row chunk that begins mid-tile
    "mixed": _mix(24, 6, [(0, 1, 40), (1, 1, 3), (2, 1, 48),
                          (3, 13, 29)]),
    # speculative verify runs of 1 + k rows: the second and third
    # straddle a tile boundary (rows 6-9 and 14-17)
    "straddling_runs": _mix(24, 4, [(2, 4, 20), (6, 4, 31), (10, 4, 9),
                                    (14, 4, 32)]),
    # live descriptors between and before len-0 padding ones
    "padding_descriptors": _mix(16, 4, [(0, 0, 0), (0, 1, 12), (0, 0, 0),
                                        (1, 6, 30), (0, 0, 0)]),
    # nothing live: the kernel's one step computes nothing
    "all_padding": _mix(16, 2, [(0, 0, 0), (0, 0, 0)]),
    # the packed axis ends inside a tile (n_rows 11 -> 2 tiles of 8)
    "ragged_axis": _mix(11, 3, [(0, 1, 24), (1, 10, 24)]),
    # THE BOUND, exactly: 3 descriptors over 2 tiles meet in
    # 2 + 3 - 1 = 4 (descriptor, tile) pairs, and every one of them sees
    # all 4 pages (the shortest horizon, rows 4-7 of the 8-row run, is
    # position 27 >= 24)
    "fills_the_bound": _mix(16, 4, [(0, 4, 32), (4, 8, 32), (12, 4, 32)]),
}


def _enumerate(n_rows, n_pages, starts, lens, kv_lens, page_size=PAGE):
    """The skip rule, spelled out: the live (descriptor, page, tile)
    set — the cells whose tile meets the descriptor's rows and whose
    page starts at or under the horizon of the tile's last in-span
    row."""
    per, _, qb = ragged_cell_shape(page_size, n_pages, n_rows)
    n_tiles = ragged_query_tiles(n_rows, qb)[1]
    cells = []
    for s, (st, ln, kv) in enumerate(zip(starts, lens, kv_lens)):
        for i in range(n_pages):
            for qt in range(n_tiles):
                row0 = qt * qb
                last = min(row0 + qb, st + ln) - 1
                if (ln > 0 and row0 < st + ln and row0 + qb > st
                        and i * page_size <= kv - ln + (last - st)):
                    cells.append((s, i, qt))
    return cells


def _grouped(live, per):
    """The kernel's cells for a live (descriptor, page, tile) set: each
    (descriptor, page // G, tile) that holds a live page, once, the
    descriptors as given, a descriptor's groups ascending, the tiles of
    a group innermost."""
    return sorted({(s, i // per, qt) for s, i, qt in live})


def _tables(rng, mix, num_pages, shared=0, page_size=PAGE):
    """[S, n_pages] page tables: distinct pages a descriptor, except the
    first `shared` pages, which every descriptor maps to the same ones
    (a prefix the cache serves them all); slots past a context are 0."""
    n_seqs = len(mix["starts"])
    perm = rng.permutation(np.arange(1, num_pages))
    pt = np.zeros((n_seqs, mix["n_pages"]), np.int32)
    used = shared
    for s, n in enumerate(-(-mix["kv_lens"] // page_size)):
        pt[s, :min(n, shared)] = perm[:min(n, shared)]
        pt[s, shared:n] = perm[used:used + max(n - shared, 0)]
        used += max(n - shared, 0)
    return pt


def _unpack(cells, n_seqs, n_groups, n_tiles):
    tile_bits, group_bits = _cell_bits(n_seqs, n_groups, n_tiles)
    return [(int(c >> group_bits),
             int(c >> tile_bits) & ((1 << (group_bits - tile_bits)) - 1),
             int(c) & ((1 << tile_bits) - 1)) for c in cells]


def _check_list(mix, page_size=PAGE):
    shape = (mix["n_pages"], mix["n_rows"])
    per, _, qb = ragged_cell_shape(page_size, *shape)
    live = _enumerate(mix["n_rows"], mix["n_pages"], mix["starts"],
                      mix["lens"], mix["kv_lens"], page_size)
    want = _grouped(live, per)
    desc = (mix["starts"], mix["lens"], mix["kv_lens"], page_size, *shape)
    # jitted anew a call: the rule reads the module's constants
    cells, count = jax.jit(lambda: ragged_work_list(*desc))()
    n_seqs = len(mix["starts"])
    capacity = ragged_grid_cells(n_seqs, *shape, page_size)
    cells, n = np.asarray(cells), int(count[0])
    assert cells.shape == (capacity,)
    assert n == len(want) <= capacity
    # the host's mirrors: the list's count (the grid counter's
    # denominator, in cells) and the live set (its numerator)
    assert n == ragged_score_groups(*desc)
    assert len(live) == ragged_score_blocks(*desc, qb)[0] <= n * per
    assert ragged_grid_cells(n_seqs, *shape, page_size, live=n) == max(n, 1)
    n_tiles = ragged_query_tiles(mix["n_rows"], qb)[1]
    assert _unpack(cells[:n], n_seqs, -(-mix["n_pages"] // per),
                   n_tiles) == want
    # padding repeats the last live entry: its pages are resident
    if n:
        assert (cells[n:] == cells[n - 1]).all()
    return n, capacity


@pytest.mark.parametrize("name", sorted(MIXES))
def test_work_list_is_the_enumeration_of_live_cells(name, cell_tokens):
    mix = MIXES[name]
    n, capacity = _check_list(mix)
    if name == "fills_the_bound":
        groups = -(-4 // max(cell_tokens // PAGE, 1))
        assert n == capacity == 4 * max(groups, 1)
    if name == "all_padding":
        assert n == 0


@pytest.mark.parametrize("seed", range(6))
def test_work_list_on_random_disjoint_descriptors(seed, monkeypatch):
    """Random back-to-back and gapped packings, contexts shorter and
    longer than the page bucket holds rows for, pages of 2 to 8 tokens
    in groups of 1 to 4, tiles of 4 or 8 rows."""
    rng = np.random.default_rng(seed)
    for _ in range(12):
        page_size = int(rng.choice([2, 4, 8]))
        monkeypatch.setattr(pa, "RAGGED_CELL_TOKENS",
                            int(rng.integers(1, 5)) * page_size)
        monkeypatch.setattr(pa, "RAGGED_CELL_ROWS", int(rng.choice([4, 8])))
        n_rows = int(rng.integers(1, 41))
        n_pages = int(rng.integers(1, 10))
        descs, pos = [], 0
        for _ in range(int(rng.integers(1, 7))):
            pos += int(rng.integers(0, 3))
            ln = int(min(rng.integers(0, 7), max(n_rows - pos, 0)))
            kv = (ln + int(rng.integers(0, n_pages * page_size - ln + 1))
                  if 0 < ln <= n_pages * page_size else 0)
            ln = ln if kv else 0
            descs.append((pos if ln else 0, ln, kv))
            pos += ln
        _check_list(_mix(n_rows, n_pages, descs), page_size)


def test_the_cell_follows_from_the_shapes(monkeypatch):
    """`ragged_cell_shape`: G from the page size and the pages bucket,
    the head block the largest divisor of the heads whose blocks fit
    the VMEM budget, the tile 8 rows or the whole short axis."""
    # opt-6.7b-d8's step: 8 pages of 16 tokens, all 32 heads, 8 rows
    assert ragged_cell_shape(16, 128, 80, 32, 128, 4) == (8, 32, 8)
    # a bucket under G holds itself; a short axis is one tile
    assert ragged_cell_shape(16, 4, 5, 32, 128, 4) == (4, 32, 5)
    assert ragged_cell_shape(64, 128, 80, 8, 128, 2) == (2, 8, 8)
    assert ragged_cell_shape(256, 128, 80, 8, 128, 2)[0] == 1
    # the list's view does not depend on the heads
    assert ragged_cell_shape(16, 128, 80)[::2] == (8, 8)
    # a 528-row axis (a 512-token chunk): 32 heads' state and blocks
    # are 66 MiB, so the block halves; 12 heads divide by 1, 2, 3, 4, 6
    assert ragged_cell_shape(16, 128, 528, 32, 128, 4) == (8, 16, 8)
    assert ragged_cell_shape(16, 128, 2048, 12, 64, 2)[1] == 4
    monkeypatch.setattr(pa, "RAGGED_CELL_HEADS", 3)
    assert ragged_cell_shape(16, 128, 80, 8, 128, 4)[1] == 2
    assert ragged_cell_shape(16, 128, 80, 9, 128, 4)[1] == 3


def _pools(rng, kv_dtype, layout, num_pages, heads, dim, page_size=PAGE):
    shape = (num_pages, page_size, heads, dim)
    scales = {}
    if kv_dtype == "int8":
        kp, vp = (rng.integers(-127, 128, shape).astype(np.int8)
                  for _ in range(2))
        scales = {"k_scale": rng.uniform(0.5, 2.0, (num_pages, heads))
                  .astype(np.float32),
                  "v_scale": rng.uniform(0.5, 2.0, (num_pages, heads))
                  .astype(np.float32)}
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(shape), kv_dtype)
                  for _ in range(2))
    if layout == "kernel":
        kp, vp = (jnp.transpose(jnp.asarray(p), (2, 0, 1, 3))
                  for p in (kp, vp))
    return kp, vp, scales


def _check_kernel(mix, kv_dtype, layout, heads, dim, page_size=PAGE):
    """Rows owned by no descriptor come back exactly 0; the others agree
    with the gather reference (online softmax reassociates; a bfloat16
    pool's weights are rounded to it before the value product, an int8
    pool's scales multiply the scores where the reference's multiply
    the keys).  The first page of every context is one shared page:
    descriptors that meet the same block through different table
    rows.  Pages no context holds are NaN: a slot nothing is fetched
    for, or a page behind no table, must not reach a sum."""
    rng = np.random.default_rng(3)
    num_pages = 64
    pt = _tables(rng, mix, num_pages, shared=1, page_size=page_size)
    kp, vp, scales = _pools(rng, kv_dtype, layout, num_pages, heads, dim,
                            page_size)
    if kv_dtype != "int8":
        free = np.setdiff1d(np.arange(num_pages), pt.reshape(-1))
        at = (slice(None), free) if layout == "kernel" else (free,)
        kp, vp = kp.at[at].set(jnp.nan), vp.at[at].set(jnp.nan)
    q = rng.standard_normal((mix["n_rows"], heads, dim)).astype(np.float32)
    args = (q, kp, vp, pt, mix["starts"], mix["lens"], mix["kv_lens"])
    ref = np.asarray(ragged_paged_attention(
        *args, use_kernel=False, layout=layout, **scales))
    ker = np.asarray(ragged_paged_attention(
        *args, use_kernel=True, interpret=True, layout=layout, **scales))
    atol = {"float32": 2e-5, "bfloat16": 2e-2, "int8": 2e-3}[kv_dtype]
    np.testing.assert_allclose(ker, ref, atol=atol, rtol=2e-5)
    owned = np.zeros(mix["n_rows"], bool)
    for st, ln in zip(mix["starts"], mix["lens"]):
        owned[st:st + ln] = True
    assert (ker[~owned] == 0.0).all()
    assert np.isfinite(ker).all()


@pytest.mark.parametrize("kv_dtype,layout", [
    ("float32", "token"), ("float32", "kernel"), ("int8", "token"),
    ("int8", "kernel")])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_kernel_over_the_list_matches_reference(name, kv_dtype, layout):
    """128-lane heads, two of them, every page of a context one group."""
    _check_kernel(MIXES[name], kv_dtype, layout, heads=2, dim=128)


@pytest.mark.parametrize("kv_dtype,layout", [
    ("float32", "kernel"), ("bfloat16", "token"), ("bfloat16", "kernel"),
    ("int8", "token")])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_kernel_over_groups_and_head_blocks_matches_reference(
        name, kv_dtype, layout, monkeypatch):
    """Cells of two pages, so a context spans several groups and its
    last one is part full, and two head blocks of two heads each."""
    monkeypatch.setattr(pa, "RAGGED_CELL_TOKENS", 2 * PAGE)
    monkeypatch.setattr(pa, "RAGGED_CELL_HEADS", 2)
    mix = MIXES[name]
    assert ragged_cell_shape(PAGE, mix["n_pages"], mix["n_rows"], 4, 128,
                             4)[:2] == (2, 2)
    _check_kernel(mix, kv_dtype, layout, heads=4, dim=128)


@pytest.mark.parametrize("kv_dtype,layout", [
    ("float32", "token"), ("float32", "kernel"), ("int8", "kernel")])
@pytest.mark.parametrize("name", ["mixed", "straddling_runs",
                                  "all_padding"])
def test_kernel_at_the_rehearsal_shapes_matches_reference(
        name, kv_dtype, layout, monkeypatch):
    """4 heads of 8, 4-token pages in cells of two: what chip_smoke.py
    --rehearse and the cells' `--rehearse` run."""
    monkeypatch.setattr(pa, "RAGGED_CELL_TOKENS", 8)
    mix = MIXES[name]
    mix = dict(mix, n_pages=2 * mix["n_pages"])    # the same tokens
    _check_kernel(mix, kv_dtype, layout, heads=4, dim=8, page_size=4)


def test_a_list_handed_in_is_the_list_built_inside():
    """model._ragged_core_fn builds the list once and hands it to every
    layer's call: same bits as the call that builds its own."""
    mix = MIXES["mixed"]
    rng = np.random.default_rng(4)
    pt = _tables(rng, mix, 64)
    kp, vp, _ = _pools(rng, "float32", "token", 64, 2, 128)
    q = rng.standard_normal((mix["n_rows"], 2, 128)).astype(np.float32)
    desc = (pt, mix["starts"], mix["lens"], mix["kv_lens"])
    work = decode_attention.ragged_work_list(*desc, PAGE, mix["n_rows"],
                                             use_kernel=True)
    own = ragged_paged_attention(q, kp, vp, *desc, use_kernel=True,
                                 interpret=True)
    given = ragged_paged_attention(q, kp, vp, *desc, use_kernel=True,
                                   interpret=True, work=work)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(given))
    # the reference has no grid, and builds none
    assert decode_attention.ragged_work_list(
        *desc, PAGE, mix["n_rows"], use_kernel=False) is None


def test_overlapping_descriptors_overflow_loudly():
    """Three descriptors that all claim rows [0, 16) meet in 3 x 2
    (descriptor, tile) pairs where disjoint ranges allow 2 + 3 - 1: the
    list cannot hold their cells, and the output says so."""
    mix = _mix(16, 2, [(0, 16, 16)] * 3)
    rng = np.random.default_rng(5)
    pt = _tables(rng, mix, 64)
    count = int(ragged_work_list(mix["starts"], mix["lens"],
                                 mix["kv_lens"], PAGE, 2, 16)[1][0])
    assert count > ragged_grid_cells(3, 2, 16, PAGE)
    kp, vp, _ = _pools(rng, "float32", "token", 64, 1, 128)
    q = rng.standard_normal((16, 1, 128)).astype(np.float32)
    out = np.asarray(ragged_paged_attention(
        q, kp, vp, pt, mix["starts"], mix["lens"], mix["kv_lens"],
        use_kernel=True, interpret=True))
    assert np.isnan(out).all()


def test_cells_that_do_not_pack_into_a_word_are_refused():
    with pytest.raises(ValueError, match="do not pack into one int32"):
        _cell_bits(n_seqs=2 ** 12, n_pages=2 ** 12, n_tiles=2 ** 10)


# -------------------- the packers' side of the bound ----------------------


def _assert_disjoint_ascending(starts, lens, n_rows):
    live = [(int(s), int(n)) for s, n in zip(starts, lens) if n > 0]
    end = 0
    for st, ln in live:        # in descriptor order: ascending, no overlap
        assert end <= st and st + ln <= n_rows, (starts, lens)
        end = st + ln
    return len(live)


@pytest.fixture(scope="module")
def model():
    return gen.TinyCausalLM(vocab_size=48, num_layers=2, num_heads=2,
                            head_dim=8, seed=3)


REPEATS = [[5, 6, 7, 5, 6, 7, 5, 6], [1, 2, 3], [9, 9, 9, 9, 9, 9],
           [4, 8, 4, 8, 4, 8, 4], [11], [3, 1, 3, 1, 3, 1, 3, 1, 3]]


@pytest.mark.parametrize("spec_tokens", [0, 3])
def test_pad_hands_over_disjoint_ascending_ranges(model, spec_tokens):
    """Every step an engine packs — decode rows, several chunks,
    speculative 1 + k runs, preemption under a small pool — reaches the
    executable as row ranges that ascend with the descriptor and never
    overlap: what `ragged_grid_cells`' capacity assumes."""
    kw = ({"spec_mode": "ngram", "spec_tokens": spec_tokens}
          if spec_tokens else {})
    eng = gen.GenerationEngine(model, gen.GenerationConfig(
        max_decode_slots=4, num_pages=24, page_size=4,
        prefill_chunk_tokens=5, kv_backend="device", step_mode="ragged",
        **kw), start=False)
    seen = []
    pad = eng._ragged.pad

    def recording_pad(*args, **kw):
        fixed = pad(*args, **kw)
        seen.append((fixed[5], fixed[6]))
        return fixed

    eng._ragged.pad = recording_pad
    handles = [eng.submit(p, max_new_tokens=10) for p in REPEATS]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=5)
    eng.shutdown()
    widest = max(_assert_disjoint_ascending(st, ln, eng._ragged.max_tokens)
                 for st, ln in seen)
    assert len(seen) > 10 and widest >= 3
    if spec_tokens:            # some run really was 1 + k rows long
        assert max(int(ln.max()) for _, ln in seen) > 1


@pytest.mark.parametrize("spec_tokens", [0, 3])
def test_loop_layout_hands_over_disjoint_ascending_ranges(
        model, spec_tokens, monkeypatch):
    """The host-free loop rebuilds its descriptors on the device every
    iteration (static starts s * (1 + K), lengths 1 + drafts): read what
    the attention call receives, iteration by iteration."""
    seen = []
    attend = decode_attention.ragged_paged_attention

    def recording_attend(q, kp, vp, pt, starts, lens, kv_lens, **kw):
        jax.debug.callback(
            lambda st, ln: seen.append((np.asarray(st), np.asarray(ln),
                                        q.shape[0])), starts, lens)
        return attend(q, kp, vp, pt, starts, lens, kv_lens, **kw)

    monkeypatch.setattr(decode_attention, "ragged_paged_attention",
                        recording_attend)
    kw = ({"spec_mode": "ngram", "spec_tokens": spec_tokens}
          if spec_tokens else {})
    eng = gen.GenerationEngine(model, gen.GenerationConfig(
        max_decode_slots=4, num_pages=128, page_size=4,
        prefill_chunk_tokens=5, kv_backend="device", step_mode="ragged",
        loop_steps=4, **kw), start=False)
    handles = [eng.submit(p, max_new_tokens=10) for p in REPEATS]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=5)
    eng.shutdown()
    jax.effects_barrier()
    looped = [(st, ln) for st, ln, t in seen if t == 4 * (1 + spec_tokens)]
    assert len(looped) > 4     # the loop's packed axis is S * (1 + K)
    for st, ln, t in seen:
        _assert_disjoint_ascending(st, ln, t)
    if spec_tokens:
        assert max(int(ln.max()) for _, ln in looped) > 1
