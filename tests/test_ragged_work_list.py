"""The ragged kernel's compacted grid: its work list, its bound, its packers.

The kernel (ops/pallas/paged_attention.py) walks a list of the live
(descriptor, page, query tile) cells, built in the trace from the step's
descriptors, under a traced grid bound.  Three things hold it up:

(a) the in-trace list IS the plain enumeration of the cells the skip rule
    keeps, in the kernel's order, for every mix a step can pack;
(b) the kernel over that list agrees with the jnp gather reference on the
    same mixes — float32 and int8 pools, both layouts, pages shared between
    descriptors (the prefix cache);
(c) the list's capacity assumes descriptors that own disjoint row ranges:
    what `RaggedStep.pad` and the host-free loop hand over is checked here,
    and an overflow (ranges that overlap) comes back NaN, never a
    plausible partial attention.
"""
import jax
import numpy as np
import pytest

from paddle_tpu import generation as gen
from paddle_tpu.generation import decode_attention
from paddle_tpu.generation.decode_attention import ragged_paged_attention
from paddle_tpu.ops.pallas.paged_attention import (
    _cell_bits, ragged_grid_cells, ragged_query_tiles, ragged_score_blocks,
    ragged_work_list)

PAGE = 8            # page size of every mix below


def _mix(n_rows, n_pages, descs):
    """descs: [(start, len, kv_len)] -> the descriptor arrays."""
    st, ln, kv = (np.array(c, np.int32) for c in zip(*descs))
    return dict(n_rows=n_rows, n_pages=n_pages, starts=st, lens=ln,
                kv_lens=kv)


# every shape of step the engine packs; n_rows a whole number of tiles
# (RAGGED_Q_BLOCK = 8 rows) or not
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


def _enumerate(n_rows, n_pages, starts, lens, kv_lens):
    """The skip rule, spelled out: (descriptor, page, tile) in the
    kernel's order, for the cells whose tile meets the descriptor's rows
    and whose page starts at or under the horizon of the tile's last
    in-span row."""
    qb, n_tiles = ragged_query_tiles(n_rows)
    cells = []
    for s, (st, ln, kv) in enumerate(zip(starts, lens, kv_lens)):
        for i in range(n_pages):
            for qt in range(n_tiles):
                row0 = qt * qb
                last = min(row0 + qb, st + ln) - 1
                if (ln > 0 and row0 < st + ln and row0 + qb > st
                        and i * PAGE <= kv - ln + (last - st)):
                    cells.append((s, i, qt))
    return cells


def _tables(rng, mix, num_pages, shared=0):
    """[S, n_pages] page tables: distinct pages a descriptor, except the
    first `shared` pages, which every descriptor maps to the same ones
    (a prefix the cache serves them all); slots past a context are 0."""
    n_seqs = len(mix["starts"])
    perm = rng.permutation(np.arange(1, num_pages))
    pt = np.zeros((n_seqs, mix["n_pages"]), np.int32)
    used = shared
    for s, n in enumerate(-(-mix["kv_lens"] // PAGE)):
        pt[s, :min(n, shared)] = perm[:min(n, shared)]
        pt[s, shared:n] = perm[used:used + max(n - shared, 0)]
        used += max(n - shared, 0)
    return pt


def _unpack(cells, n_seqs, n_pages, n_tiles):
    tile_bits, page_bits = _cell_bits(n_seqs, n_pages, n_tiles)
    return [(int(c >> page_bits),
             int(c >> tile_bits) & ((1 << (page_bits - tile_bits)) - 1),
             int(c) & ((1 << tile_bits) - 1)) for c in cells]


def _check_list(mix, pt):
    want = _enumerate(mix["n_rows"], mix["n_pages"], mix["starts"],
                      mix["lens"], mix["kv_lens"])
    pages, cells, count = jax.jit(
        ragged_work_list, static_argnums=(4, 5))(
            pt, mix["starts"], mix["lens"], mix["kv_lens"], PAGE,
            mix["n_rows"])
    n_seqs = len(mix["starts"])
    shape = (n_seqs, mix["n_pages"], mix["n_rows"])
    capacity = ragged_grid_cells(*shape)
    pages, cells, n = np.asarray(pages), np.asarray(cells), int(count[0])
    assert pages.shape == cells.shape == (capacity,)
    assert n == len(want) <= capacity
    # the host's mirror (the counter's numerator) is the same count
    assert n == ragged_score_blocks(
        mix["starts"], mix["lens"], mix["kv_lens"], PAGE, mix["n_pages"],
        mix["n_rows"])[0]
    assert ragged_grid_cells(*shape, live=n) == max(n, 1)
    n_tiles = ragged_query_tiles(mix["n_rows"])[1]
    assert _unpack(cells[:n], n_seqs, mix["n_pages"], n_tiles) == want
    assert [int(p) for p in pages[:n]] == [int(pt[s, i])
                                           for s, i, _ in want]
    # padding repeats the last live entry: its block is resident
    if n:
        assert (cells[n:] == cells[n - 1]).all()
        assert (pages[n:] == pages[n - 1]).all()
    return n, capacity


@pytest.mark.parametrize("name", sorted(MIXES))
def test_work_list_is_the_enumeration_of_live_cells(name):
    mix = MIXES[name]
    pt = _tables(np.random.default_rng(0), mix, 64)
    n, capacity = _check_list(mix, pt)
    if name == "fills_the_bound":
        assert n == capacity == 16
    if name == "all_padding":
        assert n == 0


@pytest.mark.parametrize("seed", range(6))
def test_work_list_on_random_disjoint_descriptors(seed):
    """Random back-to-back and gapped packings, contexts shorter and
    longer than the page bucket holds rows for."""
    rng = np.random.default_rng(seed)
    for _ in range(12):
        n_rows = int(rng.integers(1, 41))
        n_pages = int(rng.integers(1, 7))
        descs, pos = [], 0
        for _ in range(int(rng.integers(1, 7))):
            pos += int(rng.integers(0, 3))
            ln = int(min(rng.integers(0, 7), max(n_rows - pos, 0)))
            kv = (ln + int(rng.integers(0, n_pages * PAGE - ln + 1))
                  if 0 < ln <= n_pages * PAGE else 0)
            ln = ln if kv else 0
            descs.append((pos if ln else 0, ln, kv))
            pos += ln
        mix = _mix(n_rows, n_pages, descs)
        _check_list(mix, _tables(rng, mix, 64))


def _pools(rng, kv_dtype, layout, num_pages, heads, dim):
    shape = (num_pages, PAGE, heads, dim)
    scales = {}
    if kv_dtype == "int8":
        kp, vp = (rng.integers(-127, 128, shape).astype(np.int8)
                  for _ in range(2))
        scales = {"k_scale": rng.uniform(0.5, 2.0, (num_pages, heads))
                  .astype(np.float32),
                  "v_scale": rng.uniform(0.5, 2.0, (num_pages, heads))
                  .astype(np.float32)}
    else:
        kp, vp = (rng.standard_normal(shape).astype(np.float32)
                  for _ in range(2))
    if layout == "kernel":
        kp, vp = (np.ascontiguousarray(p.transpose(2, 0, 1, 3))
                  for p in (kp, vp))
    return kp, vp, scales


@pytest.mark.parametrize("kv_dtype,layout", [
    ("float32", "token"), ("float32", "kernel"), ("int8", "token"),
    ("int8", "kernel")])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_kernel_over_the_list_matches_reference(name, kv_dtype, layout):
    """Rows owned by no descriptor come back exactly 0; the others agree
    with the gather reference (online softmax reassociates).  The first
    page of every context is one shared page: descriptors that meet the
    same block through different table rows."""
    mix = MIXES[name]
    rng = np.random.default_rng(3)
    heads, dim, num_pages = 2, 128, 64
    pt = _tables(rng, mix, num_pages, shared=1)
    kp, vp, scales = _pools(rng, kv_dtype, layout, num_pages, heads, dim)
    q = rng.standard_normal((mix["n_rows"], heads, dim)).astype(np.float32)
    args = (q, kp, vp, pt, mix["starts"], mix["lens"], mix["kv_lens"])
    ref = np.asarray(ragged_paged_attention(
        *args, use_kernel=False, layout=layout, **scales))
    ker = np.asarray(ragged_paged_attention(
        *args, use_kernel=True, interpret=True, layout=layout, **scales))
    atol = 2e-5 if kv_dtype == "float32" else 2e-3
    np.testing.assert_allclose(ker, ref, atol=atol, rtol=2e-5)
    owned = np.zeros(mix["n_rows"], bool)
    for st, ln in zip(mix["starts"], mix["lens"]):
        owned[st:st + ln] = True
    assert (ker[~owned] == 0.0).all()
    assert np.isfinite(ker).all()


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
    count = int(ragged_work_list(pt, mix["starts"], mix["lens"],
                                 mix["kv_lens"], PAGE, 16)[2][0])
    assert count > ragged_grid_cells(3, 2, 16)
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

    def recording_pad(*args):
        fixed = pad(*args)
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
