"""Compile the Pallas kernels for a TPU v5e with no TPU attached.

libtpu builds a compile-only ``v5e:2x2`` topology under
``JAX_PLATFORMS=cpu``; lowering against one of its devices runs the real
XLA:TPU and Mosaic compilers.  Tier-1 otherwise runs every kernel in the
Pallas interpreter, which accepts programs Mosaic refuses (the int8 pools
overflowed SMEM at num_pages >= 1024 for five PRs while every interpreted
test passed).  The installation is fixed, so a topology that cannot be
built is a failure, not a skip.  Shapes are the ones chip_smoke.py's server
runs: 8 heads of 128, 4096 pages of 16 tokens.
"""
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from paddle_tpu.generation.decode_attention import (
    chunk_prefill_attention, paged_decode_attention, ragged_paged_attention)

# the package re-exports the function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

HEADS, HEAD_DIM, NUM_PAGES, PAGE_SIZE = 8, 128, 4096, 16
SLOTS, CHUNK, MAX_PAGES = 8, 64, 128


@pytest.fixture(scope="module")
def v5e_2x2():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(autouse=True)
def mosaic_not_interpreter(monkeypatch):
    # the default backend here is the CPU, where the kernels pick the
    # interpreter; the lowering below targets the TPU
    monkeypatch.setattr(fa, "_interpret", lambda: False)


def _compile(fn, sharding, *shaped):
    structs = [jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=sharding)
               for shape, dtype in shaped]
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool_operands(kv_dtype):
    pool = ((NUM_PAGES, PAGE_SIZE, HEADS, HEAD_DIM), kv_dtype)
    scale = ((NUM_PAGES, HEADS), np.float32)
    return [pool, pool] + ([scale, scale] if kv_dtype == "int8" else [])


def _scales(rest):
    return ({"k_scale": rest[0], "v_scale": rest[1]} if rest else {})


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_ragged_kernel_compiles_for_v5e(v5e, kv_dtype):
    t, s = CHUNK + SLOTS, SLOTS + 1

    def fn(q, pt, starts, lens, kv_lens, kp, vp, *rest):
        return ragged_paged_attention(q, kp, vp, pt, starts, lens, kv_lens,
                                      use_kernel=True, **_scales(rest))

    _compile(fn, v5e, ((t, HEADS, HEAD_DIM), "float32"),
             ((s, MAX_PAGES), "int32"), ((s,), "int32"), ((s,), "int32"),
             ((s,), "int32"), *_pool_operands(kv_dtype))


# the pages buckets opt-6.7b-d8's cells dispatch: powers of two up to
# its 2,048 positions in 16-token pages
OPT_PAGES_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128]


@pytest.mark.parametrize("pages_bucket,kv_dtype", [
    *((b, "float32") for b in OPT_PAGES_BUCKETS),
    (16, "int8"), (64, "int8"), (128, "int8")])
def test_ragged_kernel_compiles_at_the_benchmark_shapes(v5e, pages_bucket,
                                                        kv_dtype):
    """opt-6.7b-d8's serving cells (benchmarks/configs/opt-6.7b-d8.json):
    32 heads of 128, 16 decode slots + a 64-token chunk = 80 packed rows
    under 17 descriptors, a 1280-page pool, one executable a pages
    bucket.  A cell is 8 pages x 32 heads x 8 rows (`ragged_cell_shape`):
    in VMEM the double-buffered K and V groups (4 x 32 x 128 keys x 512 B
    = 8 MiB; 2 MiB in int8) beside q, the output and the online-softmax
    state over the 80 rows (7 x 32 x 80 x 512 B = 8.75 MiB), under the
    limit the call asks Mosaic for; in SMEM the flat page tables (17 x
    bucket words) and the list ((10 tiles + 16) x bucket / 8 cell words,
    built in the same trace): 2,176 + 416 words at the largest bucket.
    The traced grid bound lowers, and the pools are read where they are
    stored: nothing of a pool's size is made beside the token layout's
    transposes."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    heads, pages, t, s = 32, 1280, 80, 17
    pool = ((pages, PAGE_SIZE, heads, HEAD_DIM), kv_dtype)
    scale = ((pages, heads), np.float32)

    def fn(q, pt, starts, lens, kv_lens, kp, vp, *rest):
        return ragged_paged_attention(q, kp, vp, pt, starts, lens, kv_lens,
                                      use_kernel=True, **_scales(rest))

    compiled = _compile(
        fn, v5e, ((t, heads, HEAD_DIM), "float32"),
        ((s, pages_bucket), "int32"), ((s,), "int32"), ((s,), "int32"),
        ((s,), "int32"), pool, pool,
        *([scale, scale] if kv_dtype == "int8" else []))
    itemsize = np.dtype(kv_dtype).itemsize
    per, hb, qb = pa.ragged_cell_shape(PAGE_SIZE, pages_bucket, t, heads,
                                       HEAD_DIM, itemsize)
    assert (per, hb, qb) == (min(8, pages_bucket), 32, 8)
    vmem = (4 * hb * per * PAGE_SIZE * HEAD_DIM * itemsize
            + 7 * hb * t * HEAD_DIM * 4)
    assert vmem <= pa.RAGGED_VMEM_BUDGET < pa.RAGGED_VMEM_LIMIT
    if pages_bucket == 128:
        assert vmem == {4: (8 << 20) + 35 * (256 << 10),
                        1: (2 << 20) + 35 * (256 << 10)}[itemsize]
    cells = pa.ragged_grid_cells(s, pages_bucket, t, PAGE_SIZE)
    assert cells == 26 * -(-pages_bucket // per)
    # the scalar-prefetch operands lead the call: the traced grid bound,
    # the flat page tables, the cell words (benchmarks/trace/kernels.py
    # tells `pallas:ragged` by that s32 first operand)
    (call,) = _custom_call_operands(compiled.as_text())
    assert call[:3] == ["s32[]", f"s32[{s * pages_bucket}]", f"s32[{cells}]"]


@pytest.mark.parametrize("pages_bucket", OPT_PAGES_BUCKETS)
def test_the_opt_step_compiles_at_every_pages_bucket(v5e, pages_bucket):
    """The whole 8-layer ragged step of opt-6.7b-d8 (vocabulary cut,
    weights as shapes): it fits the chip beside its float32 weights and
    5 GiB of pools, ONE attention call a layer, which alone leads with
    an s32 operand (`kernel.ragged_roofline` divides a layer's floor by
    that call's time), two in-place row writes a layer, and nothing else
    that yields a pool."""
    others, in_place, temp_bytes, calls = _pool_sized_results(
        v5e, "kernel", pages_bucket, layers=8)
    assert (others, in_place) == (set(), 16)
    assert temp_bytes < 64 << 20
    assert len(calls) == 8 * 3
    leading_s32 = [call for call in calls if call[0].startswith("s32")]
    assert len(leading_s32) == 8
    assert all(call[1] == f"s32[{17 * pages_bucket}]"
               for call in leading_s32)


@pytest.mark.parametrize("heads,pages,rows", [
    (32, 1280, 80),      # opt-6.7b-d8's step
    (8, 4096, 72),       # chip_smoke.py's server
    (32, 1280, 2048),    # a whole prompt's rows, the legacy prefill's write
])
def test_pool_row_scatter_compiles_for_v5e(v5e, heads, pages, rows):
    """One token's row of one head is a DMA Mosaic takes (a 512 B lane
    row at any page and row), and the pool comes back aliased."""
    from paddle_tpu.ops.pallas.paged_attention import kernel_pool_scatter

    compiled = _compile(
        kernel_pool_scatter, v5e,
        ((heads, pages, PAGE_SIZE, HEAD_DIM), "float32"), ((rows,), "int32"),
        ((rows,), "int32"), ((rows, heads, HEAD_DIM), "float32"))
    assert "output_to_operand_aliasing" in compiled.as_text()


def test_pool_row_scatter_compiles_on_the_head_sharded_mesh(v5e_2x2):
    """The tp=4 server's form: each chip writes its 8 heads' pieces into
    its slice of the pool; no collective, no gathered pool."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas.paged_attention import kernel_pool_scatter

    mesh = Mesh(np.array(v5e_2x2), ("model",))

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=NamedSharding(mesh, P(*spec)))

    pool = sds((32, 1280, PAGE_SIZE, HEAD_DIM), "float32", "model")
    compiled = jax.jit(
        lambda *a: kernel_pool_scatter(*a, mesh=mesh, tp_axis="model"),
        donate_argnums=(0,), out_shardings=pool.sharding).lower(
            pool, sds((80,), "int32"), sds((80,), "int32"),
            sds((80, 32, HEAD_DIM), "float32", None, "model")).compile()
    text = compiled.as_text()
    assert "f32[8,1280,16,128]" in text and "tpu_custom_call" in text
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "collective-permute"))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_ragged_kernel_compiles_on_the_head_sharded_mesh(v5e_2x2):
    """The tp=4 server's form of opt-6.7b-d8's attention call: each chip
    runs the grouped cell over its 8 heads (Hb follows the shard) and its
    slice of the pools; the list and the page tables are replicated; no
    collective, no gathered pool."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e_2x2), ("model",))

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                    sharding=NamedSharding(mesh, P(*spec)))

    pool = sds((32, 1280, PAGE_SIZE, HEAD_DIM), "float32", "model")

    def fn(q, pt, starts, lens, kv_lens, kp, vp):
        return ragged_paged_attention(q, kp, vp, pt, starts, lens, kv_lens,
                                      use_kernel=True, layout="kernel",
                                      mesh=mesh, tp_axis="model")

    compiled = jax.jit(fn).lower(
        sds((80, 32, HEAD_DIM), "float32", None, "model"),
        sds((17, 64), "int32"), *[sds((17,), "int32")] * 3, pool,
        pool).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "f32[8,1280,16,128]" in text
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "collective-permute"))
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20
    (call,) = _custom_call_operands(text)
    assert call[:3] == ["s32[]", "s32[1088]", "s32[208]"]


def _handed_over(fn, fixed):
    """A model's step as the engine compiles it (`fused.RaggedStep`):
    behind the token hand-over, whose two arguments (`src` a packed row,
    the previous step's ids a descriptor) follow the model's own."""
    from paddle_tpu.generation.fused import handing_over

    rows, ids = fixed[0], fixed[5]
    return handing_over(fn, len(fixed)), [*fixed, rows, ids]


@pytest.mark.parametrize("t,s", [(80, 17), (528, 17), (576, 65)])
def test_the_token_hand_over_is_a_gather_of_ids(v5e, t, s):
    """The merged token row at the cells' packed axes (OPT and GLM,
    Trinity, Granite): a gather of [max_tokens] ids out of the previous
    step's [max_seqs] and a select.  Nothing wider than the row is made
    of the ids (no one-hot product, no broadcast over the rows), and the
    whole steps below, lowered behind it, still yield no pool and no
    state array but by their in-place writes."""
    from paddle_tpu.generation.fused import hand_over_tokens

    row, ids = ((t,), "int32"), ((s,), "int32")
    structs = [jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=v5e)
               for shape, dtype in (row, row, ids)]
    compiled = jax.jit(hand_over_tokens).lower(*structs).compile()
    shapes = set(re.findall(r"= \w+\[([\d,]*)\]", compiled.as_text()))
    widest = max(math.prod(int(d) for d in shape.split(",") if d)
                 for shape in shapes)
    assert widest <= max(t, 1024), shapes     # a row, or a tile of scratch
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _opt_step_lowered(v5e, layout, pages_bucket, layers=2):
    """TinyCausalLM's ragged step at opt-6.7b-d8's shapes (32 heads of
    128, a 1280-page pool, 80 packed rows under 17 descriptors; depth and
    vocabulary cut, weights as shapes), pools donated, lowered for v5e;
    and the pool's shape."""
    from paddle_tpu.generation.model import TinyCausalLM

    heads, pages, t, s, vocab = 32, 1280, 80, 17, 128
    d = heads * HEAD_DIM
    model = TinyCausalLM(vocab_size=8, num_layers=0, num_heads=heads,
                         head_dim=HEAD_DIM, max_positions=8)

    def sds(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    block = {"ln1_s": sds((d,)), "ln1_b": sds((d,)), "wq": sds((d, d)),
             "wk": sds((d, d)), "wv": sds((d, d)), "wo": sds((d, d)),
             "ln2_s": sds((d,)), "ln2_b": sds((d,)),
             "w1": sds((d, 4 * d)), "b1": sds((4 * d,)),
             "w2": sds((4 * d, d)), "b2": sds((d,))}
    params = {"tok_emb": sds((vocab, d)), "pos_emb": sds((2048, d)),
              "blocks": [block] * layers, "ln_f_s": sds((d,)),
              "ln_f_b": sds((d,)), "head": sds((d, vocab))}
    shape = ((heads, pages, PAGE_SIZE, HEAD_DIM) if layout == "kernel"
             else (pages, PAGE_SIZE, heads, HEAD_DIM))
    fixed = ([sds((t,), "int32")] * 4 + [sds((s, pages_bucket), "int32")]
             + [sds((s,), "int32")] * 3)
    fn, fixed = _handed_over(
        model.ragged_step_fn(PAGE_SIZE, pages, use_kernel=True,
                             pool_layout=layout), fixed)
    return jax.jit(fn, donate_argnums=(11, 12)).lower(
        params, *fixed, [sds(shape)] * layers, [sds(shape)] * layers), shape


def _pool_sized_results(v5e, layout, pages_bucket, layers=2):
    """That step compiled: the opcodes of the instructions that yield a
    whole pool and are not the in-place row write, the count of those
    that are, the program's temporaries in bytes, and the operand
    types of its custom calls."""
    heads, pages = 32, 1280
    lowered, shape = _opt_step_lowered(v5e, layout, pages_bucket, layers)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= layers
    either = "|".join(",".join(map(str, dims)) for dims in (
        shape, (heads, pages, PAGE_SIZE, HEAD_DIM)))
    whole = [(m.group(1), "output_to_operand_aliasing" in m.group(0))
             for m in re.finditer(
                 rf"^\s*(?:ROOT )?%?[\w.\-]+ = f32\[(?:{either})\]\S* "
                 r"([\w\-]+)\(.*$", text, re.M)]
    in_place = [op for op, aliased in whole
                if op == "custom-call" and aliased]
    return ({op for op, aliased in whole if op != "parameter"
             and not (op == "custom-call" and aliased)},
            len(in_place), compiled.memory_analysis().temp_size_in_bytes,
            _custom_call_operands(text))


@pytest.mark.parametrize("pages_bucket", [16, 128])
def test_ragged_step_moves_no_pool_in_kernel_layout(v5e, pages_bucket):
    """The layout the engine picks for opt-6.7b-d8's pools: the only
    instructions that yield a whole pool (335 MB) are the K and V row
    writes of each layer, each aliased to its operand; no copy, no
    transpose, and temporaries far under one pool."""
    others, in_place, temp_bytes, _ = _pool_sized_results(v5e, "kernel",
                                                          pages_bucket)
    assert (others, in_place) == (set(), 4)
    assert temp_bytes < 64 << 20


# sha256 of the OPT step's lowered text with the kernels' source
# locations dropped, by pages bucket; as PR 38's tree lowers it (PR 35's
# grouped cell of the per-head kernel, behind the token hand-over; PR 38
# named the Pallas calls, and the text differs from PR 37's by those
# names alone)
OPT_STEP_DIGESTS = {
    16: "a8df76c3951184e3694960e1867bb914d91fd0d1c4764c1d35d4e3bede26f4d8",
    128: "d46e3f5461ac8997ec99589ac63d8e68bcaa2c22a7f48c8309da225588282cd3",
}


@pytest.mark.parametrize("pages_bucket", sorted(OPT_STEP_DIGESTS))
def test_the_opt_ragged_step_lowers_to_the_text_it_had(v5e, pages_bucket):
    """The per-head kernel's step is not the latent kernel's: a change to
    `latent_*` leaves opt-6.7b-d8's lowered step as it was.  The text
    compared is the step's StableHLO with each Mosaic kernel's body (a
    serialised module inside its custom call) read back and printed
    without source locations, which name the checkout's path and every
    line of the kernel; all else counts.  A PR that means to change what
    the OPT cells run states the new digests here."""
    lowered, _ = _opt_step_lowered(v5e, "kernel", pages_bucket)
    # a layer: two row writes; the attention call is one jitted
    # function of the step, lowered once for its two layers
    digest = _step_digest(lowered, 2 * 2 + 1)
    assert digest == OPT_STEP_DIGESTS[pages_bucket]


def _step_digest(lowered, n_kernels):
    """sha256 of a lowered step's StableHLO with each Mosaic kernel's
    body read back and printed without source locations."""
    import base64
    import hashlib

    from jax.extend.mlir import ir

    body = re.compile(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22')
    text = lowered.as_text()
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        kernels = [ir.Module.parse(base64.b64decode(b)).operation.get_asm(
            enable_debug_info=False) for b in body.findall(text)]
    assert len(kernels) == n_kernels
    return hashlib.sha256(
        (body.sub("BODY", text) + "".join(kernels)).encode()).hexdigest()


def _shapes_only(monkeypatch, cls):
    """`cls` draws its weights as shapes: a whole model for lowering."""
    draw = cls._draw
    monkeypatch.setattr(
        cls, "_draw",
        lambda self, seed: jax.eval_shape(lambda: draw(self, seed)))


# the latent step behind the token hand-over (one dense and one expert
# layer, a 1,024-row vocabulary, the 512-page bucket; all else the
# cell's), its Pallas call named, the experts' pairs choice-major (the
# text differs from the token-major form's inside `moe.expert_ffn` alone)
GLM_STEP_DIGEST = (
    "39cfb42507fc751c33ea1974a678788370c57f0dd7e3b4eec02eb4363d6dff3f")


def test_the_glm_ragged_step_lowers_to_the_text_it_had(v5e, monkeypatch):
    """`LatentMoELM`'s step is not touched by what it now shares with
    the third served model (`generation/blocks.py`) nor by that model's
    cache and kernel: glm-4.7-flash-d7's lowered step, cut to two layers
    and a small vocabulary, is the text `GLM_STEP_DIGEST` pins."""
    from paddle_tpu.generation import latent_moe_model as lm

    args, engine = _glm_cell()
    _shapes_only(monkeypatch, lm.LatentMoELM)
    model = lm.LatentMoELM(**dict(args, num_layers=2, vocab_size=1024),
                           seed=1)
    t = engine["prefill_chunk_tokens"] + engine["max_decode_slots"]
    s = engine["max_decode_slots"] + 1
    rows = model.kv_rows()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    model.params)
    pool = sds((engine["num_pages"], engine["page_size"], rows.lanes),
               rows.dtype)
    fixed = [sds((t,), "int32")] * 4 + [sds((s, 512), "int32")] + [
        sds((s,), "int32")] * 3
    fn, fixed = _handed_over(
        model.ragged_step_fn(engine["page_size"], engine["num_pages"],
                             use_kernel=True), fixed)
    lowered = jax.jit(fn, donate_argnums=(11,)).lower(
        params, *fixed, [pool] * model.num_layers)
    assert _step_digest(lowered, 2) == GLM_STEP_DIGEST


def _granite_cell():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "granite-4.0-h-small-d10.json")) as f:
        builder = json.load(f)["builder"]
    return builder["model_args"], builder["engine"]


def _yielded_outside_fusions(text, shape):
    """The opcodes of a compiled program's instructions that yield
    `shape` in the computations no fusion calls: the arrays of that
    shape the program materialises."""
    fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", text))
    opcodes, inside = [], False
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            name = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            inside = name in fused
        elif not inside:
            m = re.match(rf"\s*(?:ROOT )?%?[\w.\-]+ = {re.escape(shape)}\S* "
                         r"([\w\-]+)\(", line)
            if m:
                opcodes.append(m.group(1))
    return opcodes


def test_hybrid_step_compiles_at_the_published_widths(v5e, monkeypatch):
    """The whole ragged step of granite-4.0-h-small-d10 at the cell's
    largest pages bucket, weights as shapes: it fits the chip beside its
    13.2 GB of weights, states and pages; NO operation copies a state
    array (272 MB a layer: XLA:TPU did, twice a layer, while the scan's
    state products had two free axes a side), each state array is
    yielded whole by the one-token update's fusion and by the scan's
    in-place slot write alone, and the attention layer's pool by its row
    write; one grouped-query call and 10 layers x 3 grouped products.
    The experts' way back makes no [576, 10, 4096] relayout of the
    (token, choice) products (k = 10 on the 8-row tile: 151 MB a layer
    while the pairs lay token-major) and materialises the products'
    [5760, 4096] once a layer beside the grouped product itself: the
    gather home."""
    from paddle_tpu.generation import hybrid_ssm_moe_model as hm

    args, engine = _granite_cell()
    _shapes_only(monkeypatch, hm.HybridSSMMoELM)
    model = hm.HybridSSMMoELM(**args, seed=1)
    slots = engine["max_decode_slots"]
    t, s = engine["prefill_chunk_tokens"] + slots, slots + 1
    rows, state = model.kv_rows(), model.kv_slot_state()
    assert (rows.lanes, rows.token_bytes(1)) == (2048, 4096)
    assert state.bytes_a_slot == 4194304 + 50688

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    model.params)
    pools = [sds((slots + 1,) + state.state_shape, state.state_dtype)
             if kind == "state" else
             sds((engine["num_pages"], engine["page_size"], rows.lanes),
                 rows.dtype) for kind in model.layer_kinds]
    tails = [sds((slots + 1,) + state.tail_shape, state.tail_dtype)
             for kind in model.layer_kinds if kind == "state"]
    fixed = ([sds((t,), "int32")] * 4 + [sds((s, 64), "int32")]
             + [sds((s,), "int32")] * 4)
    fn, fixed = _handed_over(
        model.ragged_step_fn(engine["page_size"], engine["num_pages"],
                             use_kernel=True), fixed)
    compiled = jax.jit(fn, donate_argnums=(12, 13)).lower(
        params, *fixed, pools, tails).compile()
    memory = compiled.memory_analysis()
    assert 13.0e9 < memory.argument_size_in_bytes < 13.5e9
    assert memory.temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + 10 * 3
    whole = [m.group(1) for m in re.finditer(
        r"^\s*%?[\w.\-]+ = f32\[65,128,64,128\]\S* ([\w\-]+)\(", text,
        re.M)]
    assert "copy" not in whole and "transpose" not in whole, whole
    assert whole.count("fusion") == 9       # the scan's slot write a layer
    pool = [m.group(1) for m in re.finditer(
        r"^\s*%?[\w.\-]+ = bf16\[3072,64,2048\]\S* (?!parameter)([\w\-]+)\(",
        text, re.M)]
    assert pool == ["fusion"], pool
    assert "f32[576,10,4096]" not in text
    products = [op for op in _yielded_outside_fusions(text, "f32[5760,4096]")
                if op != "custom-call"]
    assert len(products) <= 10, products


def test_the_pool_check_tells_the_token_layout(v5e, monkeypatch):
    """The same check on what the engine ran until PR 29: each layer's
    token-layout pools are transposed whole for the kernel (a fusion and
    335 MB of temporaries each) — and in kernel layout XLA's own scatter
    copies each pool into a layout of its choosing and back."""
    from paddle_tpu.ops.pallas import paged_attention

    pool_bytes = 32 * 1280 * PAGE_SIZE * HEAD_DIM * 4
    others, in_place, temp_bytes, _ = _pool_sized_results(v5e, "token", 16)
    assert {"scatter", "fusion"} <= others and in_place == 0
    assert temp_bytes > pool_bytes
    monkeypatch.setattr(paged_attention, "pool_scatter_in_place",
                        lambda shape, dtype: False)
    others, in_place, temp_bytes, _ = _pool_sized_results(v5e, "kernel", 16)
    assert {"scatter", "copy"} <= others and in_place == 0
    assert temp_bytes > pool_bytes


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_decode_kernel_compiles_for_v5e(v5e, kv_dtype):
    def fn(q, pt, seq_lens, kp, vp, *rest):
        return paged_decode_attention(q, kp, vp, pt, seq_lens,
                                      use_kernel=True, **_scales(rest))

    _compile(fn, v5e, ((SLOTS, HEADS, HEAD_DIM), "float32"),
             ((SLOTS, MAX_PAGES), "int32"), ((SLOTS,), "int32"),
             *_pool_operands(kv_dtype))


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_chunk_kernel_compiles_for_v5e(v5e, kv_dtype):
    def fn(q, pt, start, kp, vp, *rest):
        return chunk_prefill_attention(q, kp, vp, pt, start,
                                       use_kernel=True, **_scales(rest))

    _compile(fn, v5e, ((CHUNK, HEADS, HEAD_DIM), "float32"),
             ((MAX_PAGES,), "int32"), ((), "int32"),
             *_pool_operands(kv_dtype))


@pytest.mark.parametrize("shape,dtype", [
    ((8, 12, 512, 64), "bfloat16"),     # GPT-2 small, bench.py's batch
    ((2, 8, 1024, 128), "float32"),
])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, shape, dtype):
    b, h, l, d = shape

    def loss(q, k, v):
        km = jnp.zeros((1, 1, l), jnp.float32)
        out = fa._flash(q, k, v, km, True, h, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    flat = ((b * h, l, d), dtype)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, flat, flat, flat)


def test_a_tpu_lowering_has_no_cost_analysis(v5e):
    """Why bench._measured_flops and every cost_analysis() caller must
    take None: on this backend jax 0.9.0 analyses the compiled
    executable only."""
    struct = jax.ShapeDtypeStruct((256, 256), np.float32, sharding=v5e)
    lowered = jax.jit(jnp.matmul).lower(struct, struct)
    assert lowered.cost_analysis() is None
    assert lowered.compile().cost_analysis()["flops"] > 0


def _custom_call_operands(text):
    """The operands' types (``s32[17]``) of every `tpu_custom_call` of a
    compiled module's text, which names its operands without them."""
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])",
                            text, re.M))
    return [[types.get(name, name) for name in m.group(1).split(", ")]
            for m in re.finditer(
                r" custom-call\(([^)]*)\), custom_call_target="
                r'"tpu_custom_call"', text)]


def _glm_cell():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-4.7-flash-d7.json")) as f:
        builder = json.load(f)["builder"]
    return builder["model_args"], builder["engine"]


@pytest.mark.parametrize("pages_bucket", [64, 512])
def test_latent_kernel_compiles_at_the_benchmark_shapes(v5e, pages_bucket):
    """glm-4.7-flash-d7.docqa-closed: 20 heads against one 640-lane latent
    row (576 numbers a token), 16 slots + a 64-token chunk = 80 packed
    rows under 17 descriptors, a 5760-page pool of 64-token pages.  The
    tile-major list of 16-page cells (26 x bucket / 16 of them: 832 cell
    words and 13,312 page words in SMEM at the 512-page bucket), a q
    tile's 160 rows and the two 1.25 MiB halves of a cell's page block
    lower, and nothing of the pool's size is made."""
    from paddle_tpu.generation.decode_attention import (
        latent_ragged_attention)
    from paddle_tpu.ops.pallas.paged_attention import (
        latent_grid_cells, latent_pages_per_cell)

    args, engine = _glm_cell()
    t = engine["prefill_chunk_tokens"] + engine["max_decode_slots"]
    s, lanes = engine["max_decode_slots"] + 1, 640

    def fn(q, pool, pt, starts, lens, kv_lens):
        return latent_ragged_attention(
            q, pool, pt, starts, lens, kv_lens, 1 / 16,
            args["kv_lora_rank"], True)

    compiled = _compile(
        fn, v5e, ((t, args["num_heads"], lanes), "bfloat16"),
        ((engine["num_pages"], engine["page_size"], lanes), "bfloat16"),
        ((s, pages_bucket), "int32"), ((s,), "int32"), ((s,), "int32"),
        ((s,), "int32"))
    # the pool goes to the kernel as it is stored: a copy of it would
    # show as a temporary of the pool's size
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    per = latent_pages_per_cell(engine["page_size"], pages_bucket)
    cells = latent_grid_cells(s, pages_bucket, t, engine["page_size"])
    assert (per, cells) == (16, 26 * pages_bucket // 16)
    # the list rides as the kernel's scalar-prefetch operands: the
    # traced grid bound first, then the page slots and the cell words
    (call,) = _custom_call_operands(compiled.as_text())
    assert call[:3] == ["s32[]", f"s32[{cells * per}]", f"s32[{cells}]"]


def test_latent_step_compiles_at_the_published_widths(v5e, monkeypatch):
    """The whole ragged step of glm-4.7-flash-d7 at the cell's largest
    pages bucket, weights as shapes: it fits the chip beside its 11.5 GiB
    of weights and pools, the 7 latent pools are updated in place (no
    operation but a layer's scatter produces a whole pool), and the
    kernels are there (7 latent calls, 6 layers x 3 grouped-product
    calls)."""
    from paddle_tpu.generation import latent_moe_model as lm

    args, engine = _glm_cell()
    draw = lm.LatentMoELM._draw
    monkeypatch.setattr(
        lm.LatentMoELM, "_draw",
        lambda self, seed: jax.eval_shape(lambda: draw(self, seed)))
    model = lm.LatentMoELM(**args, seed=1)
    t = engine["prefill_chunk_tokens"] + engine["max_decode_slots"]
    s = engine["max_decode_slots"] + 1
    rows = model.kv_rows()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    model.params)
    pool = sds((engine["num_pages"], engine["page_size"], rows.lanes),
               rows.dtype)
    fixed = [sds((t,), "int32")] * 4 + [sds((s, 512), "int32")] + [
        sds((s,), "int32")] * 3
    fn, fixed = _handed_over(
        model.ragged_step_fn(engine["page_size"], engine["num_pages"],
                             use_kernel=True), fixed)
    compiled = jax.jit(fn, donate_argnums=(11,)).lower(
        params, *fixed, [pool] * model.num_layers).compile()
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    assert memory.temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 7 + 6 * 3
    # ... which the benchmark tells apart by their first operand
    # (benchmarks/trace/custom_calls.py): ONE latent call a layer, the
    # s32 grid bound leading it and the list behind, or
    # `kernel.latent_roofline` divides one call's floor by the wrong time
    latent = [call for call in _custom_call_operands(text)
              if call[:3] == ["s32[]", "s32[13312]", "s32[832]"]]
    assert len(latent) == model.num_layers
    whole = [m.group(1) for m in re.finditer(
        r"^\s*%?([\w.\-]+) = bf16\[5760,64,640\]\S* (?!parameter)", text,
        re.M)]
    assert len(whole) == model.num_layers, whole


def _trinity_cell():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "trinity-mini-d8.json")) as f:
        builder = json.load(f)["builder"]
    return builder["model_args"], builder["engine"]


@pytest.mark.parametrize("pages_bucket,pool_pages,window", [
    (1024, 4096, None), (1024, 697, 2048), (64, 697, 2048), (1, 4096, None)])
def test_gqa_kernel_compiles_at_the_benchmark_shapes(v5e, pages_bucket,
                                                     pool_pages, window):
    """trinity-mini-d8.mixed-closed: 32 query heads over 4 KV heads of
    128 against 1,024-lane bf16 rows, 16 slots + a 512-token chunk = 528
    packed rows under 17 descriptors, 64-token pages; the full layers'
    4,096-page pool and the window layers' 697-page one.  At the cell's
    largest bucket (1,024 pages: 33,280 tokens) the flat page tables
    (17,408 words) and the list (3,136 cell words, 196 with a window) ride
    in SMEM, a q tile's 4 x 128 rows and the two 2 MiB halves of a cell's
    page block lower, and nothing of the pool's size is made."""
    from paddle_tpu.generation.decode_attention import gqa_ragged_attention
    from paddle_tpu.ops.pallas.gqa_paged_attention import gqa_grid_cells

    args, engine = _trinity_cell()
    t = engine["prefill_chunk_tokens"] + engine["max_decode_slots"]
    s = engine["max_decode_slots"] + 1
    lanes = 2 * args["num_kv_heads"] * args["head_dim"]

    def fn(q, pool, pt, starts, lens, kv_lens):
        return gqa_ragged_attention(q, pool, pt, starts, lens, kv_lens,
                                    args["head_dim"] ** -0.5,
                                    args["num_kv_heads"], window, True)

    compiled = _compile(
        fn, v5e, ((t, args["num_heads"], args["head_dim"]), "bfloat16"),
        ((pool_pages, engine["page_size"], lanes), "bfloat16"),
        ((s, pages_bucket), "int32"), ((s,), "int32"), ((s,), "int32"),
        ((s,), "int32"))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    cells = gqa_grid_cells(s, pages_bucket, t, engine["page_size"], window)
    # the scalar-prefetch operands: the traced grid bound, the flat page
    # tables, the cell words
    (call,) = _custom_call_operands(compiled.as_text())
    assert call[:3] == ["s32[]", f"s32[{s * pages_bucket}]", f"s32[{cells}]"]


def test_gqa_step_compiles_at_the_published_widths(v5e, monkeypatch):
    """The whole ragged step of trinity-mini-d8 at the cell's largest
    pages bucket, weights as shapes: it fits the chip beside its 12.6 GiB
    of weights and pools; no operation but a layer's row write produces a
    whole pool, of either group (no copy, no transpose); and the kernels
    are there: 8 attention calls, 6 layers x 3 grouped-product calls."""
    from paddle_tpu.generation import gqa_window_moe_model as gm

    args, engine = _trinity_cell()
    _shapes_only(monkeypatch, gm.GQAWindowMoELM)
    model = gm.GQAWindowMoELM(**args, seed=1)
    t = engine["prefill_chunk_tokens"] + engine["max_decode_slots"]
    s = engine["max_decode_slots"] + 1
    rows = model.kv_rows()
    assert (rows.lanes, rows.token_bytes(1)) == (1024, 2048)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    model.params)
    pages = {"full": engine["num_pages"], "window": 697}
    pools = [sds((pages[kind], engine["page_size"], rows.lanes), rows.dtype)
             for kind in model.layer_kinds]
    tables = sds((s, 1024), "int32")
    fixed = ([sds((t,), "int32")] * 4 + [tables] + [sds((s,), "int32")] * 3
             + [sds((t,), "int32"), tables])
    fn, fixed = _handed_over(
        model.ragged_step_fn(engine["page_size"], engine["num_pages"],
                             use_kernel=True), fixed)
    compiled = jax.jit(fn, donate_argnums=(13,)).lower(
        params, *fixed, pools).compile()
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    assert memory.temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 8 + 6 * 3
    attention = [call for call in _custom_call_operands(text)
                 if call[:2] == ["s32[]", f"s32[{s * 1024}]"]]
    assert len(attention) == model.num_layers
    for kind, count in (("full", 2), ("window", 6)):
        whole = [m.group(1) for m in re.finditer(
            rf"^\s*%?([\w.\-]+) = bf16\[{pages[kind]},64,1024\]\S* "
            r"(?!parameter)", text, re.M)]
        assert len(whole) == count, (kind, whole)
