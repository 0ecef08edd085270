"""The names a device profile of the served models is read by, compiled
for a TPU v5e with no TPU attached (`test_chip_compile.py`'s compile-only
topology).

A TPU profile names an operation by its instruction and its program's
module, and carries no scope (PR 36).  So the compiled step of each
served model, at two layers and the widths of its cell, has to hold:
the program's name (`ragged_step_p<bucket>`, which `CompiledModelCache`
gives it); a kernel name on every `tpu_custom_call` (the program's own
Pallas calls, or XLA's grouped product, `ragged-dot...`); and every dot,
convolution and custom call, and every fusion that kept an op_name,
under one of the step's parts (`fused.STEP_SCOPES`), as
`profiler.device_op_scopes()` reads it from the compiled text.
"""
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.generation.fused import STEP_SCOPES, handing_over
from paddle_tpu.profiler import device_op_scopes
from paddle_tpu.serving.bucketing import CompiledModelCache

# the package re-exports the function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the program's Pallas calls, and XLA:TPU's grouped product and its
# group bookkeeping (`jax.lax.ragged_dot`)
KERNELS = re.compile(r"^(ragged_paged_attention|latent_paged_attention|"
                     r"gqa_paged_attention|pool_row_write|ragged-dot[\w-]*)"
                     r"(\.\d+)?$")
_CALL = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .* custom-call\(.*"
                   r'custom_call_target="tpu_custom_call"', re.M)
_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                 r"(dot|convolution|custom-call|fusion)\(")


def _unfused(text):
    """The compiled text without the computations fusions call: what is
    left are the instructions a profile's line shows."""
    fused = set(re.findall(r" fusion\(.*?, calls=%([\w.\-]+)", text))
    return re.sub(r"^%([\w.\-]+) [^\n]*\{\n.*?^\}\n",
                  lambda m: "" if m.group(1) in fused else m.group(0),
                  text, flags=re.M | re.S)


@pytest.fixture(scope="module")
def one_v5e():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("chip",)),
                         PartitionSpec())


@pytest.fixture(autouse=True)
def mosaic_not_interpreter(monkeypatch):
    monkeypatch.setattr(fa, "_interpret", lambda: False)


def _cell(config):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        builder = json.load(f)["builder"]
    return builder["model_args"], builder["engine"]


def _shapes_only(monkeypatch, cls):
    draw = cls._draw
    monkeypatch.setattr(
        cls, "_draw",
        lambda self, seed: jax.eval_shape(lambda: draw(self, seed)))


def _compiled_step(fn, params, fixed, state, bucket):
    """`fn` behind the hand-over, as `RaggedStep` compiles it, through
    `CompiledModelCache` under the name `RaggedStep` gives the bucket;
    checked as the module docstring says.  Returns the step's parts."""
    s = fixed[5].shape[0]
    fixed = fixed + [fixed[0], jax.ShapeDtypeStruct(
        (s,), jnp.int32, sharding=fixed[0].sharding)]
    step = handing_over(fn, len(fixed) - 2)
    leaves, tree = jax.tree_util.tree_flatten((params, fixed, state))

    def flat(*args):
        params, fixed, state = jax.tree_util.tree_unflatten(tree, args)
        return step(params, *fixed, *state)

    cache = CompiledModelCache(flat, name=lambda _: f"ragged_step_p{bucket}")
    text = cache.get(leaves).as_text()
    module = f"jit_ragged_step_p{bucket}"
    assert text.startswith(f"HloModule {module},")
    # read while the cache holds the program: the profiler keeps a weak
    # reference alone
    scopes = device_op_scopes()[module]
    text = _unfused(text)
    calls = _CALL.findall(text)
    assert calls and all(KERNELS.match(c) for c in calls), calls
    # XLA's own custom calls (`ConcatBitcast` of a prefetched weight)
    # carry no op_name: they take a user's scope where one has it
    unscoped = [(m.group(1), m.group(2), scopes.get(m.group(1)))
                for m in map(_OP.match, text.splitlines())
                if m and (m.group(2) in ("dot", "convolution")
                          or "op_name=" in m.string
                          or "tpu_custom_call" in m.string)
                and scopes.get(m.group(1), "").split("/")[0]
                not in STEP_SCOPES]
    assert not unscoped, unscoped
    return {path.split("/")[0] for path in scopes.values()}


def _sds(sharding):
    def sds(shape, dtype="int32"):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=sharding)
    return sds


def test_the_opt_step_names_its_kernels_and_parts(one_v5e):
    """Two layers of TinyCausalLM at opt-6.7b-d8's widths over the
    per-head pools in kernel layout: row writes and the ragged kernel."""
    from paddle_tpu.generation.model import TinyCausalLM

    args, engine = _cell("opt-6.7b-d8")
    heads, dim, pages = args["num_heads"], args["head_dim"], 1280
    d, t, s, bucket = heads * dim, 80, 17, 128
    model = TinyCausalLM(vocab_size=8, num_layers=0, num_heads=heads,
                         head_dim=dim, max_positions=8)
    sds = _sds(one_v5e)
    f32 = "float32"
    block = {"ln1_s": sds((d,), f32), "ln1_b": sds((d,), f32),
             "wq": sds((d, d), f32), "wk": sds((d, d), f32),
             "wv": sds((d, d), f32), "wo": sds((d, d), f32),
             "ln2_s": sds((d,), f32), "ln2_b": sds((d,), f32),
             "w1": sds((d, 4 * d), f32), "b1": sds((4 * d,), f32),
             "w2": sds((4 * d, d), f32), "b2": sds((d,), f32)}
    params = {"tok_emb": sds((128, d), f32), "pos_emb": sds((2048, d), f32),
              "blocks": [block] * 2, "ln_f_s": sds((d,), f32),
              "ln_f_b": sds((d,), f32), "head": sds((d, 128), f32)}
    pool = sds((heads, pages, engine["page_size"], dim), f32)
    fixed = [sds((t,))] * 4 + [sds((s, bucket))] + [sds((s,))] * 3
    parts = _compiled_step(
        model.ragged_step_fn(engine["page_size"], pages, use_kernel=True,
                             pool_layout="kernel"),
        params, fixed, ([pool] * 2, [pool] * 2), bucket)
    assert parts >= {"hand_over", "embed", "attention", "mlp", "head"}


@pytest.mark.parametrize("config,module,name,kinds", [
    ("glm-4.7-flash-d7", "latent_moe_model", "LatentMoELM", None),
    ("trinity-mini-d8", "gqa_window_moe_model", "GQAWindowMoELM",
     ["sliding_attention", "full_attention"]),
    ("granite-4.0-h-small-d10", "hybrid_ssm_moe_model", "HybridSSMMoELM",
     ["mamba", "attention"]),
])
def test_a_served_step_names_its_kernels_and_parts(one_v5e, monkeypatch,
                                                   config, module, name,
                                                   kinds):
    """Two layers of each model with experts: GLM's dense one and an
    expert one, Trinity's window and full layer (the second with
    experts), Granite's state-space and attention layer."""
    cls = getattr(importlib.import_module(
        f"paddle_tpu.generation.{module}"), name)
    args, engine = _cell(config)
    args = dict(args, num_layers=2, vocab_size=1024)
    if kinds is not None:
        args["layer_types"] = kinds
    if "first_k_dense_replace" in args:
        args["first_k_dense_replace"] = 1
    _shapes_only(monkeypatch, cls)
    model = cls(**args, seed=1)
    sds = _sds(one_v5e)
    slots, bucket = engine["max_decode_slots"], 64
    t, s = engine["prefill_chunk_tokens"] + slots, slots + 1
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    model.params)
    rows = model.kv_rows()
    pool = sds((engine["num_pages"], engine["page_size"], rows.lanes),
               rows.dtype)
    fixed = [sds((t,))] * 4 + [sds((s, bucket))] + [sds((s,))] * 3
    state = ([pool] * 2,)
    layer_kinds = getattr(model, "layer_kinds", ())
    if "window" in layer_kinds:
        fixed += [sds((t,)), sds((s, bucket))]
    if "state" in layer_kinds:
        st = model.kv_slot_state()
        fixed.append(sds((s,)))
        state = ([sds((slots + 1,) + st.state_shape, st.state_dtype)
                  if kind == "state" else pool for kind in layer_kinds],
                 [sds((slots + 1,) + st.tail_shape, st.tail_dtype)])
    parts = _compiled_step(
        model.ragged_step_fn(engine["page_size"], engine["num_pages"],
                             use_kernel=True),
        params, fixed, state, bucket)
    assert parts >= {"hand_over", "embed", "attention", "experts", "head"}
    assert ("state_space" in parts) == ("state" in layer_kinds)
    assert ("mlp" in parts) == (config != "granite-4.0-h-small-d10")
