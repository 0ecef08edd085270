"""The plain reference of the hybrid state-space model
(`benchmarks/reference/granitemoehybrid.py`) against `transformers`' own
`GraniteMoeHybridForCausalLM`: one set of seeded float32 weights copied
into both, the logits of a full pass compared.  The reference is what
every served-path test compares with; this ties it to the published
code (`torch_forward`: the chunked scan of `transformers`, against the
reference's token-by-token recurrence).
"""
import os
import sys

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import granitemoehybrid as ref  # noqa: E402

SHAPE = {
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
    "num_experts_per_tok": 3, "layer_types": ["mamba", "attention",
                                              "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 16.0,
    "rms_norm_eps": 1e-5,
}
VOCAB, HIDDEN, EXPERTS, WIDTH, SHARED, TAPS = 97, 32, 8, 12, 20, 4


def _config():
    return transformers.GraniteMoeHybridConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=WIDTH,
        num_hidden_layers=len(SHAPE["layer_types"]),
        num_attention_heads=SHAPE["num_heads"],
        num_key_value_heads=SHAPE["num_kv_heads"],
        rms_norm_eps=SHAPE["rms_norm_eps"], tie_word_embeddings=True,
        embedding_multiplier=SHAPE["embedding_multiplier"],
        logits_scaling=SHAPE["logits_scaling"],
        residual_multiplier=SHAPE["residual_multiplier"],
        attention_multiplier=SHAPE["attention_multiplier"],
        num_local_experts=EXPERTS,
        num_experts_per_tok=SHAPE["num_experts_per_tok"],
        shared_intermediate_size=SHARED, position_embedding_type="nope",
        layer_types=SHAPE["layer_types"],
        mamba_n_heads=SHAPE["mamba_n_heads"], mamba_n_groups=1,
        mamba_d_state=SHAPE["mamba_d_state"],
        mamba_d_head=SHAPE["mamba_d_head"], mamba_d_conv=TAPS,
        mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True,
        mamba_proj_bias=False, attention_dropout=0.0)


def _params(rng):
    """The reference's tree, float32: normals of 1/sqrt(fan-in), the
    recurrence's parameters in the ranges the configuration's file
    states (decays between 0.2 and 0.999 a token)."""
    def w(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])).astype(
            np.float32)

    def gain(n):
        return (1.0 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    d_inner = SHAPE["mamba_n_heads"] * SHAPE["mamba_d_head"]
    conv = d_inner + 2 * SHAPE["mamba_d_state"]
    q_width = SHAPE["num_heads"] * SHAPE["head_dim"]
    kv_width = SHAPE["num_kv_heads"] * SHAPE["head_dim"]
    layers = []
    for kind in SHAPE["layer_types"]:
        lp = {"norm1": gain(HIDDEN), "norm2": gain(HIDDEN),
              "w_router": w(HIDDEN, EXPERTS),
              "experts_gate_up": w(EXPERTS, HIDDEN, 2 * WIDTH),
              "experts_down": w(EXPERTS, WIDTH, HIDDEN),
              "shared_gate_up": w(HIDDEN, 2 * SHARED),
              "shared_down": w(SHARED, HIDDEN)}
        if kind == "mamba":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                    SHAPE["mamba_n_heads"]))
            lp.update({
                "w_in": w(HIDDEN, 2 * d_inner + 2 * SHAPE["mamba_d_state"]
                          + SHAPE["mamba_n_heads"]),
                "conv_w": (rng.standard_normal((TAPS, conv)) * 0.5).astype(
                    np.float32),
                "conv_b": (0.1 * rng.standard_normal(conv)).astype(
                    np.float32),
                "dt_bias": np.log(np.expm1(dt)).astype(np.float32),
                "A_log": np.log(rng.uniform(1.0, 16.0,
                                            SHAPE["mamba_n_heads"])).astype(
                    np.float32),
                "D": gain(SHAPE["mamba_n_heads"]),
                "norm_ssm": gain(d_inner), "w_out": w(d_inner, HIDDEN)})
        else:
            lp.update({"w_q": w(HIDDEN, q_width), "w_k": w(HIDDEN, kv_width),
                       "w_v": w(HIDDEN, kv_width), "w_o": w(q_width, HIDDEN)})
        layers.append(lp)
    return {"embed": (0.5 * rng.standard_normal((VOCAB, HIDDEN))).astype(
                np.float32),
            "layers": layers, "norm_f": gain(HIDDEN)}


def _copy(dst, src):
    assert tuple(dst.shape) == tuple(src.shape), (dst.shape, src.shape)
    with torch.no_grad():
        dst.copy_(torch.from_numpy(np.ascontiguousarray(src)))


def _into_hf(params, hf):
    """torch's Linear stores [out, in], ours [in, out]; its depthwise
    convolution [C, 1, K], ours [K, C]; its experts [E, 2f, d] and
    [E, d, f], ours [E, d, 2f] and [E, f, d]."""
    _copy(hf.model.embed_tokens.weight, params["embed"])
    _copy(hf.model.norm.weight, params["norm_f"])
    for lp, layer in zip(params["layers"], hf.model.layers):
        _copy(layer.input_layernorm.weight, lp["norm1"])
        _copy(layer.post_attention_layernorm.weight, lp["norm2"])
        moe = layer.block_sparse_moe
        _copy(moe.router.layer.weight, lp["w_router"].T)
        _copy(moe.input_linear.weight,
              lp["experts_gate_up"].transpose(0, 2, 1))
        _copy(moe.output_linear.weight, lp["experts_down"].transpose(0, 2, 1))
        _copy(layer.shared_mlp.input_linear.weight, lp["shared_gate_up"].T)
        _copy(layer.shared_mlp.output_linear.weight, lp["shared_down"].T)
        if "w_in" in lp:
            m = layer.mamba
            _copy(m.in_proj.weight, lp["w_in"].T)
            _copy(m.conv1d.weight, lp["conv_w"].T[:, None, :])
            _copy(m.conv1d.bias, lp["conv_b"])
            _copy(m.dt_bias, lp["dt_bias"])
            _copy(m.A_log, lp["A_log"])
            _copy(m.D, lp["D"])
            _copy(m.norm.weight, lp["norm_ssm"])
            _copy(m.out_proj.weight, lp["w_out"].T)
        else:
            a = layer.self_attn
            _copy(a.q_proj.weight, lp["w_q"].T)
            _copy(a.k_proj.weight, lp["w_k"].T)
            _copy(a.v_proj.weight, lp["w_v"].T)
            _copy(a.o_proj.weight, lp["w_o"].T)


@pytest.mark.parametrize("length", [5, 16, 37])
def test_the_reference_gives_transformers_logits(length):
    """Lengths that end inside a scan block of `transformers` (8 tokens),
    on its edge and after several: the recurrence and the published
    chunked form agree at 1e-4 on every position's logits."""
    rng = np.random.default_rng(36)
    params = _params(rng)
    hf = transformers.GraniteMoeHybridForCausalLM(_config()).float().eval()
    _into_hf(params, hf)
    tokens = rng.integers(0, VOCAB, length)
    with torch.no_grad():
        want = hf(torch.from_numpy(tokens)[None]).logits[0].numpy()
    got = np.asarray(ref.next_token_logits(params, tokens.tolist(), SHAPE,
                                           length))
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_the_share_of_the_reference_adds_up():
    """Experts 0-3 held and experts 4-7 held, the shared expert counted
    once: the two partial layers sum to the uncut one."""
    rng = np.random.default_rng(7)
    lp = _params(rng)["layers"][0]
    x = rng.standard_normal((11, HIDDEN)).astype(np.float32)
    whole = np.asarray(ref.feed_forward(lp, x, SHAPE))
    shared = np.asarray(ref._gated(x, lp["shared_gate_up"],
                                   lp["shared_down"]))
    parts = []
    for first in (0, EXPERTS // 2):
        held = slice(first, first + EXPERTS // 2)
        parts.append(np.asarray(ref.feed_forward(
            dict(lp, experts_gate_up=lp["experts_gate_up"][held],
                 experts_down=lp["experts_down"][held]), x,
            dict(SHAPE, experts_held=(first, EXPERTS // 2)))))
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole,
                               atol=1e-5)
    assert np.abs(parts[0] - shared).max() > 1e-3
