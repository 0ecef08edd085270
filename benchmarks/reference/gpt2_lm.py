"""GPT-2 next-token loss, plainly: learned positions, pre-LN blocks with
causal attention and an exact-gelu MLP, final LN, head tied to the token
embedding, mean cross entropy over every position — what
`GPTForPretraining.loss(ids, labels)` computes in evaluation mode on one
device.  float32 `jax.numpy`, no dropout, no kernels, no sharding, one
sequence at a time.

`params` maps the program's parameter names to arrays.  The program's qkv
projection is head-major: its 3*hidden columns are grouped per head as
(q, k, v) triples, so that a tensor-parallel column shard holds whole
heads; this follows it.  Departure from the published model: exact gelu
where GPT-2 uses the tanh approximation ("gelu_new").
"""
import jax
import jax.numpy as jnp


def _ln(x, p, prefix, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p[prefix + ".weight"]
            + p[prefix + ".bias"])


def _linear(x, p, prefix):
    return x @ p[prefix + ".weight"] + p[prefix + ".bias"]


def _sequence_nll(p, ids, labels, num_layers, num_heads):
    s = ids.shape[0]
    x = p["gpt.wte.weight"][ids] + p["gpt.wpe.weight"][:s]
    d = x.shape[-1] // num_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(num_layers):
        blk = f"gpt.blocks.{i}."
        qkv = _linear(_ln(x, p, blk + "ln1"), p, blk + "attn.qkv")
        qkv = qkv.reshape(s, num_heads, 3, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
        w = jax.nn.softmax(jnp.where(causal, sc, -1e9), axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, -1)
        x = x + _linear(attn, p, blk + "attn.out_proj")
        h = jax.nn.gelu(_linear(_ln(x, p, blk + "ln2"), p,
                                blk + "mlp.fc_in"), approximate=False)
        x = x + _linear(h, p, blk + "mlp.fc_out")
    logp = jax.nn.log_softmax(
        _ln(x, p, "gpt.ln_f") @ p["gpt.wte.weight"].T, -1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def loss(params, ids, labels, model_args):
    """Mean loss of a batch, ids and labels [B, S] int32."""
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda p, i, l: _sequence_nll(
            p, i, l, int(model_args["num_layers"]),
            int(model_args["num_heads"])))
        total = sum(float(one(params, jnp.asarray(i), jnp.asarray(l)))
                    for i, l in zip(ids, labels))
    return total / labels.size
