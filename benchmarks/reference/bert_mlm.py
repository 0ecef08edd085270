"""BERT pretraining loss, plainly: post-LN encoder, exact gelu, masked-LM
head tied to the word embeddings, mean cross entropy over all positions
with ignored labels (-100) counting 0 — what `BertForPretraining.loss(ids,
mlm_labels)` computes in evaluation mode.  float32 `jax.numpy`, no dropout,
no kernels, one sequence at a time.

`params` maps the program's parameter names to arrays; nothing else of
the program is used.  Departures from the published model that the
program makes and this follows: layer-norm epsilon 1e-5, segment id 0
everywhere, no next-sentence term.
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
    """Summed negative log-likelihood of one sequence's labelled
    positions.  ids, labels: [S]."""
    s = ids.shape[0]
    emb = "bert.embeddings."
    x = (p[emb + "word_embeddings.weight"][ids]
         + p[emb + "position_embeddings.weight"][:s]
         + p[emb + "token_type_embeddings.weight"][0])
    x = _ln(x, p, emb + "layer_norm")
    d = x.shape[-1] // num_heads
    for i in range(num_layers):
        lay = f"bert.encoder.layers.{i}."
        q, k, v = (_linear(x, p, lay + f"self_attn.{n}_proj")
                   .reshape(s, num_heads, d) for n in "qkv")
        w = jax.nn.softmax(
            jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d)), axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, -1)
        x = _ln(x + _linear(attn, p, lay + "self_attn.out_proj"),
                p, lay + "norm1")
        h = jax.nn.gelu(_linear(x, p, lay + "linear1"), approximate=False)
        x = _ln(x + _linear(h, p, lay + "linear2"), p, lay + "norm2")
    h = _ln(jax.nn.gelu(_linear(x, p, "mlm_transform"), approximate=False),
            p, "mlm_norm")
    logp = jax.nn.log_softmax(h @ p[emb + "word_embeddings.weight"].T, -1)
    picked = jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[:, None], axis=-1)[:, 0]
    return -jnp.where(labels == -100, 0.0, picked).sum()


def loss(params, ids, labels, model_args):
    """Mean loss of a batch, ids and labels [B, S] int32."""
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda p, i, l: _sequence_nll(
            p, i, l, int(model_args["num_layers"]),
            int(model_args["num_heads"])))
        total = sum(float(one(params, jnp.asarray(i), jnp.asarray(l)))
                    for i, l in zip(ids, labels))
    return total / labels.size
