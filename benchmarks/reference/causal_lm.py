"""The serving model's forward pass, plainly: token + learned position
embeddings, pre-LN blocks of full multi-head causal attention and a ReLU
MLP, final LN, untied head — the block of OPT as `TinyCausalLM` states it
(no bias on q/k/v/out, no position offset).  float32 `jax.numpy` at
"highest" matmul precision, dense causal attention over the whole
sequence, no cache, no paging, no batching.

`params` is the pytree `TinyCausalLM.decode_params()` hands the engine's
own executables: {"tok_emb", "pos_emb", "blocks": [{"ln1_s", "ln1_b",
"wq", "wk", "wv", "wo", "ln2_s", "ln2_b", "w1", "b1", "w2", "b2"}],
"ln_f_s", "ln_f_b", "head"}.
"""
import jax
import jax.numpy as jnp


def _ln(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _forward(p, tokens, num_heads, last):
    t = tokens.shape[0]
    x = p["tok_emb"][tokens] + p["pos_emb"][:t]
    d = x.shape[-1] // num_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for blk in p["blocks"]:
        h = _ln(x, blk["ln1_s"], blk["ln1_b"])
        q, k, v = ((h @ blk[w]).reshape(t, num_heads, d)
                   for w in ("wq", "wk", "wv"))
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", w, v).reshape(t, -1) @ blk["wo"]
        h = _ln(x, blk["ln2_s"], blk["ln2_b"])
        x = x + jnp.maximum(h @ blk["w1"] + blk["b1"], 0.0) @ blk["w2"] \
            + blk["b2"]
    return _ln(x[t - last:], p["ln_f_s"], p["ln_f_b"]) @ p["head"]


def next_token_logits(params, tokens, num_heads, last):
    """Logits [last, V] that follow each of the final `last` positions of
    `tokens`: row j is the distribution of the token after position
    len(tokens) - last + j, given everything up to it.  One dense pass:
    causality makes these the logits of `last` separate prefills."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(_forward, static_argnums=(2, 3))
        return fn(params, jnp.asarray(tokens, jnp.int32), int(num_heads),
                  int(last))
