"""GLM-4.7-Flash's forward pass (`glm4_moe_lite`), plainly: the block of
ISSUE 28 part 1 in float32 `jax.numpy` at "highest" matmul precision.
Latent attention in its EXPANDED form (every head's keys and values
built from the compressed row; dense causal attention over the whole
sequence), every token through each of its chosen experts by a dense
mask over all experts, no cache, no paging, no kernel, no batching.
Nothing of `paddle_tpu/generation/` is imported.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    h_0 = E[token];  h' = h + Attn(rms(h; g1));  h'' = h' + FFN(rms(h'; g2))
    logits = rms(h_L; g_f) W_head

    Attn:  c_q = rms(x W_qa; g_q);  q = c_q W_qb -> [q_nope | q_rope] a head
           [c | r] = x W_kva;  c = rms(c; g_kv);  k_rope = RoPE(r)
           [k_nope_h | v_h] = c W_kvb a head;  q_rope_h = RoPE(q_rope_h)
           score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope)
           out = concat_h(softmax(score_h) v_h) W_o
    FFN:   dense layers (silu(x W_g) * x W_u) W_d; the others
           s = sigmoid(x W_r); the k experts of largest s + b;
           w_i = scaling * s_i / sum_chosen s; sum_i w_i Expert_i(x) + Shared(x)

`params` is the served model's own pytree (`LatentMoELM.decode_params()`):
{"embed", "layers": [{"norm1", "w_qa", "norm_q", "w_qb", "w_kva",
"norm_kv", "w_kvb", "w_o", "norm2", and either "w_gate_up", "w_down" or
"w_router", "router_bias", "experts_gate_up" [E, d, 2f], "experts_down"
[E, f, d], "shared_gate_up", "shared_down"}], "norm_f", "head"}; a
`*_gate_up` holds the gate and the up projection side by side.  The
weights may be bf16: a layer's (an expert's, a vocabulary block's) are
upcast as they are used, so the pass fits beside the served model, and
long sequences go through attention, the experts and the head in
blocks.  `shape` carries what the arrays cannot say: num_heads,
qk_nope_head_dim, qk_rope_head_dim, num_experts_per_tok,
routed_scaling_factor, rope_theta, rms_norm_eps.

Departures from the release, as the served model: the rotation pairs
lanes (2i, 2i + 1) (not in `config.json`; DeepSeek-V3's, up to a fixed
permutation of columns); the next-token-prediction layer is not run.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEADS = 5             # heads a block: their q, k, v of every position
ATTN_ROWS = 256       # query rows a block: [HEADS, rows, T] scores
FFN_ROWS = 2048       # tokens a block through an MLP
VOCAB_COLS = 16384    # columns of the head a block


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """x [T, ..., R], pairs (2i, 2i + 1) turned by pos * theta**(-2i/R)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = positions.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(
                          x.shape)


def _gated(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(F32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "lo", "hi", "nope",
                                             "rope", "theta", "eps"))
def _qkv(lp, x, positions, *, heads, lo, hi, nope, rope, theta, eps):
    """Expanded queries, keys and values of heads [lo, hi) at every
    position."""
    t, n = x.shape[0], hi - lo
    h = _rms(x, lp["norm1"], eps)
    cq = _rms(h @ lp["w_qa"].astype(F32), lp["norm_q"], eps)
    w_qb = lp["w_qb"].reshape(-1, heads, nope + rope)[:, lo:hi]
    q = jnp.einsum("tr,rhd->thd", cq, w_qb.astype(F32))
    kva = h @ lp["w_kva"].astype(F32)
    rank = kva.shape[-1] - rope
    c = _rms(kva[:, :rank], lp["norm_kv"], eps)
    k_rope = _rope(kva[:, rank:], positions, theta)
    w_kvb = lp["w_kvb"].reshape(rank, heads, -1)[:, lo:hi]
    kv = jnp.einsum("tc,chd->thd", c, w_kvb.astype(F32))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                              theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], (t, n, rope))], -1)
    return q, k, kv[..., nope:]


@jax.jit
def _attend(q, k, v, q_pos):
    """Dense causal attention of a block of query rows (at positions
    q_pos) over all keys."""
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    seen = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("top_k", "scaling"))
def route(x, w_router, bias, *, top_k, scaling):
    """(experts [T, k], weights [T, k]) of normed rows x: the bias
    chooses, the scores weigh."""
    s = jax.nn.sigmoid(x @ w_router.astype(F32))
    experts = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(s, experts, axis=1)
    return experts, scaling * chosen / chosen.sum(-1, keepdims=True)


@jax.jit
def _expert(x, share, w_gate_up, w_down):
    """One expert over every row, weighed by the row's share of it
    (zero where the row did not choose it)."""
    return share[:, None] * _gated(x, w_gate_up, w_down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn_in(x, gain, eps):
    return _rms(x, gain, eps)


_gated_jit = jax.jit(_gated)


@functools.partial(jax.jit, static_argnames=("top_k",))
def router_margin(x, w_router, bias, *, top_k):
    """[T]: how far the last chosen expert's `s + b` stands above the
    first one left out: the noise in `s + b` that would change a row's
    choice."""
    top = jax.lax.top_k(jax.nn.sigmoid(x @ w_router.astype(F32)) + bias,
                        top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k]


def _ffn(lp, x, shape, margins=None):
    """`margins`, a list, gains an expert layer's `router_margin`."""
    h = _ffn_in(x, lp["norm2"], shape["rms_norm_eps"])
    if "w_router" not in lp:
        return _gated_jit(h, lp["w_gate_up"], lp["w_down"])
    top_k = int(shape["num_experts_per_tok"])
    experts, weights = route(
        h, lp["w_router"], lp["router_bias"], top_k=top_k,
        scaling=float(shape["routed_scaling_factor"]))
    if margins is not None:
        margins.append(router_margin(h, lp["w_router"], lp["router_bias"],
                                     top_k=top_k))
    y = _gated_jit(h, lp["shared_gate_up"], lp["shared_down"])
    for e in range(lp["experts_gate_up"].shape[0]):
        share = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        y = y + _expert(h, share, lp["experts_gate_up"][e],
                        lp["experts_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, head, eps):
    return _rms(x, gain, eps) @ head.astype(F32)


def _blocks(n, size):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def hidden_states(params, tokens, shape, margins=None):
    """h_L [T, d] of the whole sequence.  `margins`, a list, gains one
    [T] array of `router_margin` for each expert layer, in order."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    heads = int(shape["num_heads"])
    kw = dict(heads=heads, nope=int(shape["qk_nope_head_dim"]),
              rope=int(shape["qk_rope_head_dim"]),
              theta=float(shape["rope_theta"]),
              eps=float(shape["rms_norm_eps"]))
    for lp in params["layers"]:
        w_o = lp["w_o"].reshape(heads, -1, x.shape[-1])
        attn = jnp.zeros_like(x)
        for h0, h1 in _blocks(heads, HEADS):
            q, k, v = _qkv(lp, x, positions, lo=h0, hi=h1, **kw)
            o = jnp.concatenate([_attend(q[lo:hi], k, v, positions[lo:hi])
                                 for lo, hi in _blocks(t, ATTN_ROWS)])
            attn = attn + o @ w_o[h0:h1].reshape(o.shape[-1], -1).astype(F32)
        x = x + attn
        blocks = [] if margins is not None else None
        x = x + jnp.concatenate([_ffn(lp, x[lo:hi], shape, blocks)
                                 for lo, hi in _blocks(t, FFN_ROWS)])
        if blocks:
            margins.append(jnp.concatenate(blocks))
    return x


def next_token_logits(params, tokens, shape, last, margins=None):
    """Logits [last, V] that follow each of the final `last` positions
    of `tokens` (`causal_lm.next_token_logits`'s contract), the head in
    blocks of the vocabulary.  `margins`: see `hidden_states`; of every
    position, not of the last ones alone."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, shape,
                          margins)[len(tokens) - int(last):]
        vocab = params["head"].shape[1]
        return jnp.concatenate(
            [_head(x, params["norm_f"], params["head"][:, lo:hi],
                   float(shape["rms_norm_eps"]))
             for lo, hi in _blocks(vocab, VOCAB_COLS)], axis=1)
