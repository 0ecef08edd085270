"""Granite-4.0-H's forward pass (`granitemoehybrid`), plainly: the block
of ISSUE 36 part 1 in float32 `jax.numpy` at "highest" matmul precision.
The state-space layer is a TOKEN-BY-TOKEN RECURRENCE under `lax.scan`
(no blocks, no chunked form: independent of how the served model scans
a prefill chunk), attention is dense and causal over the whole sequence,
every token goes through each of its chosen experts by a dense mask, no
cache, no paging, no kernel, no batching.  Nothing of
`paddle_tpu/generation/` is imported.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    h_0     = embedding_multiplier * E[token]          E tied to the head
    h'      = h  + residual_multiplier * Mixer_l(rms(h;  g1))
    h''     = h' + residual_multiplier * (Experts_l(x) + Shared_l(x)),
              x = rms(h'; g2)
    logits  = rms(h_L; g_f) E^T / logits_scaling

    Attention: q = x W_q -> H heads of D;  k, v = x W_k, x W_v -> n heads
               of D; no bias, NO rotation and no other position signal;
               head h reads KV head h // (H / n);
               o_h = softmax(q_h . k * attention_multiplier, causal) v;
               out = concat_h(o_h) W_o
    State-space (Mamba-2, one group):
               [z | xBC | dt] = x W_in      widths d_i | d_i + 2 N | heads
               xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-(K-1)+j}), zeros
                       before the sequence's start (K = d_conv taps)
               [x | B | C] = xBC            x -> [heads, P]; B, C [N]
               dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t * A),
                      A = -exp(A_log)       a scalar a head
               S_t  = a_t * S_{t-1} + dt_t * x_t (outer) B_t,  S_{-1} = 0
               y_t  = S_t C_t + D * x_t
               out  = rms(y * silu(z); g_n over all d_i lanes) W_out
    Experts:   logits = x W_r; the k largest chosen; w = softmax over the
               chosen k logits; Experts(x) = sum_chosen w_i Expert_i(x),
               Expert_i(x) = (silu(x W_g,i) * x W_u,i) W_d,i
    Shared:    the same gated MLP at the shared width, every token
    The share: `experts_held` = (first, count): the sum runs over the
               chosen experts in [first, first + count) alone; the others
               add nothing (their chip is absent), and that partial sum
               goes on to the next layer.

`params` is the served model's own pytree (`HybridSSMMoELM.
decode_params()`): {"embed", "layers": [{"norm1", "norm2", "w_router"
[d, router width], "experts_gate_up" [held, d, 2f], "experts_down"
[held, f, d], "shared_gate_up", "shared_down", and either "w_q", "w_k",
"w_v", "w_o" or "w_in", "conv_w" [K, C], "conv_b", "dt_bias", "A_log",
"D", "norm_ssm", "w_out"}], "norm_f"}.  The weights may be bf16: a
layer's (an expert's, a vocabulary block's) are upcast as they are used.
`shape` carries what the arrays cannot say (the served model's
arguments): num_heads, num_kv_heads, head_dim, num_experts_per_tok,
experts_held, layer_types, mamba_n_heads, mamba_d_head, mamba_d_state,
the four multipliers, rms_norm_eps, and state_dtype (float32 where the
key is absent).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
KV_HEADS = 1          # KV heads a block: their q, k, v of every position
ATTN_ROWS = 128       # query rows a block: [heads, rows, T] scores
FFN_ROWS = 2048       # tokens a block through an MLP
VOCAB_ROWS = 16384    # rows of the tied embedding a block of the head


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _gated(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(F32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(F32)


def _state_kind(kind):
    return kind in ("mamba", "state")


# ------------------------------ attention ---------------------------
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "lo", "hi"))
def _qkv(lp, h, *, heads, kv_heads, lo, hi):
    """Queries [T, (hi - lo) * rep, D], keys and values [T, hi - lo, D]
    of KV heads [lo, hi) and of the query heads that read them."""
    rep = heads // kv_heads
    d = lp["w_k"].shape[1] // kv_heads
    w_q = lp["w_q"].reshape(-1, heads, d)[:, lo * rep:hi * rep]
    w_k = lp["w_k"].reshape(-1, kv_heads, d)[:, lo:hi]
    w_v = lp["w_v"].reshape(-1, kv_heads, d)[:, lo:hi]
    return (jnp.einsum("tc,chd->thd", h, w_q.astype(F32)),
            jnp.einsum("tc,chd->thd", h, w_k.astype(F32)),
            jnp.einsum("tc,chd->thd", h, w_v.astype(F32)))


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q, k, v, q_pos, *, scale):
    """Dense causal attention of a block of query rows (at positions
    q_pos) over all keys; q [rows, n * rep, D] against k, v [T, n, D],
    query head j reading KV head j // rep."""
    rows, n = q.shape[0], k.shape[1]
    qg = q.reshape(rows, n, -1, q.shape[-1])
    sc = jnp.einsum("qgrd,kgd->grqk", qg, k) * scale
    seen = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    w = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", w, v).reshape(rows, -1)


def attention(lp, h, shape):
    """The attention mixer over normed rows h [T, d]."""
    t = h.shape[0]
    heads, kv_heads = int(shape["num_heads"]), int(shape["num_kv_heads"])
    rep = heads // kv_heads
    positions = jnp.arange(t, dtype=jnp.int32)
    w_o = lp["w_o"].reshape(heads, -1, h.shape[-1])
    out = jnp.zeros_like(h)
    for g0, g1 in _blocks(kv_heads, KV_HEADS):
        q, k, v = _qkv(lp, h, heads=heads, kv_heads=kv_heads, lo=g0, hi=g1)
        o = jnp.concatenate([
            _attend(q[lo:hi], k, v, positions[lo:hi],
                    scale=float(shape["attention_multiplier"]))
            for lo, hi in _blocks(t, ATTN_ROWS)])
        out = out + o @ w_o[g0 * rep:g1 * rep].reshape(
            o.shape[-1], -1).astype(F32)
    return out


# ----------------------------- state space --------------------------
@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "d_state",
                                             "state_dtype"))
def recurrence(lp, h, *, heads, head_dim, d_state, state_dtype="float32"):
    """The state-space mixer over normed rows h [T, d] of ONE sequence
    from its start.  Returns (y [T, heads * head_dim] before the gated
    norm, z [T, heads * head_dim], the state S_{T-1} [heads, head_dim,
    d_state], the last d_conv - 1 rows of xBC before the convolution
    [d_conv - 1, C], zeros where the sequence is shorter).
    `state_dtype`: the state is rounded to it after every token, as a
    system that KEEPS it so would (the precision control's "bfloat16";
    the configurations state float32, which rounds nothing)."""
    t = h.shape[0]
    d_inner = heads * head_dim
    zxbcdt = h @ lp["w_in"].astype(F32)
    z = zxbcdt[:, :d_inner]
    xbc_in = zxbcdt[:, d_inner:2 * d_inner + 2 * d_state]
    dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * d_state:]
                         + lp["dt_bias"].astype(F32))          # [T, heads]
    taps = lp["conv_w"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc_in.shape[1]), F32), xbc_in])
    conv_w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(lp["conv_b"].astype(F32) + sum(
        conv_w[j] * padded[j:j + t] for j in range(taps)))
    x = xbc[:, :d_inner].reshape(t, heads, head_dim)
    b = xbc[:, d_inner:d_inner + d_state]
    c = xbc[:, d_inner + d_state:]
    a = -jnp.exp(lp["A_log"].astype(F32))                       # [heads]
    d_skip = lp["D"].astype(F32)
    kept = jnp.finfo(state_dtype)

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        # not a pair of converts: XLA:TPU removes those (excess
        # precision is allowed) and the control would round nothing
        state = jax.lax.reduce_precision(state, kept.nexp, kept.nmant)
        y_t = jnp.einsum("hpn,n->hp", state, c_t) + d_skip[:, None] * x_t
        return state, y_t

    state, y = jax.lax.scan(
        token, jnp.zeros((heads, head_dim, d_state), F32), (x, b, c, dt))
    return y.reshape(t, d_inner), z, state, padded[t:]


@functools.partial(jax.jit, static_argnames=("eps",))
def _gated_norm_out(lp, y, z, eps):
    """The gate BEFORE the norm, the norm over all lanes, then W_out."""
    return _rms(y * jax.nn.silu(z), lp["norm_ssm"], eps) @ lp[
        "w_out"].astype(F32)


def state_space(lp, h, shape):
    y, z, _, _ = recurrence(
        lp, h, heads=int(shape["mamba_n_heads"]),
        head_dim=int(shape["mamba_d_head"]),
        d_state=int(shape["mamba_d_state"]),
        state_dtype=str(shape.get("state_dtype", "float32")))
    return _gated_norm_out(lp, y, z, float(shape["rms_norm_eps"]))


def first_layer_state(params, tokens, shape):
    """The state S [heads, P, N] the FIRST layer holds after `tokens`,
    where it is a state-space layer (its input is the embedding's norm
    alone, so nothing behind it weighs); None where it is not."""
    if not _state_kind(shape["layer_types"][0]):
        return None
    lp = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            F32) * float(shape["embedding_multiplier"])
        return recurrence(
            lp, _norm(x, lp["norm1"], float(shape["rms_norm_eps"])),
            heads=int(shape["mamba_n_heads"]),
            head_dim=int(shape["mamba_d_head"]),
            d_state=int(shape["mamba_d_state"]),
            state_dtype=str(shape.get("state_dtype", "float32")))[2]


# ------------------------------- experts ----------------------------
@functools.partial(jax.jit, static_argnames=("top_k",))
def route(x, w_router, *, top_k):
    """(experts [T, k], weights [T, k]) of normed rows x: the k largest
    logits, a softmax over those k."""
    logits = x @ w_router.astype(F32)
    experts = jnp.argsort(-logits, axis=-1, stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(logits, experts, axis=1)
    return experts, jax.nn.softmax(chosen, axis=-1)


@functools.partial(jax.jit, static_argnames=("top_k",))
def router_margin(x, w_router, *, top_k):
    """[T]: how far the last chosen expert's logit stands above the
    first one left out: the noise that would change a row's choice."""
    top = jax.lax.top_k(x @ w_router.astype(F32), top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k]


@jax.jit
def _expert(x, share, w_gate_up, w_down):
    """One expert over every row, weighed by the row's share of it
    (zero where the row did not choose it)."""
    return share[:, None] * _gated(x, w_gate_up, w_down)


_gated_jit = jax.jit(_gated)


def feed_forward(lp, x, shape, margins=None):
    """Experts(x) + Shared(x) over normed rows x; `margins`, a list,
    gains the layer's `router_margin`.  Of the chosen experts only those
    `shape["experts_held"]` = (first, count) names are summed (all of
    them where the key is absent): `lp["experts_gate_up"][i]` is expert
    first + i."""
    top_k = int(shape["num_experts_per_tok"])
    experts, weights = route(x, lp["w_router"], top_k=top_k)
    if margins is not None:
        margins.append(router_margin(x, lp["w_router"], top_k=top_k))
    first = int((shape.get("experts_held") or (0, 0))[0])
    y = _gated_jit(x, lp["shared_gate_up"], lp["shared_down"])
    for i in range(lp["experts_gate_up"].shape[0]):
        share = jnp.sum(jnp.where(experts == first + i, weights, 0.0),
                        axis=-1)
        y = y + _expert(x, share, lp["experts_gate_up"][i],
                        lp["experts_down"][i])
    return y


# ------------------------------ the pass ----------------------------
@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, gain, eps):
    return _rms(x, gain, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, embed_rows, eps):
    return _rms(x, gain, eps) @ embed_rows.astype(F32).T


def _blocks(n, size):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def hidden_states(params, tokens, shape, margins=None):
    """h_L [T, d] of the whole sequence.  `margins`, a list, gains one
    [T] array of `router_margin` for each layer, in order."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    eps = float(shape["rms_norm_eps"])
    residual = float(shape["residual_multiplier"])
    x = params["embed"][tokens].astype(F32) * float(
        shape["embedding_multiplier"])
    for lp, kind in zip(params["layers"], shape["layer_types"]):
        h = _norm(x, lp["norm1"], eps)
        mixed = (state_space if _state_kind(kind) else attention)(
            lp, h, shape)
        x = x + residual * mixed
        h = _norm(x, lp["norm2"], eps)
        blocks = [] if margins is not None else None
        y = jnp.concatenate([feed_forward(lp, h[lo:hi], shape, blocks)
                             for lo, hi in _blocks(t, FFN_ROWS)])
        x = x + residual * y
        if blocks:
            margins.append(jnp.concatenate(blocks))
    return x


def next_token_logits(params, tokens, shape, last, margins=None):
    """Logits [last, V] that follow each of the final `last` positions
    of `tokens` (`causal_lm.next_token_logits`'s contract), the tied head
    in blocks of the vocabulary.  `margins`: see `hidden_states`; of
    every position, not of the last ones alone."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, shape,
                          margins)[len(tokens) - int(last):]
        vocab = params["embed"].shape[0]
        logits = jnp.concatenate(
            [_head(x, params["norm_f"], params["embed"][lo:hi],
                   float(shape["rms_norm_eps"]))
             for lo, hi in _blocks(vocab, VOCAB_ROWS)], axis=1)
        return logits / float(shape["logits_scaling"])
