"""Trinity-Mini's forward pass (`afmoe`), plainly: the block of ISSUE 34
part 1 in float32 `jax.numpy` at "highest" matmul precision.  Dense
causal attention over the whole sequence with the window as a MASK,
every token through each of its chosen experts by a dense mask over all
experts, no cache, no paging, no kernel, no batching.  Nothing of
`paddle_tpu/generation/` is imported.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    h_0    = sqrt(d) * E[token]                   (mup: the embedding alone)
    h'     = h  + rms(Attn_l(rms(h;  g1)); g2)    four norms a layer
    h''    = h' + rms(FFN_l (rms(h'; g3)); g4)
    logits = rms(h_L; g_f) W_head

    Attn_l:  q = x W_q -> H heads of D;  k = x W_k, v = x W_v -> n heads of D
             gate = x W_gate;  q = rms(q; g_q), k = rms(k; g_k) a head
             sliding layer: q, k rotated, pairs (i, i + D/2) at
                            theta^(-2i/D); the query at p sees keys
                            p - window + 1 .. p
             full layer:    no rotation; the query at p sees keys 0 .. p
             head h reads KV head h // (H / n); score / sqrt(D)
             out = (concat_h softmax(score_h) v * sigmoid(gate)) W_o
    FFN_l:   dense layers (silu(x W_g) * x W_u) W_d; the others
             s = sigmoid(x W_r); the k experts of largest s + b;
             w_i = scale * s_i / sum_chosen s; sum_i w_i Expert_i(x) + Shared(x)

`params` is the served model's own pytree (`GQAWindowMoELM.
decode_params()`): {"embed", "layers": [{"norm1", "w_q", "w_k", "w_v",
"w_gate", "norm_q", "norm_k", "w_o", "norm2", "norm3", "norm4", and
either "w_gate_up", "w_down" or "w_router", "router_bias",
"experts_gate_up" [E, d, 2f], "experts_down" [E, f, d],
"shared_gate_up", "shared_down"}], "norm_f", "head"}.  The weights may be
bf16: a layer's (an expert's, a vocabulary block's) are upcast as they
are used, so the pass fits beside the served model, and long sequences
go through attention, the experts and the head in blocks.  `shape`
carries what the arrays cannot say (the served model's arguments):
num_heads, num_kv_heads, head_dim, num_experts_per_tok,
routed_scaling_factor, sliding_window, layer_types, rope_theta,
rms_norm_eps, mup_enabled.

What `config.json` does not say and this pass takes as the served model
does is listed under `assumed` in the configuration's file.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
KV_HEADS = 1          # KV heads a block: their q, k, v of every position
ATTN_ROWS = 128       # query rows a block: [heads, rows, T] scores
FFN_ROWS = 2048       # tokens a block through an MLP
VOCAB_COLS = 16384    # columns of the head a block


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """x [T, heads, D], pairs (i, i + D/2) turned by pos * theta**(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (positions.astype(F32)[:, None] * inv)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _gated(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(F32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "lo", "hi", "rotated", "theta", "eps"))
def _qkv(lp, x, positions, *, heads, kv_heads, lo, hi, rotated, theta, eps):
    """Queries [T, (hi - lo) * rep, D], keys and values [T, hi - lo, D]
    of KV heads [lo, hi) and of the query heads that read them, and
    those query heads' gate [T, (hi - lo) * rep * D]."""
    t = x.shape[0]
    rep = heads // kv_heads
    h = _rms(x, lp["norm1"], eps)
    d = lp["w_k"].shape[1] // kv_heads
    w_q = lp["w_q"].reshape(-1, heads, d)[:, lo * rep:hi * rep]
    w_k = lp["w_k"].reshape(-1, kv_heads, d)[:, lo:hi]
    w_v = lp["w_v"].reshape(-1, kv_heads, d)[:, lo:hi]
    w_g = lp["w_gate"].reshape(-1, heads, d)[:, lo * rep:hi * rep]
    q = _rms(jnp.einsum("tc,chd->thd", h, w_q.astype(F32)), lp["norm_q"], eps)
    k = _rms(jnp.einsum("tc,chd->thd", h, w_k.astype(F32)), lp["norm_k"], eps)
    v = jnp.einsum("tc,chd->thd", h, w_v.astype(F32))
    gate = jnp.einsum("tc,chd->thd", h, w_g.astype(F32)).reshape(t, -1)
    if rotated:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    return q, k, v, gate


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, q_pos, *, window):
    """Dense causal attention of a block of query rows (at positions
    q_pos) over all keys; q [rows, n * rep, D] against k, v [T, n, D],
    query head j reading KV head j // rep.  `window`: the keys a query
    sees counting its own, 0 for all."""
    rows, n = q.shape[0], k.shape[1]
    qg = q.reshape(rows, n, -1, q.shape[-1])
    sc = jnp.einsum("qgrd,kgd->grqk", qg, k) / jnp.sqrt(F32(q.shape[-1]))
    key = jnp.arange(k.shape[0])[None, :]
    seen = key <= q_pos[:, None]
    if window:
        seen = seen & (key > q_pos[:, None] - window)
    w = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", w, v).reshape(rows, -1)


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
def _norm(x, gain, eps):
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
    """The feed-forward half before its output norm; `margins`, a list,
    gains an expert layer's `router_margin`."""
    h = _norm(x, lp["norm3"], shape["rms_norm_eps"])
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


def _sliding(kind):
    return kind in ("sliding_attention", "window")


def hidden_states(params, tokens, shape, margins=None):
    """h_L [T, d] of the whole sequence.  `margins`, a list, gains one
    [T] array of `router_margin` for each expert layer, in order."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    x = params["embed"][tokens].astype(F32)
    if shape.get("mup_enabled", True):
        x = x * jnp.sqrt(F32(x.shape[-1]))
    heads, kv_heads = int(shape["num_heads"]), int(shape["num_kv_heads"])
    rep = heads // kv_heads
    eps = float(shape["rms_norm_eps"])
    kinds = shape.get("layer_types") or [
        "full_attention" if li % 4 == 3 else "sliding_attention"
        for li in range(len(params["layers"]))]
    for lp, kind in zip(params["layers"], kinds):
        window = int(shape["sliding_window"]) if _sliding(kind) else 0
        w_o = lp["w_o"].reshape(heads, -1, x.shape[-1])
        attn = jnp.zeros_like(x)
        for g0, g1 in _blocks(kv_heads, KV_HEADS):
            q, k, v, gate = _qkv(
                lp, x, positions, heads=heads, kv_heads=kv_heads, lo=g0,
                hi=g1, rotated=_sliding(kind),
                theta=float(shape["rope_theta"]), eps=eps)
            o = jnp.concatenate([
                _attend(q[lo:hi], k, v, positions[lo:hi], window=window)
                for lo, hi in _blocks(t, ATTN_ROWS)])
            attn = attn + (o * jax.nn.sigmoid(gate)) @ w_o[
                g0 * rep:g1 * rep].reshape(o.shape[-1], -1).astype(F32)
        x = x + _norm(attn, lp["norm2"], eps)
        blocks = [] if margins is not None else None
        y = jnp.concatenate([_ffn(lp, x[lo:hi], shape, blocks)
                             for lo, hi in _blocks(t, FFN_ROWS)])
        x = x + _norm(y, lp["norm4"], eps)
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
