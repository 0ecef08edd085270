"""Hy4-preview's forward pass (`hy_v4`), plainly: float32 `jax.numpy` at
"highest" matmul precision, no cache, no paging, no kernel, no batching.
Latent attention in its EXPANDED form (every head's keys and values
built from the compressed row, dense masked attention), the indexer's
scores of every (query, key) pair and an exact top-k, every token
through each of its chosen experts by a mask over the experts held.
Nothing of `paddle_tpu/generation/` is imported.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    X_0 = [E[token]] x n;  logits = rms(sum_i X_i; g_f) W_head
    each sublayer F (attention, then the feed-forward half):
        u = rms(vec X);  a = alpha * (u Phi) + b
        h_pre = sigmoid(a[:n]);  h_post = magnitude * sigmoid(a[n:2n])
        H_res = Sinkhorn(exp(a[2n:] as n x n))   (rows, then columns,
                                                  `rounds` times)
        X <- H_res X + h_post (x) F(sum_i h_pre_i X_i)

    Attn(x), h = rms(x; g1):
        c_q = rms(h W_qa; g_q);  q = c_q W_qb -> [q_nope | RoPE(q_rope)]
        [c | r] = h W_kva;  c = rms(c; g_kv);  k_rope = RoPE(r)
        [k_nope_h | v_h] = c W_kvb a head;  k_h = [k_nope_h | k_rope]
        S_t: all of 0..t while t < K, else the top K of
             I(t, s) = sum_j w_j relu(q^I_j . k^I(s)),  s <= t
             q^I = RoPE_64(c_q W_iq);  k^I = RoPE_64(LayerNorm(h W_ik))
             w = h W_iw / sqrt(heads_I * dim_I)
             (a `shared` layer takes the S_t of the `full` layer before)
        p_s = exp(z_s - m) / (exp(sink_h - m) + sum_{S_t} exp(z - m)),
             z_s = q_h . k_h(s) / sqrt(nope + rope)
        o_h = sum_s p_s v_h(s) * sigmoid(h W_g)_h;  out = concat_h o_h W_o
    FFN(x), h = rms(x; g2): dense layers swiglu(h W_gu) W_d, the others
        s = sigmoid(h W_r) over the router's width; the k largest s + b;
        w_i = scaling * s_i / sum_chosen s; sum over the chosen experts
        that are HELD of w_i Expert_i(h), + Shared(h);
        swiglu(g, u) = silu(min(g, L)) * clip(u, -L, L)

`params` is the served model's own pytree
(`SparseLatentMoELM.decode_params()`); `shape` its builder's
`model_args`.  The weights may be bf16: each is upcast as it is used.
Long sequences go through in blocks of rows, the residual streams held
on the host between a layer's sublayers (a 65,536-token document's four
streams are 6.4 GB in float32): first every position's latent rows and
index keys, then each block of rows through its selection, its
attention over the keys up to its last row, and its feed-forward half,
each of those one program a block (inside it `Q_ROWS` rows and `HEADS`
heads at a time, one after another).  Every block has one shape
whatever the sequence's length: the rows are padded with zeros to whole
blocks, and a block's keys run to its last row rounded up to a whole
`KEY_STEP` (or to the padded end), so that a pass compiles a score of
programs (rows past the sequence are computed and dropped; keys past it
are masked).  Beside the served model's 12.9 GB a block holds one head
block's expanded keys and values at a time.

Departures from the release, as the served model's: the rotation pairs
lanes (2i, 2i + 1); the multi-token-prediction layer is not run; the
points the configuration's `assumed` names.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 2048          # positions a block of the layer's passes
Q_ROWS = 128         # query rows at a time of attention or of the indexer
HEADS = 2            # heads at a time: their keys and values of every key
INDEX_HEADS = 4      # index heads at a time of the indexer's scores
KEY_STEP = 8192      # a block's keys run to its last row, rounded up to this
VOCAB_COLS = 16384   # columns of the head a block
INDEX_NORM_EPS = 1e-6


def _blocks(n, size):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def _part(lp, names):
    """The arrays of layer `lp` a jitted helper reads: a helper handed a
    whole layer would compile again for every layer of another kind."""
    return {name: lp[name] for name in names}


_LATENT = ("w_qa", "norm_q", "w_kva", "norm_kv")
_INDEX_KEY = ("w_ik", "ik_gain", "ik_bias")
_ATTENTION = ("w_qb", "w_gate", "w_kvb", "sink", "w_o")
_EXPERTS = ("w_router", "router_bias", "experts_gate_up", "experts_down",
            "shared_gate_up", "shared_down")


def _whole(n, size):
    return -(-n // size) * size


def _key_span(last, n_keys):
    """Keys a block whose last row sits at `last` reads: to the next
    whole KEY_STEP, within the padded rows."""
    return min(_whole(int(last) + 1, KEY_STEP), n_keys)


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


def _rope_leading(x, positions, lanes, theta):
    return jnp.concatenate([_rope(x[..., :lanes], positions, theta),
                            x[..., lanes:]], -1)


def _swiglu(x, w_gate_up, w_down, limit):
    gu = x @ w_gate_up.astype(F32)
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "sinkhorn_eps",
                                             "magnitude", "rounds"))
def _mix(x, hc, *, eps, sinkhorn_eps, magnitude, rounds):
    """(sum_i h_pre_i X_i [T, d], h_post [T, n], H_res [T, n, n]) of
    streams x [T, n, d]."""
    t, n, d = x.shape
    v = x.reshape(t, n * d)
    u = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    a = (u @ hc["phi"]) * hc["alpha"] + hc["bias"]
    m = jnp.exp(a[:, 2 * n:].reshape(t, n, n))
    for _ in range(rounds):
        m = m / (m.sum(2, keepdims=True) + sinkhorn_eps)
        m = m / (m.sum(1, keepdims=True) + sinkhorn_eps)
    h_pre = jax.nn.sigmoid(a[:, :n])
    return (jnp.einsum("tn,tnd->td", h_pre, x),
            magnitude * jax.nn.sigmoid(a[:, n:2 * n]), m)


@jax.jit
def _unmix(x, y, h_post, h_res):
    return jnp.einsum("tij,tjd->tid", h_res, x) + h_post[:, :, None] * y[
        :, None, :]


@functools.partial(jax.jit, static_argnames=("eps", "rank", "rope", "theta",
                                             "full"))
def _latents(lp, h, positions, *, eps, rank, rope, theta, full):
    """(c_q, c, k_rope, k^I or None) of normed rows h."""
    cq = _rms(h @ lp["w_qa"].astype(F32), lp["norm_q"], eps)
    kva = h @ lp["w_kva"].astype(F32)
    c = _rms(kva[:, :rank], lp["norm_kv"], eps)
    k_rope = _rope(kva[:, rank:], positions, theta)
    key = None
    if full:
        z = h @ lp["w_ik"].astype(F32)
        mean = z.mean(-1, keepdims=True)
        z = (z - mean) * jax.lax.rsqrt(((z - mean) ** 2).mean(
            -1, keepdims=True) + INDEX_NORM_EPS) * lp["ik_gain"] + lp[
                "ik_bias"]
        key = _rope_leading(z, positions, rope, theta)
    return cq, c, k_rope, key


def _by_rows(fn, *arrays):
    """fn over `Q_ROWS` rows of `arrays` at a time, one after another
    (`lax.map`), its outputs joined again along the rows."""
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape((x.shape[0] // Q_ROWS, Q_ROWS) + x.shape[1:])
        for x in arrays))
    return jax.tree_util.tree_map(
        lambda y: y.reshape((y.shape[0] * Q_ROWS,) + y.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("heads", "dim", "rope", "theta",
                                             "top_k"))
def _select_rows(lp, cq, h, keys, positions, *, heads, dim, rope, theta,
                 top_k):
    """(picked [R, K], gap [R]) of rows at `positions` over `keys` [N, D]:
    each row's top K of I(t, s), s <= t, and how far the K-th score
    stands above the (K+1)-th."""
    q = _rope_leading((cq @ lp["w_iq"].astype(F32)).reshape(
        cq.shape[0], heads, dim), positions, rope, theta)
    w = (h @ lp["w_iw"].astype(F32)) * (heads * dim) ** -0.5

    def rows(q, w, positions):
        scores = jnp.zeros((q.shape[0], keys.shape[0]), F32)
        for j0, j1 in _blocks(heads, INDEX_HEADS):
            s = jnp.einsum("rjd,nd->rjn", q[:, j0:j1], keys)
            scores = scores + jnp.einsum("rjn,rj->rn", jnp.maximum(s, 0.0),
                                         w[:, j0:j1])
        seen = jnp.arange(keys.shape[0])[None, :] <= positions[:, None]
        values, picked = jax.lax.top_k(
            jnp.where(seen, scores, -jnp.inf), top_k + 1)
        return picked[:, :top_k], values[:, top_k - 1] - values[:, top_k]

    return _by_rows(rows, q, w, positions)


def select(lp, cq, h, keys, positions, shape):
    """(S [R, K] int32 positions, gap [R]) of rows at `positions` (host
    numpy) over `keys`.  A row whose context holds at most K tokens
    selects all of them (S is 0..K-1, the causal mask drops what lies
    past the row) and its gap is inf."""
    k = int(shape["index_topk"])
    out = np.broadcast_to(np.arange(k, dtype=np.int32),
                          (len(positions), k)).copy()
    gap = np.full(len(positions), np.inf)
    long = np.asarray(positions) + 1 > k
    if keys.shape[0] > k and long.any():
        picked, g = _select_rows(
            _part(lp, ("w_iq", "w_iw")), cq, h, keys, jnp.asarray(positions),
            heads=int(shape["index_n_heads"]),
            dim=int(shape["index_head_dim"]),
            rope=int(shape["qk_rope_head_dim"]),
            theta=float(shape["rope_theta"]), top_k=k)
        out[long] = np.asarray(picked)[long]
        gap[long] = np.asarray(g)[long]
    return out, gap


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope",
                                             "v_dim", "theta"))
def _attention_rows(lp, h, cq, c, k_rope, picked, positions, *, heads,
                    nope, rope, v_dim, theta):
    """The attention sublayer's output [R, d] of rows h at `positions`
    over the latent rows c, k_rope [N, ...] of the keys they may read:
    each head's keys and values expanded from them, `HEADS` heads at a
    time, and each row attending over the keys it selected (`picked`
    [R, K]) at or before it, a sink logit a head in the denominator."""
    r, n = h.shape[0], c.shape[0]
    q = (cq @ lp["w_qb"].astype(F32)).reshape(r, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                              theta)], -1)
    gate = jax.nn.sigmoid(h @ lp["w_gate"].astype(F32)).reshape(
        r, heads, v_dim)
    w_kvb = lp["w_kvb"].reshape(-1, heads, nope + v_dim)
    chosen = jnp.zeros((r, n), bool).at[
        jnp.arange(r)[:, None], picked].set(True, mode="drop")
    chosen = chosen & (jnp.arange(n)[None, :] <= positions[:, None])

    def head_block(h0):
        kv = jnp.einsum("nc,chd->nhd", c, jax.lax.dynamic_slice_in_dim(
            w_kvb, h0, HEADS, axis=1).astype(F32))
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, None, :], (n, HEADS, rope))], -1)
        v = kv[..., nope:]
        sink = jax.lax.dynamic_slice_in_dim(lp["sink"], h0, HEADS)

        def rows(q, chosen):
            z = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
            z = jnp.where(chosen[None], z, -jnp.inf)
            m = jnp.maximum(z.max(-1), sink[:, None])
            p = jnp.where(chosen[None], jnp.exp(z - m[..., None]), 0.0)
            denom = p.sum(-1) + jnp.exp(sink[:, None] - m)
            return jnp.einsum("hqk,khd->qhd", p / denom[..., None], v)

        return _by_rows(rows, jax.lax.dynamic_slice_in_dim(
            q, h0, HEADS, axis=1), chosen)

    o = jax.lax.map(head_block, jnp.arange(0, heads, HEADS))  # [H/hb, R, ..]
    o = o.transpose(1, 0, 2, 3).reshape(r, heads, v_dim) * gate
    return o.reshape(r, heads * v_dim) @ lp["w_o"].astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "first",
                                             "limit"))
def _experts(lp, h, *, top_k, scaling, first, limit):
    """(y [R, d] of the held experts and the shared one, the router's
    closest call [R])."""
    s = jax.nn.sigmoid(h @ lp["w_router"].astype(F32))
    top = jax.lax.top_k(s + lp["router_bias"], top_k + 1)[0]
    chosen = jnp.argsort(-(s + lp["router_bias"]), axis=-1,
                         stable=True)[:, :top_k]
    w = jnp.take_along_axis(s, chosen, axis=1)
    w = scaling * w / w.sum(-1, keepdims=True)
    y = _swiglu(h, lp["shared_gate_up"], lp["shared_down"], limit)
    for e in range(lp["experts_gate_up"].shape[0]):
        share = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        y = y + share[:, None] * _swiglu(h, lp["experts_gate_up"][e],
                                         lp["experts_down"][e], limit)
    return y, top[:, top_k - 1] - top[:, top_k]


@functools.partial(jax.jit, static_argnames=("limit",))
def _dense(lp, h, *, limit):
    return _swiglu(h, lp["w_gate_up"], lp["w_down"], limit)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, gain, *, eps):
    return _rms(x, gain, eps)


def hidden_states(params, tokens, shape, margins=None, gaps=None):
    """sum_i X_i [T, d] of the whole sequence (host numpy).  `margins`,
    a list, gains one [T] array of the routers' closest calls for each
    expert layer; `gaps`, a list, one [T] array of the indexer's closest
    calls (inf where a row selects all) for each `full` layer."""
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = _whole(t, ROWS)
    positions = np.arange(t_pad, dtype=np.int32)
    eps = float(shape["rms_norm_eps"])
    rank, rope = int(shape["kv_lora_rank"]), int(shape["qk_rope_head_dim"])
    heads, nope = int(shape["num_heads"]), int(shape["qk_nope_head_dim"])
    v_dim, theta = int(shape["v_head_dim"]), float(shape["rope_theta"])
    if heads % HEADS or ROWS % Q_ROWS:
        raise ValueError(f"HEADS {HEADS} and Q_ROWS {Q_ROWS} have to divide "
                         f"the {heads} heads and the ROWS {ROWS}")
    n = int(shape.get("hc_mult", 4))
    mix = dict(eps=eps, sinkhorn_eps=float(shape.get("hc_eps", 1e-6)),
               magnitude=float(shape.get("hc_magnitude", 2.0)),
               rounds=int(shape.get("hc_sinkhorn_rounds", 20)))
    types = shape["indexer_types"]
    limit = shape.get("swiglu_limit")
    limit = None if limit is None else float(limit)
    first = int((shape.get("experts_held") or (0, 0))[0])
    dense = int(shape.get("first_k_dense_replace", 1))
    embed = np.asarray(jnp.asarray(params["embed"])[jnp.asarray(tokens)],
                       np.float32)
    x = np.zeros((t_pad, n, embed.shape[-1]), np.float32)
    x[:t] = embed[:, None, :]

    picked = None
    for li, lp in enumerate(params["layers"]):
        full = types[li] == "full"
        parts = []
        for lo, hi in _blocks(t_pad, ROWS):
            h = _normed(_mix(jnp.asarray(x[lo:hi]), lp["hc_attn"], **mix)[0],
                        lp["norm1"], eps=eps)
            parts.append(_latents(_part(lp, _LATENT + _INDEX_KEY * full), h,
                                  jnp.asarray(positions[lo:hi]),
                                  eps=eps, rank=rank, rope=rope, theta=theta,
                                  full=full)[1:])
        c = jnp.concatenate([p[0] for p in parts])
        k_rope = jnp.concatenate([p[1] for p in parts])
        keys = jnp.concatenate([p[2] for p in parts]) if full else None
        if full:
            picked = np.zeros((t_pad, int(shape["index_topk"])), np.int32)
            gap = np.full(t_pad, np.inf)
        routed = []
        for lo, hi in _blocks(t_pad, ROWS):
            pos = positions[lo:hi]
            xs = jnp.asarray(x[lo:hi])
            x_in, h_post, h_res = _mix(xs, lp["hc_attn"], **mix)
            h = _normed(x_in, lp["norm1"], eps=eps)
            cq = _latents(_part(lp, _LATENT), h, jnp.asarray(pos), eps=eps,
                          rank=rank, rope=rope, theta=theta, full=False)[0]
            span = _key_span(pos[-1], t_pad)
            if full:
                picked[lo:hi], gap[lo:hi] = select(lp, cq, h, keys[:span],
                                                   pos, shape)
            y = _attention_rows(
                _part(lp, _ATTENTION), h, cq, c[:span], k_rope[:span],
                jnp.asarray(picked[lo:hi]), jnp.asarray(pos), heads=heads,
                nope=nope, rope=rope, v_dim=v_dim, theta=theta)
            xs = _unmix(xs, y, h_post, h_res)
            x_in, h_post, h_res = _mix(xs, lp["hc_mlp"], **mix)
            h = _normed(x_in, lp["norm2"], eps=eps)
            if li < dense:
                y = _dense(lp, h, limit=limit)
            else:
                y, margin = _experts(
                    _part(lp, _EXPERTS), h,
                    top_k=int(shape["num_experts_per_tok"]),
                    scaling=float(shape["routed_scaling_factor"]),
                    first=first, limit=limit)
                routed.append(np.asarray(margin))
            x[lo:hi] = np.asarray(_unmix(xs, y, h_post, h_res))
        if routed and margins is not None:
            margins.append(np.concatenate(routed)[:t])
        if full and gaps is not None:
            gaps.append(gap[:t])
    return x[:t].sum(axis=1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, head, eps):
    return _rms(x, gain, eps) @ head.astype(F32)


def next_token_logits(params, tokens, shape, last, margins=None):
    """Logits [last, V] that follow each of the final `last` positions
    of `tokens` (`causal_lm.next_token_logits`'s contract), the head in
    blocks of the vocabulary.  `margins`, a list, gains each expert
    layer's router closest calls and each `full` layer's indexer closest
    calls, [T] arrays of every position: the least of them is the least
    noise that would change a choice of either."""
    with jax.default_matmul_precision("highest"):
        gaps = []
        x = jnp.asarray(hidden_states(params, tokens, shape, margins,
                                      gaps)[len(tokens) - int(last):])
        if margins is not None:
            margins.extend(gaps)
        vocab = params["head"].shape[1]
        return jnp.concatenate(
            [_head(x, params["norm_f"], params["head"][:, lo:hi],
                   float(shape["rms_norm_eps"]))
             for lo, hi in _blocks(vocab, VOCAB_COLS)], axis=1)
