"""GQAWindowMoELM: grouped-query attention in window and full layers,
gated, with four norms a layer and sigmoid-routed experts, behind the
engine's ragged step — the block kind `afmoe` (Arcee's Trinity family).

    h_0    = sqrt(d) * E[token]                   (mup: the embedding alone)
    h'     = h  + rms(Attn_l(rms(h;  g1)); g2)    four norms a layer: before
    h''    = h' + rms(FFN_l (rms(h'; g3)); g4)    and after each half
    logits = rms(h_L; g_f) W_head                 head untied

    Attn_l:  q = x W_q -> H heads of D;  k = x W_k, v = x W_v -> n heads of D
             gate = x W_gate -> H x D;  q, k normed over each head's D lanes
             window layer: q, k rotated (half-split pairs (i, i + D/2));
                           the query at p sees keys p - window + 1 .. p
             full layer:   no rotation, no other position signal; keys 0 .. p
             head h reads KV head h // (H / n);  o_h = softmax(q_h.k / sqrt(D)) v
             out = (concat_h o_h * sigmoid(gate)) W_o
    FFN_l:   l < first_k_dense_replace: a gated MLP; else `moe.route`'s
             experts beside a shared one (`blocks.feed_forward`)

A cached token of a layer is ONE row ``[k_0 .. k_{n-1} | v_0 .. v_{n-1}]``
(`kv_cache.HeadRows`), keys after their norm and rotation: `kv_rows()`
tells the engine's `DeviceKVPool` what a row is and `kv_layer_kinds()`
which layers keep only a window, so that the pool holds a page table
and a free list for each kind and gives a window layer's pages back
behind the window.  `ragged_step_fn` / `decode_params` are the whole of
the engine protocol this model implements: the ragged step is the one
path that serves it (`engine.UnsupportedModelPathError` for the rest).

Weights are seeded and drawn on the device in `dtype` (`blocks.
DeviceDraw`); matrix products accumulate in float32 and round to
`dtype`; norms, the softmax, the gate's sigmoid, the router and the
logits are float32.
"""
import math

import jax
import jax.numpy as jnp

from . import decode_attention
from .blocks import (STEP_COUNTERS, DeviceDraw, feed_forward,
                     feed_forward_scope, rms_norm, valid_rows)
from .fused import step_scope
from .kv_cache import HeadRows

WINDOW, FULL = "window", "full"
# the published names of the two kinds of layer
_KINDS = {"sliding_attention": WINDOW, "full_attention": FULL,
          WINDOW: WINDOW, FULL: FULL}


def rotate_half(x, positions, theta):
    """RoPE over the whole last axis of x [T, ..., D], lanes paired
    (i, i + D/2) at frequency theta**(-2i / D): the half-split pairing
    of `transformers`' `rotate_half`."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv          # [T, D/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class GQAWindowMoELM:
    """The model.  Argument names are `LatentMoELM`'s wherever they
    mean the same; the configuration file says which published key each
    one is."""

    def __init__(self, vocab_size=256, hidden_size=64, num_layers=4,
                 num_heads=4, num_kv_heads=2, head_dim=16,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2,
                 n_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=2.826, sliding_window=8,
                 layer_types=None, rope_theta=1e4, rms_norm_eps=1e-5,
                 mup_enabled=True, max_positions=131072,
                 dtype="bfloat16", seed=0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{num_heads} query heads over {num_kv_heads} KV heads of "
                f"{head_dim}: the heads must divide and pair up")
        self.dense_width = int(intermediate_size)
        self.expert_width = int(moe_intermediate_size)
        self.n_experts = int(n_routed_experts)
        self.top_k = int(num_experts_per_tok)
        self.n_shared = int(n_shared_experts)
        self.dense_layers = int(first_k_dense_replace)
        self.scaling = float(routed_scaling_factor)
        self.window = int(sliding_window)
        if layer_types is None:   # three window layers, then a full one
            layer_types = [FULL if li % 4 == 3 else WINDOW
                           for li in range(self.num_layers)]
        self.layer_kinds = tuple(_KINDS[kind] for kind in layer_types)
        if len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"{len(self.layer_kinds)} layer types for "
                f"{self.num_layers} layers")
        self.theta = float(rope_theta)
        self.eps = float(rms_norm_eps)
        self.embed_scale = math.sqrt(self.d_model) if mup_enabled else 1.0
        self.max_positions = int(max_positions)
        self.dtype = jnp.dtype(dtype)
        self.seed = seed
        self.scale = 1.0 / math.sqrt(self.head_dim)
        self.step_counters = (STEP_COUNTERS
                              if self.num_layers > self.dense_layers else ())
        self.params = self._draw(int(seed))

    # ----------------------------- weights ---------------------------
    def _draw(self, seed):
        draw = DeviceDraw(seed, self.dtype)
        w, gain = draw.w, draw.gain
        d, hd = self.d_model, self.head_dim
        q_width = self.num_heads * hd
        kv_width = self.num_kv_heads * hd
        layers = []
        for li in range(self.num_layers):
            layer = {
                "norm1": gain(d), "w_q": w(d, q_width), "w_k": w(d, kv_width),
                "w_v": w(d, kv_width), "w_gate": w(d, q_width),
                "norm_q": gain(hd), "norm_k": gain(hd),
                "w_o": w(q_width, d), "norm2": gain(d), "norm3": gain(d),
            }
            if li < self.dense_layers:
                layer["w_gate_up"] = w(d, 2 * self.dense_width)
                layer["w_down"] = w(self.dense_width, d)
            else:
                layer.update(draw.expert_layer(
                    d, self.expert_width, self.n_experts, self.n_shared))
            layer["norm4"] = gain(d)
            layers.append(layer)
        # sqrt(d) * E has unit variance: what a mup embedding is scaled
        # back to
        return {"embed": w(self.vocab_size, d, scale=1.0 / self.embed_scale),
                "layers": layers, "norm_f": gain(d),
                "head": w(d, self.vocab_size)}

    def decode_params(self):
        """The weights as a pytree: an argument of the step, never a
        constant of it."""
        return self.params

    def kv_rows(self):
        """A token's cache row, for `DeviceKVPool(rows=...)`."""
        return HeadRows(self.num_kv_heads, self.head_dim, self.dtype)

    def kv_layer_kinds(self):
        """``(kinds, window)``: 'window' or 'full' for each layer and
        the keys a window layer's query sees counting its own; the pool
        keeps a page table and a free list for each kind."""
        return self.layer_kinds, self.window

    # ------------------------------ layers ---------------------------
    def _mm(self, a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(
            self.dtype)

    def _queries_gate_and_row(self, lp, x, positions, kind):
        """(q [T, H, D], gate [T, H * D] float32, row [T, lanes]) of the
        packed tokens: normed (and, in a window layer, rotated) queries,
        the output gate, and the cache row ``[k.. | v..]``."""
        t = x.shape[0]
        q = rms_norm(self._mm(x, lp["w_q"]).reshape(
            t, self.num_heads, self.head_dim), lp["norm_q"], self.eps)
        k = rms_norm(self._mm(x, lp["w_k"]).reshape(
            t, self.num_kv_heads, self.head_dim), lp["norm_k"], self.eps)
        if kind == WINDOW:
            q = rotate_half(q, positions, self.theta)
            k = rotate_half(k, positions, self.theta)
        rows_spec = self.kv_rows()
        parts = [k.reshape(t, -1), self._mm(x, lp["w_v"])]
        if rows_spec.lanes > rows_spec.width:
            parts.append(jnp.zeros((t, rows_spec.lanes - rows_spec.width),
                                   self.dtype))
        gate = jnp.dot(x, lp["w_gate"], preferred_element_type=jnp.float32)
        return q, gate, jnp.concatenate(parts, axis=-1)

    def _attention_out(self, lp, o, gate):
        """o [T, H, D] under the sigmoid gate, through w_o."""
        gated = (o.reshape(o.shape[0], -1).astype(jnp.float32)
                 * jax.nn.sigmoid(gate)).astype(self.dtype)
        return self._mm(gated, lp["w_o"])

    # --------------------------- the ragged step ---------------------
    def ragged_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", interpret=None):
        """The pure mixed-batch step `fused.RaggedStep` jits, over a row
        cache with a page table for each kind of layer:

            fn(params, tokens, positions, pages, rows, page_tables,
               starts, lens, kv_lens, window_pages, window_page_tables,
               pools)
              -> ((token_ids [S], logits [S, V] f32, counters [3]), pools')

        The packed axis, the descriptors and the sampling rows are
        `TinyCausalLM.ragged_step_fn`'s; `pages` / `page_tables` are the
        full layers', `window_pages` / `window_page_tables` the same
        rows' and descriptors' in the window group (left out, with the
        window group, by a model without a window layer).  `counters`
        is `STEP_COUNTERS` summed over the expert layers."""
        del num_pages, pool_layout
        windowed = WINDOW in self.layer_kinds

        def step(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, *rest):
            (*window_args, pools) = rest
            tokens = jnp.asarray(tokens, jnp.int32)
            positions = jnp.asarray(positions, jnp.int32)
            rows = jnp.asarray(rows, jnp.int32)
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
            write = {FULL: jnp.asarray(pages, jnp.int32)}
            table = {FULL: jnp.asarray(page_tables, jnp.int32)}
            if windowed:
                write[WINDOW] = jnp.asarray(window_args[0], jnp.int32)
                table[WINDOW] = jnp.asarray(window_args[1], jnp.int32)
            t = tokens.shape[0]
            valid = valid_rows(starts, lens, t)
            with step_scope("embed"):
                x = (params["embed"][tokens].astype(jnp.float32)
                     * self.embed_scale).astype(self.dtype)
            with step_scope("attention"):
                work = decode_attention.gqa_work_lists(
                    starts, lens, kv_lens, page_size, table[FULL].shape[1],
                    t, self.window, use_kernel)
            pools_out = []
            with step_scope("head"):
                counters = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
            for lp, pool, kind in zip(params["layers"], pools,
                                      self.layer_kinds):
                with step_scope("attention"), jax.named_scope(kind):
                    q, gate, row = self._queries_gate_and_row(
                        lp, rms_norm(x, lp["norm1"], self.eps), positions,
                        kind)
                    pool = pool.at[write[kind], rows].set(row, mode="drop")
                    pools_out.append(pool)
                    o = decode_attention.gqa_ragged_attention(
                        q, pool, table[kind], starts, lens, kv_lens,
                        self.scale, self.num_kv_heads,
                        self.window if kind == WINDOW else None, use_kernel,
                        interpret=interpret, work=work[kind])
                    x = x + rms_norm(self._attention_out(lp, o, gate),
                                     lp["norm2"], self.eps)
                with feed_forward_scope(lp):
                    y, stats = feed_forward(
                        lp, rms_norm(x, lp["norm3"], self.eps), valid,
                        self.top_k, self.scaling)
                if stats is not None:
                    with step_scope("head"):
                        counters = counters + stats
                with feed_forward_scope(lp):
                    x = x + rms_norm(y, lp["norm4"], self.eps)
            with step_scope("head"):
                sample_rows = jnp.clip(starts + lens - 1, 0, t - 1)
                logits = jnp.dot(
                    rms_norm(x[sample_rows], params["norm_f"], self.eps),
                    params["head"], preferred_element_type=jnp.float32)
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = (ids, logits, counters) if self.step_counters \
                else (ids, logits)
            return out, pools_out

        return step
