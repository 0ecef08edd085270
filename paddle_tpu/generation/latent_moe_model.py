"""LatentMoELM: latent (MLA) attention and sigmoid-routed experts behind
the engine's ragged step — the block kind of GLM-4.7-Flash
(`glm4_moe_lite`), DeepSeek-V3's family.

    h_0 = E[token]                                  no position table
    h'  = h  + Attn(rms(h;  g1))
    h'' = h' + FFN_l(rms(h'; g2))                   l < dense_layers:
                                                    a gated MLP; else
                                                    experts + a shared one
    logits = rms(h_L; g_f) W_head                   head untied

Attention keeps ONE row a token a layer in the cache, ``[c | k_rope]``:
the normalised compressed kv (kv_lora_rank numbers) and the rotated
shared key (qk_rope_head_dim), after the norm and the rotation.  The
served path never expands it into per-head keys and values; it attends
in the ABSORBED form,

    q~_h    = q_nope_h W_kvb[K, h]^T                 (kv_lora_rank)
    score_h = (q~_h . c(s) + q_rope_h . k_rope(s)) / sqrt(nope + rope)
    o~_h    = sum_s p c(s);    o_h = o~_h W_kvb[V, h]

which is the expanded form with the products regrouped
(`benchmarks/reference/glm4_moe_lite.py` computes the expanded one; the
tests hold the two together).  `kv_rows()` tells the engine's
`DeviceKVPool` what a row is; `ragged_step_fn` / `decode_params` are the
whole of the engine protocol this model implements: the ragged step is
the one path that serves it, and every other is refused when the engine
is built (`engine.UnsupportedModelPathError`).

Weights are seeded and drawn ON THE DEVICE in `dtype` (bf16 as the
release): 4.5 B normals through numpy on the host would be most of a
run's set-up.  Matrix products accumulate in float32 and round to
`dtype`; norms, the softmax, the router and the logits are float32.
"""
import math

import jax
import jax.numpy as jnp

from . import decode_attention
from .blocks import (STEP_COUNTERS, DeviceDraw, feed_forward,
                     feed_forward_scope, rms_norm, valid_rows)
from .fused import step_scope
from .kv_cache import LatentRows


def rotate(x, positions, theta):
    """RoPE over the whole last axis of x [T, ..., R], lanes paired
    (2i, 2i + 1) at frequency theta**(-2i / R) — DeepSeek-V3's pairing
    (its release rotates half-split after de-interleaving both q and k,
    which gives the same scores)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv          # [T, R/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentMoELM:
    """The model; argument names are the published configuration's."""

    def __init__(self, vocab_size=256, hidden_size=64, num_layers=2,
                 num_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2,
                 n_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=1.8, rope_theta=1e6,
                 rms_norm_eps=1e-5, dtype="bfloat16", seed=0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.q_rank = int(q_lora_rank)
        self.kv_rank = int(kv_lora_rank)
        self.nope = int(qk_nope_head_dim)
        self.rope = int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        # the engine's `num_heads * head_dim` is an activation width
        self.head_dim = self.v_dim
        self.dense_width = int(intermediate_size)
        self.expert_width = int(moe_intermediate_size)
        self.n_experts = int(n_routed_experts)
        self.top_k = int(num_experts_per_tok)
        self.n_shared = int(n_shared_experts)
        self.dense_layers = int(first_k_dense_replace)
        self.scaling = float(routed_scaling_factor)
        self.theta = float(rope_theta)
        self.eps = float(rms_norm_eps)
        self.dtype = jnp.dtype(dtype)
        self.seed = seed
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        self.step_counters = (STEP_COUNTERS
                              if self.num_layers > self.dense_layers else ())
        self.params = self._draw(int(seed))

    # ----------------------------- weights ---------------------------
    def _draw(self, seed):
        draw = DeviceDraw(seed, self.dtype)
        w, gain = draw.w, draw.gain
        d, h = self.d_model, self.num_heads
        layers = []
        for li in range(self.num_layers):
            layer = {
                "norm1": gain(d),
                "w_qa": w(d, self.q_rank), "norm_q": gain(self.q_rank),
                "w_qb": w(self.q_rank, h * (self.nope + self.rope)),
                "w_kva": w(d, self.kv_rank + self.rope),
                "norm_kv": gain(self.kv_rank),
                "w_kvb": w(self.kv_rank, h * (self.nope + self.v_dim)),
                "w_o": w(h * self.v_dim, d),
                "norm2": gain(d),
            }
            if li < self.dense_layers:
                layer["w_gate_up"] = w(d, 2 * self.dense_width)
                layer["w_down"] = w(self.dense_width, d)
            else:
                layer.update(draw.expert_layer(
                    d, self.expert_width, self.n_experts, self.n_shared))
            layers.append(layer)
        return {"embed": w(self.vocab_size, d, scale=0.5), "layers": layers,
                "norm_f": gain(d), "head": w(d, self.vocab_size)}

    def decode_params(self):
        """The weights as a pytree: an argument of the step, never a
        constant of it."""
        return self.params

    def kv_rows(self):
        """A token's cache row, for `DeviceKVPool(rows=...)`."""
        return LatentRows(self.kv_rank + self.rope, self.kv_rank, self.dtype)

    # ------------------------------ layers ---------------------------
    def _mm(self, a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(
            self.dtype)

    def _queries_and_row(self, lp, x, positions):
        """(q_abs [T, H, lanes], row [T, lanes]): the absorbed queries
        and the cache row of each packed token, both zero past the
        row's width."""
        t, h = x.shape[0], self.num_heads
        cq = rms_norm(self._mm(x, lp["w_qa"]), lp["norm_q"], self.eps)
        q = self._mm(cq, lp["w_qb"]).reshape(t, h, self.nope + self.rope)
        q_rope = rotate(q[..., self.nope:], positions, self.theta)
        kva = self._mm(x, lp["w_kva"])
        c = rms_norm(kva[:, :self.kv_rank], lp["norm_kv"], self.eps)
        k_rope = rotate(kva[:, self.kv_rank:], positions, self.theta)
        w_k = lp["w_kvb"].reshape(self.kv_rank, h, self.nope + self.v_dim)[
            :, :, :self.nope]
        q_abs = jnp.einsum("thn,chn->thc", q[..., :self.nope], w_k,
                           preferred_element_type=jnp.float32).astype(
                               self.dtype)
        pad = self.kv_rows().lanes - self.kv_rank - self.rope
        q_abs = jnp.concatenate(
            [q_abs, q_rope, jnp.zeros((t, h, pad), self.dtype)], axis=-1)
        row = jnp.concatenate(
            [c, k_rope, jnp.zeros((t, pad), self.dtype)], axis=-1)
        return q_abs, row

    def _attention_out(self, lp, o_abs):
        """o~ [T, H, kv_rank] through the value half of w_kvb and w_o."""
        t, h = o_abs.shape[0], self.num_heads
        w_v = lp["w_kvb"].reshape(self.kv_rank, h, self.nope + self.v_dim)[
            :, :, self.nope:]
        o = jnp.einsum("thc,chv->thv", o_abs.astype(self.dtype), w_v,
                       preferred_element_type=jnp.float32).astype(self.dtype)
        return self._mm(o.reshape(t, h * self.v_dim), lp["w_o"])

    # --------------------------- the ragged step ---------------------
    def ragged_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", interpret=None):
        """The pure mixed-batch step `fused.RaggedStep` jits, over a
        latent cache's one pool group:

            fn(params, tokens, positions, pages, rows, page_tables,
               starts, lens, kv_lens, pools)
              -> ((token_ids [S], logits [S, V] f32, counters [3]), pools')

        The packed axis, the descriptors and the sampling rows are
        `TinyCausalLM.ragged_step_fn`'s.  `counters` is `STEP_COUNTERS`
        summed over the expert layers."""
        del num_pages, pool_layout
        rows_spec = self.kv_rows()

        def step(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, pools):
            tokens = jnp.asarray(tokens, jnp.int32)
            positions = jnp.asarray(positions, jnp.int32)
            pages = jnp.asarray(pages, jnp.int32)
            rows = jnp.asarray(rows, jnp.int32)
            pt = jnp.asarray(page_tables, jnp.int32)
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
            t = tokens.shape[0]
            valid = valid_rows(starts, lens, t)
            with step_scope("embed"):
                x = params["embed"][tokens]
            with step_scope("attention"):
                work = decode_attention.latent_work_list(
                    pt, starts, lens, kv_lens, page_size, t, use_kernel)
            pools_out = []
            with step_scope("head"):
                counters = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
            for lp, pool in zip(params["layers"], pools):
                with step_scope("attention"), jax.named_scope("latent"):
                    q_abs, row = self._queries_and_row(
                        lp, rms_norm(x, lp["norm1"], self.eps), positions)
                    pool = pool.at[pages, rows].set(row, mode="drop")
                    pools_out.append(pool)
                    o_abs = decode_attention.latent_ragged_attention(
                        q_abs, pool, pt, starts, lens, kv_lens, self.scale,
                        rows_spec.value_width, use_kernel,
                        interpret=interpret, work=work)
                    x = x + self._attention_out(lp, o_abs)
                with feed_forward_scope(lp):
                    y, stats = feed_forward(
                        lp, rms_norm(x, lp["norm2"], self.eps), valid,
                        self.top_k, self.scaling)
                if stats is not None:
                    with step_scope("head"):
                        counters = counters + stats
                with feed_forward_scope(lp):
                    x = x + y
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
