"""SparseLatentMoELM: gated latent (MLA) attention over the tokens a
learned indexer selects, a residual four streams wide mixed by
hyper-connections, and sigmoid-routed experts of which a layer holds a
share, behind the engine's ragged step: the block of Hy4-preview
(`hy_v4`), DeepSeek-V3.2's sparse attention in its family.

    X_0 = [E[token]] x n                         n residual streams
    each sublayer F (attention, then the feed-forward half):
        X <- H_res X + h_post (x) F(sum_i h_pre_i X_i)     (blocks.
                                                 hyper_connection)
    logits = rms(sum_i X_i; g_f) W_head           float32 weights and
                                                  product

Attention is `LatentMoELM`'s latent attention (its projections, its
rotation, its absorbed form over ONE cached row ``[c | k_rope]`` a token
and layer) with three changes:

- the row attends over S_t alone, the tokens of its context the
  indexer selects: all of them while the context holds at most
  `index_topk`, else the exact top `index_topk` of

      I(t, s) = sum_j w_j relu(q^I_j(t) . k^I(s)) / sqrt(D_I)
      q^I = c^Q W_iq (rotated on its first rope lanes)
      k^I = LayerNorm(x W_ik) (likewise);   w = x W_iw / sqrt(H_I)

  A `full` layer (`indexer_types`) scores and selects; a `shared` layer
  has no indexer and attends over the set of the `full` layer before
  it.  The index keys are cached beside the latent rows of the `full`
  layers (`kv_cache.SparseLatentRows`);
- a sink logit a head in the softmax's denominator;
- an elementwise gate on each head's value: o_h * sigmoid(x W_g)_h.

The feed-forward half is the dense gated MLP in the first
`first_k_dense_replace` layers, else `n_routed_experts` experts HELD of
a router `router_width` wide (`experts_held`) beside the shared one,
every MLP clamped by `swiglu_limit` (`moe.swiglu`).

Weights are seeded and drawn on the device in `dtype`; products
accumulate in float32; norms, the softmax, the routers, the index
scores, the hyper-connections' coefficients and the logits are float32.
The residual streams are kept in `dtype`, as every served model keeps
its residual.  `benchmarks/reference/hy_v4.py` is the plain forward
pass the tests hold this to.
"""
import math

import jax
import jax.numpy as jnp

from . import decode_attention
from .blocks import (HELD_STEP_COUNTERS, DeviceDraw, feed_forward,
                     feed_forward_scope, hyper_connection, rms_norm,
                     valid_rows)
from .fused import step_scope
from .kv_cache import SparseLatentRows
from .latent_moe_model import LatentMoELM, rotate

FULL, SHARED = "full", "shared"
# what a step counts behind the experts' four: the selected tokens
# attended and the context tokens they were chosen from, both summed
# over rows and layers, the (row, index layer) pairs scored, and the
# index keys of the descriptors' contexts, each once an index layer (the
# last two 0 in a step whose contexts all fit the selection: nothing is
# scored there)
DSA_COUNTERS = tuple(f"generation.dsa_{name}" for name in (
    "tokens_attended", "context_tokens", "rows_indexed", "index_keys"))
_INDEX_NORM_EPS = 1e-6


def layer_norm(x, gain, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * gain + bias).astype(
        x.dtype)


def rotate_leading(x, positions, lanes, theta):
    """`rotate` on the first `lanes` lanes of x's last axis alone."""
    return jnp.concatenate(
        [rotate(x[..., :lanes], positions, theta), x[..., lanes:]], axis=-1)


class SparseLatentMoELM(LatentMoELM):
    """The model.  Argument names are the published configuration's;
    `n_routed_experts` is the experts HELD, `router_width` the experts
    the routers know and `experts_held` = (first, count) which of them
    are held (`HybridSSMMoELM`'s names)."""

    def __init__(self, vocab_size=256, hidden_size=64, num_layers=3,
                 num_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2,
                 router_width=None, experts_held=None, n_shared_experts=1,
                 first_k_dense_replace=1, routed_scaling_factor=2.827,
                 swiglu_limit=10.0, index_n_heads=4, index_head_dim=16,
                 index_topk=8, indexer_types=None, hc_mult=4,
                 hc_magnitude=2.0, hc_eps=1e-6, hc_sinkhorn_rounds=20,
                 rope_theta=1e7, rms_norm_eps=1e-5, dtype="bfloat16",
                 seed=0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.q_rank = int(q_lora_rank)
        self.kv_rank = int(kv_lora_rank)
        self.nope = int(qk_nope_head_dim)
        self.rope = int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.head_dim = self.v_dim
        self.dense_width = int(intermediate_size)
        self.expert_width = int(moe_intermediate_size)
        self.n_experts = int(n_routed_experts)
        self.router_width = int(router_width or self.n_experts)
        first, count = experts_held or (0, self.router_width)
        self.experts_held = (int(first), int(count))
        if (count != self.n_experts or first < 0
                or first + count > self.router_width):
            raise ValueError(
                f"experts_held {experts_held!r}: {self.n_experts} experts "
                f"held of a router {self.router_width} wide")
        self.top_k = int(num_experts_per_tok)
        self.n_shared = int(n_shared_experts)
        self.dense_layers = int(first_k_dense_replace)
        self.scaling = float(routed_scaling_factor)
        self.swiglu_limit = (None if swiglu_limit is None
                             else float(swiglu_limit))
        self.index_heads = int(index_n_heads)
        self.index_dim = int(index_head_dim)
        self.index_topk = int(index_topk)
        if indexer_types is None:    # the first, then one in four
            indexer_types = [FULL if li == 0 or li % 4 == 1 else SHARED
                             for li in range(self.num_layers)]
        self.indexer_types = tuple(indexer_types)
        if (len(self.indexer_types) != self.num_layers
                or self.indexer_types[0] != FULL
                or set(self.indexer_types) - {FULL, SHARED}):
            raise ValueError(
                f"indexer_types {indexer_types!r}: 'full' or 'shared' "
                f"for each of {num_layers} layers, the first 'full'")
        self.hc_n = int(hc_mult)
        self.hc_magnitude = float(hc_magnitude)
        self.hc_eps = float(hc_eps)
        self.hc_rounds = int(hc_sinkhorn_rounds)
        self.theta = float(rope_theta)
        self.eps = float(rms_norm_eps)
        self.dtype = jnp.dtype(dtype)
        self.seed = seed
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        self.step_counters = HELD_STEP_COUNTERS + DSA_COUNTERS
        self.params = self._draw(int(seed))

    # ----------------------------- weights ---------------------------
    def _hyper(self, draw):
        """One sublayer's hyper-connection: Phi of 1/sqrt(n d), alpha
        about 0.1, the biases 0.5 n with the residual's leaning to the
        identity (2 on its diagonal), so that the streams mix and stay
        apart."""
        n, d = self.hc_n, self.d_model
        f32 = jnp.float32
        bias = 0.5 * draw.w(n * (n + 2), scale=1.0, dtype=f32)
        bias = bias.at[2 * n:].add(2.0 * jnp.eye(n, dtype=f32).reshape(-1))
        return {"phi": draw.w(n * d, n * (n + 2), dtype=f32),
                "alpha": 0.1 + 0.05 * draw.w(n * (n + 2), scale=1.0,
                                             dtype=f32),
                "bias": bias}

    def _draw(self, seed):
        draw = DeviceDraw(seed, self.dtype)
        w, gain = draw.w, draw.gain
        d, h = self.d_model, self.num_heads
        f32 = jnp.float32
        layers = []
        for li in range(self.num_layers):
            layer = {
                "hc_attn": self._hyper(draw),
                "norm1": gain(d),
                "w_qa": w(d, self.q_rank), "norm_q": gain(self.q_rank),
                "w_qb": w(self.q_rank, h * (self.nope + self.rope)),
                "w_kva": w(d, self.kv_rank + self.rope),
                "norm_kv": gain(self.kv_rank),
                "w_kvb": w(self.kv_rank, h * (self.nope + self.v_dim)),
                "w_gate": w(d, h * self.v_dim),
                "sink": w(h, scale=1.0, dtype=f32),
                "w_o": w(h * self.v_dim, d),
                "hc_mlp": self._hyper(draw),
                "norm2": gain(d),
            }
            if self.indexer_types[li] == FULL:
                layer.update({
                    "w_iq": w(self.q_rank, self.index_heads * self.index_dim),
                    "w_ik": w(d, self.index_dim),
                    "ik_gain": gain(self.index_dim),
                    "ik_bias": 0.1 * w(self.index_dim, scale=1.0, dtype=f32),
                    "w_iw": w(d, self.index_heads)})
            if li < self.dense_layers:
                layer["w_gate_up"] = w(d, 2 * self.dense_width)
                layer["w_down"] = w(self.dense_width, d)
            else:
                layer.update(draw.expert_layer(
                    d, self.expert_width, self.n_experts, self.n_shared,
                    self.router_width))
            layers.append(layer)
        return {"embed": w(self.vocab_size, d, scale=0.5), "layers": layers,
                "norm_f": gain(d),
                "head": w(d, self.vocab_size, dtype=f32)}

    def kv_rows(self):
        """A token's cache row, for `DeviceKVPool(rows=...)`: the latent
        row of every layer, and the index key of the `full` ones."""
        return SparseLatentRows(
            self.kv_rank + self.rope, self.dtype, self.index_dim,
            [li for li, kind in enumerate(self.indexer_types)
             if kind == FULL], self.index_topk)

    def build_gauges(self):
        """What the engine stamps of this model beside its own gauges."""
        return {"moe_experts_held": self.experts_held[1],
                "moe_router_width": self.router_width,
                "kv_index_layers": self.indexer_types.count(FULL),
                "dsa_index_topk": self.index_topk}

    # ------------------------------ layers ---------------------------
    def _row_numbers(self):
        """Numbers a stored latent row carries (`SparseLatentRows`)."""
        rows = self.kv_rows()
        return rows.lanes * rows.per_word

    def _pad(self, x):
        pad = self._row_numbers() - x.shape[-1]
        return jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)

    def _store(self, row):
        """[T, numbers] in `dtype` -> the stored words."""
        rows = self.kv_rows()
        if rows.per_word == 1:
            return row
        from ..ops.pallas.sparse_latent_attention import pack_rows

        return pack_rows(row, rows.lanes)

    def _indexer(self, lp, h, cq, positions, keys, write, ctx):
        """The index keys of this step's rows into `keys`, then every
        row's selection: (keys', positions [T, K], counts [T])."""
        t = h.shape[0]
        q = self._mm(cq, lp["w_iq"]).reshape(t, self.index_heads,
                                             self.index_dim)
        q = rotate_leading(q, positions, self.rope, self.theta)
        k = layer_norm(self._mm(h, lp["w_ik"]), lp["ik_gain"], lp["ik_bias"],
                       _INDEX_NORM_EPS)
        k = rotate_leading(k, positions, self.rope, self.theta)
        keys = keys.at[write].set(k, mode="drop")
        w = jnp.dot(h, lp["w_iw"], preferred_element_type=jnp.float32) * (
            (self.index_heads * self.index_dim) ** -0.5)

        def scores():
            return decode_attention.dsa_index_scores(
                q, w, keys, ctx["table"], ctx["starts"], ctx["lens"],
                ctx["kv_lens"], ctx["qpos"], ctx["use_kernel"],
                ctx["interpret"])

        picked, counts = decode_attention.dsa_select(
            scores, ctx["qpos"], self.index_topk,
            ctx["table"].shape[1] * ctx["page_size"])
        return keys, picked, counts

    def _attention(self, lp, x, pool, keys, pick, write, positions, ctx):
        """The attention sublayer over normed-by-itself input x: this
        step's rows into the pool (and, in a `full` layer, the index
        keys), the selection, the sparse attention, the gate, w_o.
        Returns (out [T, d], pool', keys', pick)."""
        h = rms_norm(x, lp["norm1"], self.eps)
        cq, q_abs, q_rope, c, k_rope = self._latent_parts(lp, h, positions)
        row = self._store(self._pad(jnp.concatenate([c, k_rope], axis=-1)))
        if ctx["use_kernel"]:
            from ..ops.pallas.sparse_latent_attention import pool_row_write

            pool = pool_row_write(pool, *write, row,
                                  interpret=ctx["interpret"])
        else:
            pool = pool.at[write].set(row[:, None, :], mode="drop")
        if keys is not None:
            with jax.named_scope("indexer"):
                keys, picked, counts = self._indexer(
                    lp, h, cq, positions, keys, write, ctx)
                pick = (decode_attention.selected_slots(
                    picked, counts, ctx["table"], ctx["desc"],
                    ctx["page_size"]), counts)
        q = self._pad(jnp.concatenate([q_abs, q_rope], axis=-1))
        q = (q.astype(jnp.float32) * self.scale).astype(self.dtype)
        with jax.named_scope("sparse_latent"):
            o_abs = decode_attention.sparse_latent_attention(
                q, pool, *pick, lp["sink"], self.kv_rank,
                ctx["use_kernel"], interpret=ctx["interpret"])
        gate = jax.nn.sigmoid(jnp.dot(
            h, lp["w_gate"], preferred_element_type=jnp.float32)).reshape(
                h.shape[0], self.num_heads, self.v_dim)
        return self._attention_out(lp, o_abs, gate), pool, keys, pick

    # --------------------------- the ragged step ---------------------
    def ragged_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", interpret=None):
        """The pure mixed-batch step `fused.RaggedStep` jits, over a
        latent cache with index keys:

            fn(params, tokens, positions, pages, rows, page_tables,
               starts, lens, kv_lens, pools, keys)
              -> ((token_ids [S], logits [S, V] f32, counters [8]),
                  pools', keys')

        The packed axis, the descriptors and the sampling rows are
        `TinyCausalLM.ragged_step_fn`'s; `pools` one latent pool a
        layer, `keys` one index-key pool a `full` layer.  `counters` is
        `step_counters`: the experts' four summed over the expert
        layers, then `DSA_COUNTERS`."""
        del num_pages, pool_layout
        n_full = self.indexer_types.count(FULL)

        def step(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, pools, keys):
            tokens = jnp.asarray(tokens, jnp.int32)
            positions = jnp.asarray(positions, jnp.int32)
            write = (jnp.asarray(pages, jnp.int32),
                     jnp.asarray(rows, jnp.int32))
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
            t = tokens.shape[0]
            valid = valid_rows(starts, lens, t)
            with step_scope("attention"):
                desc, qpos = decode_attention.row_positions(
                    starts, lens, kv_lens, t)
            ctx = {"table": jnp.asarray(page_tables, jnp.int32),
                   "starts": starts, "lens": lens, "kv_lens": kv_lens,
                   "desc": desc, "qpos": qpos, "page_size": page_size,
                   "use_kernel": use_kernel, "interpret": interpret}
            with step_scope("embed"):
                x = params["embed"][tokens]
                streams = jnp.broadcast_to(x[:, None, :],
                                           (t, self.hc_n, self.d_model))
            with step_scope("head"):
                moe_counts = jnp.zeros((len(HELD_STEP_COUNTERS),),
                                       jnp.int32)
            mix = dict(eps=self.eps, sinkhorn_eps=self.hc_eps,
                       magnitude=self.hc_magnitude, rounds=self.hc_rounds)
            pools_out, keys_out, keys = [], [], iter(keys)
            pick = None
            for lp, pool, kind in zip(params["layers"], pools,
                                      self.indexer_types):
                layer_keys = next(keys) if kind == FULL else None

                def attention(x, lp=lp, pool=pool, layer_keys=layer_keys):
                    with step_scope("attention"):
                        out, *state = self._attention(
                            lp, x, pool, layer_keys, pick, write, positions,
                            ctx)
                    return out, state

                streams, (pool, layer_keys, pick) = hyper_connection(
                    streams, lp["hc_attn"], attention, **mix)
                pools_out.append(pool)
                if kind == FULL:
                    keys_out.append(layer_keys)

                def mlp(x, lp=lp):
                    with feed_forward_scope(lp):
                        return feed_forward(
                            lp, rms_norm(x, lp["norm2"], self.eps), valid,
                            self.top_k, self.scaling, "sigmoid_bias",
                            self.experts_held, self.swiglu_limit)

                streams, stats = hyper_connection(streams, lp["hc_mlp"], mlp,
                                                  **mix)
                if stats is not None:
                    with step_scope("head"):
                        moe_counts = moe_counts + stats
            with step_scope("head"):
                sample_rows = jnp.clip(starts + lens - 1, 0, t - 1)
                last = jnp.sum(streams[sample_rows].astype(jnp.float32),
                               axis=1)
                logits = jnp.dot(
                    rms_norm(last, params["norm_f"], self.eps),
                    params["head"], precision="highest")
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                held = qpos >= 0
                counts = pick[1]
                # the indexer scores only in a step with a row to sort
                scored = n_full * (jnp.any(qpos + 1 > self.index_topk) & (
                    page_tables.shape[1] * page_size > self.index_topk))
                dsa = jnp.stack([
                    self.num_layers * jnp.sum(counts),
                    self.num_layers * jnp.sum(jnp.where(held, qpos + 1, 0)),
                    scored * jnp.sum(held.astype(jnp.int32)),
                    scored * jnp.sum(jnp.where(lens > 0, kv_lens, 0))])
                counters = jnp.concatenate([moe_counts, dsa.astype(jnp.int32)])
            return (ids, logits, counters), pools_out, keys_out

        return step
