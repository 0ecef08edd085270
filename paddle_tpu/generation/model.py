"""TinyCausalLM: a small pure-jnp causal transformer implementing the
GenerationEngine decode protocol — the reference model for tests,
benchmarks, and the docs walkthrough.

Two forward paths over the SAME weights:

- `prefill(tokens)` — dense causal attention over the whole prefix
  (full recompute), returning the last position's logits plus every
  position's per-layer K/V for the paged cache;
- `prefill_batch(tokens, lengths)` — the bucketed-batch variant: B
  length-padded prompts in one dense causal pass.  Causality makes the
  padding invisible (a padded position only ever sits AFTER every real
  position it could have influenced), and the batched einsums evaluate
  each sequence's rows with the same reduction order as the single-
  sequence path, so real rows are BITWISE equal to `prefill` — the
  property that lets the engine batch prefills under the zero-tolerance
  token-identity oracle;
- `decode(tokens, positions, attend)` — one token per sequence, with
  attention delegated to the engine's paged-KV callback.

Both paths compute each position with identical math (same einsums, same
masked-softmax construction — see decode_attention.py on why the masking
is exact), which is what makes the engine's oracle meaningful: greedy
decode through the paged path must reproduce full-recompute generation
token for token.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import decode_attention
from .decode_attention import dense_causal_reference
from .fused import step_scope


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


class TinyCausalLM:
    """Pre-LN transformer decoder: emb -> [attn + MLP] x L -> LN -> head.

    Deterministic per (seed, shape): weights come from one seeded
    np.random.Generator, so tests and benches reproduce exactly.
    """

    def __init__(self, vocab_size=64, num_layers=2, num_heads=2,
                 head_dim=8, mlp_ratio=2, max_positions=512, seed=0):
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.d_model = self.num_heads * self.head_dim
        self.max_positions = int(max_positions)
        self.seed = seed  # weights are deterministic per (seed, shape)
        rng = np.random.default_rng(seed)
        d = self.d_model

        def w(*shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[0])
            return jnp.asarray(
                rng.standard_normal(shape, np.float32) * scale)

        self.tok_emb = w(self.vocab_size, d, scale=0.5)
        self.pos_emb = w(self.max_positions, d, scale=0.1)
        self.blocks = []
        for _ in range(self.num_layers):
            self.blocks.append({
                "ln1_s": jnp.ones((d,), jnp.float32),
                "ln1_b": jnp.zeros((d,), jnp.float32),
                "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
                "ln2_s": jnp.ones((d,), jnp.float32),
                "ln2_b": jnp.zeros((d,), jnp.float32),
                "w1": w(d, mlp_ratio * d), "b1": jnp.zeros(
                    (mlp_ratio * d,), jnp.float32),
                "w2": w(mlp_ratio * d, d), "b2": jnp.zeros((d,),
                                                           jnp.float32),
            })
        self.ln_f_s = jnp.ones((d,), jnp.float32)
        self.ln_f_b = jnp.zeros((d,), jnp.float32)
        self.head = w(d, self.vocab_size)

    # ----------------------- shared per-position math ----------------
    def _embed(self, tokens, positions):
        # loud failure over jnp's silent out-of-bounds gather clamp:
        # position max_positions would reuse row max_positions-1 and
        # generate wrong logits with no error
        if int(jnp.max(positions)) >= self.max_positions:
            raise ValueError(
                f"position {int(jnp.max(positions))} >= max_positions="
                f"{self.max_positions}")
        return self.tok_emb[tokens] + self.pos_emb[positions]

    def _qkv(self, blk, x):
        """x: [N, d_model] -> q, k, v each [N, H, D]."""
        n = x.shape[0]
        h, dd = self.num_heads, self.head_dim
        q = (x @ blk["wq"]).reshape(n, h, dd)
        k = (x @ blk["wk"]).reshape(n, h, dd)
        v = (x @ blk["wv"]).reshape(n, h, dd)
        return q, k, v

    def _mlp(self, blk, x):
        hlay = jnp.maximum(x @ blk["w1"] + blk["b1"], 0.0)
        return hlay @ blk["w2"] + blk["b2"]

    @staticmethod
    def _row_matmul(mesh, tp_axis, quant_collectives):
        """The matmul used for the two ROW-SHARDED contractions (wo,
        w2) in the jitted step fns.  Plain ``a @ w`` normally (GSPMD
        inserts the fp32 allreduce from the sharding); with
        `quant_collectives` under a real mesh, the EQuARX-style
        explicit quantized ring (parallel.quantized_allreduce) placed
        exactly where the implicit allreduce sits."""
        if quant_collectives and mesh is not None:
            if tp_axis is None:
                tp_axis = tuple(mesh.axis_names)[0]
            if int(mesh.shape[tp_axis]) > 1:
                from ..parallel.quantized_allreduce import (
                    quantized_matmul_allreduce)

                return quantized_matmul_allreduce(mesh, tp_axis)
        return lambda a, w: a @ w

    @staticmethod
    def _mlp_rowmm(blk, x, rowmm):
        """_mlp with the second (row-sharded) matmul routed through
        `rowmm` — identical ops when rowmm is the plain matmul."""
        hlay = jnp.maximum(x @ blk["w1"] + blk["b1"], 0.0)
        return rowmm(hlay, blk["w2"]) + blk["b2"]

    def _logits(self, x):
        return _layer_norm(x, self.ln_f_s, self.ln_f_b) @ self.head

    # ----------------------------- prefill ---------------------------
    def prefill(self, tokens):
        """tokens: [T] ints.  Returns (last_logits [V],
        k [L, T, H, D], v [L, T, H, D])."""
        tokens = jnp.asarray(tokens, jnp.int32)
        t = tokens.shape[0]
        x = self._embed(tokens, jnp.arange(t, dtype=jnp.int32))
        ks, vs = [], []
        for blk in self.blocks:
            hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
            q, k, v = self._qkv(blk, hn)
            ks.append(k)
            vs.append(v)
            attn = dense_causal_reference(q, k, v)     # [T, H, D]
            x = x + attn.reshape(t, self.d_model) @ blk["wo"]
            x = x + self._mlp(blk, _layer_norm(x, blk["ln2_s"],
                                               blk["ln2_b"]))
        logits = self._logits(x[t - 1:t])[0]
        return logits, jnp.stack(ks), jnp.stack(vs)

    # -------------------------- batched prefill -----------------------
    def prefill_batch(self, tokens, lengths):
        """tokens: [B, T] ints, length-padded (pad ids are real vocab
        rows — harmless, their K/V and logits are discarded); lengths:
        [B] real token counts.  Returns (last_logits [B, V] taken at
        each sequence's lengths-1, k [B, L, T, H, D], v [B, L, T, H, D]).

        Bounds are checked via the STATIC padded length (jit-safe), so
        this lowers cleanly when the engine AOT-compiles per bucket."""
        tokens = jnp.asarray(tokens, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        b, t = tokens.shape
        if t > self.max_positions:
            raise ValueError(
                f"padded length {t} > max_positions={self.max_positions}")
        h, dd = self.num_heads, self.head_dim
        scale = 1.0 / math.sqrt(dd)
        x = self.tok_emb[tokens] + self.pos_emb[
            jnp.arange(t, dtype=jnp.int32)][None]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        ks, vs = [], []
        for blk in self.blocks:
            hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
            q = (hn @ blk["wq"]).reshape(b, t, h, dd)
            k = (hn @ blk["wk"]).reshape(b, t, h, dd)
            v = (hn @ blk["wv"]).reshape(b, t, h, dd)
            ks.append(k)
            vs.append(v)
            # dense_causal_reference with a batch axis: same einsum
            # contraction order per sequence, so bitwise-equal rows
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            logits = jnp.where(causal[None, None], logits,
                               decode_attention.NEG_INF)
            weights = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
            x = x + attn.reshape(b, t, self.d_model) @ blk["wo"]
            x = x + self._mlp(blk, _layer_norm(x, blk["ln2_s"],
                                               blk["ln2_b"]))
        last = x[jnp.arange(b), lengths - 1]
        return self._logits(last), jnp.stack(ks, 1), jnp.stack(vs, 1)

    # ------------------------- chunked prefill ------------------------
    def prefill_chunk(self, tokens, start, attend):
        """One prefill CHUNK (the eager path, mirrors `decode`): tokens
        [n] are the prompt slice at global positions
        ``start .. start + n - 1``.  Per layer, ``attend(layer, q, k, v)``
        (each [n, H, D]) appends the chunk's K/V to the engine-owned
        paged cache and returns causal attention over prefix + chunk.
        Returns the chunk's LAST position logits [V] — for the final
        chunk these ARE the next-token logits, exactly like `prefill`.

        Row math is identical to `prefill` (same helpers, same einsums;
        the key source — cached fp32 prefix rows — is an exact copy),
        so the only divergence from full prefill is XLA's per-shape
        reduction strategy: values agree at the reassociation ulp
        level, and the oracle contract is TOKEN identity
        (tests/test_chunked_prefill.py), the fused-decode standard."""
        tokens = jnp.asarray(tokens, jnp.int32)
        n = tokens.shape[0]
        positions = start + jnp.arange(n, dtype=jnp.int32)
        x = self._embed(tokens, positions)
        for li, blk in enumerate(self.blocks):
            hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
            q, k, v = self._qkv(blk, hn)
            attn = jnp.asarray(attend(li, q, k, v))    # [n, H, D]
            x = x + attn.reshape(n, self.d_model) @ blk["wo"]
            x = x + self._mlp(blk, _layer_norm(x, blk["ln2_s"],
                                               blk["ln2_b"]))
        return self._logits(x[n - 1:n])[0]

    def prefill_chunk_fn(self, page_size, num_pages, use_kernel=False,
                         pool_layout="token", mesh=None, tp_axis=None,
                         kv_quant=False, quant_collectives=False):
        """Build the PURE whole-chunk function the engine's jitted
        chunked-prefill path compiles (mirrors `decode_step_fn`)::

            fn(params, tokens, start, length, k_pools, v_pools,
               page_table) -> (last_logits [V], k_pools', v_pools')

        tokens: [C] int32, the chunk padded to the fixed chunk shape;
        start: int32 scalar, the chunk's first global position (== the
        tokens already in the cache); length: int32 scalar, real chunk
        tokens (rows >= length are bucket padding: their K/V write is
        routed to the OOB sentinel page and dropped, their logits are
        never read).  k_pools/v_pools: length-L lists of pool arrays
        (donated by the caller; returned updated).  page_table:
        [max_pages] int32 for THIS sequence, padded with page 0.  Each
        layer scatters the chunk's K/V into the pool, then attends over
        the page table — prefix and chunk through one paged read
        (decode_attention.chunk_prefill_attention), so the executable's
        shape depends only on (chunk, pages bucket), never the prompt.

        mesh / tp_axis: the same tensor-parallel sharding contract as
        decode_step_fn — chunk q/k/v sharded over heads, pools pinned to
        the pool sharding through the donation chain, last-position
        logits pinned replicated.

        kv_quant: int8 pools — the fn signature grows the per-layer
        [P, H] scale arrays (``..., k_pools, v_pools, k_scales,
        v_scales, page_table``) riding the same donation chain, writes
        run the quantized three-step transform, and attention takes the
        scales for in-kernel dequant.  quant_collectives: the two
        row-sharded matmuls run the explicit quantized ring allreduce
        (_row_matmul)."""
        from ..parallel.sharding_annotations import (constrain,
                                                     kv_pool_spec,
                                                     kv_scale_spec)
        from .kv_cache import scatter_pool_update
        from .quantized_kv import quantized_pool_write

        page_size = int(page_size)
        num_pages = int(num_pages)
        pool_spec = (kv_pool_spec(pool_layout, tp_axis)
                     if mesh is not None else None)
        scale_spec = (kv_scale_spec(tp_axis)
                      if mesh is not None else None)
        rowmm = self._row_matmul(mesh, tp_axis, quant_collectives)

        def step(params, tokens, start, length, k_pools, v_pools,
                 *rest):
            if kv_quant:
                k_scales, v_scales, page_table = rest
            else:
                (page_table,) = rest
            tokens = jnp.asarray(tokens, jnp.int32)
            start = jnp.asarray(start, jnp.int32)
            length = jnp.asarray(length, jnp.int32)
            pt = jnp.asarray(page_table, jnp.int32)
            c = tokens.shape[0]
            idx = jnp.arange(c, dtype=jnp.int32)
            live = idx < length
            # padding rows embed position 0 (in bounds by construction);
            # their K/V is dropped and their logits are never read
            positions = jnp.where(live, start + idx, 0)
            x = params["tok_emb"][tokens] + params["pos_emb"][positions]
            pages = jnp.where(
                live, pt[jnp.clip((start + idx) // page_size, 0,
                                  pt.shape[0] - 1)], num_pages)
            rows = (start + idx) % page_size
            k_out, v_out, ks_out, vs_out = [], [], [], []
            for li, blk in enumerate(params["blocks"]):
                hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
                q, k, v = self._qkv(blk, hn)
                q = constrain(q, mesh, None, tp_axis, None)
                k = constrain(k, mesh, None, tp_axis, None)
                v = constrain(v, mesh, None, tp_axis, None)
                ks = vs = None
                if kv_quant:
                    kp, ks = quantized_pool_write(
                        k_pools[li], k_scales[li], pages, rows, k,
                        pool_layout)
                    vp, vs = quantized_pool_write(
                        v_pools[li], v_scales[li], pages, rows, v,
                        pool_layout)
                    if scale_spec is not None:
                        ks = constrain(ks, mesh, *scale_spec)
                        vs = constrain(vs, mesh, *scale_spec)
                    ks_out.append(ks)
                    vs_out.append(vs)
                else:
                    kp = scatter_pool_update(
                        k_pools[li], pages, rows,
                        k.astype(k_pools[li].dtype), pool_layout,
                        mesh=mesh, tp_axis=tp_axis)
                    vp = scatter_pool_update(
                        v_pools[li], pages, rows,
                        v.astype(v_pools[li].dtype), pool_layout,
                        mesh=mesh, tp_axis=tp_axis)
                if pool_spec is not None:
                    kp = constrain(kp, mesh, *pool_spec)
                    vp = constrain(vp, mesh, *pool_spec)
                k_out.append(kp)
                v_out.append(vp)
                attn = decode_attention.chunk_prefill_attention(
                    q, kp, vp, pt, start, use_kernel=use_kernel,
                    layout=pool_layout, mesh=mesh, tp_axis=tp_axis,
                    k_scale=ks, v_scale=vs)
                x = x + rowmm(attn.reshape(c, self.d_model), blk["wo"])
                x = x + self._mlp_rowmm(
                    blk, _layer_norm(x, blk["ln2_s"], blk["ln2_b"]),
                    rowmm)
            last = jnp.take(x, length - 1, axis=0)[None]
            logits = (_layer_norm(last, params["ln_f_s"],
                                  params["ln_f_b"]) @ params["head"])[0]
            if kv_quant:
                return (constrain(logits, mesh), k_out, v_out, ks_out,
                        vs_out)
            return constrain(logits, mesh), k_out, v_out

        return step

    # ----------------------------- decode ----------------------------
    def decode(self, tokens, positions, attend):
        """tokens, positions: [B] ints.  attend(layer, q, k, v) performs
        paged attention (engine-owned KV).  Returns logits [B, V]."""
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        b = tokens.shape[0]
        x = self._embed(tokens, positions)
        for li, blk in enumerate(self.blocks):
            hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
            q, k, v = self._qkv(blk, hn)
            attn = jnp.asarray(attend(li, q, k, v))    # [B, H, D]
            x = x + attn.reshape(b, self.d_model) @ blk["wo"]
            x = x + self._mlp(blk, _layer_norm(x, blk["ln2_s"],
                                               blk["ln2_b"]))
        return self._logits(x)

    # -------------------------- fused decode --------------------------
    def decode_params(self):
        """The weights as a jit-traceable pytree — the `params` argument
        of the pure function `decode_step_fn` returns.  Passed as an
        argument (not closed over) so the fused executable doesn't bake
        the weights in as constants."""
        return {
            "tok_emb": self.tok_emb, "pos_emb": self.pos_emb,
            "blocks": self.blocks,
            "ln_f_s": self.ln_f_s, "ln_f_b": self.ln_f_b,
            "head": self.head,
        }

    def decode_param_specs(self, tp_axis):
        """PartitionSpec pytree matching decode_params(), sharding the
        per-layer projection weights over the HEAD axis (the Megatron
        column/row split, SNIPPETS.md [3]'s NamedSharding-over-model
        pattern):

        - wq/wk/wv ``[d, H*D]``: columns sharded (head-major reshape, so
          each device's column block IS its heads' projections);
        - wo ``[H*D, d]``: rows sharded — the contraction over the
          sharded axis yields partial sums, and XLA inserts the layer's
          allreduce exactly there;
        - MLP w1/b1 column-sharded, w2 row-sharded (second allreduce);
        - embeddings, layernorm scales, and the LM head replicated —
          activations between layers are replicated, so the final
          logits need NO collective of their own.
        """
        from jax.sharding import PartitionSpec as P

        col, row, rep = P(None, tp_axis), P(tp_axis, None), P()
        blk = {"ln1_s": rep, "ln1_b": rep,
               "wq": col, "wk": col, "wv": col, "wo": row,
               "ln2_s": rep, "ln2_b": rep,
               "w1": col, "b1": P(tp_axis), "w2": row, "b2": rep}
        return {"tok_emb": rep, "pos_emb": rep,
                "blocks": [dict(blk) for _ in self.blocks],
                "ln_f_s": rep, "ln_f_b": rep, "head": rep}

    def decode_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", greedy=False, mesh=None,
                       tp_axis=None, kv_quant=False,
                       quant_collectives=False):
        """Build the PURE whole-decode-step function the engine's fused
        path jits: embed -> L x (scatter-append K/V into the pools +
        paged decode attention) -> logits, in one traceable body.

            fn(params, tokens, positions, k_pools, v_pools,
               page_tables, lens) -> (out, k_pools', v_pools')

        tokens/positions: [B] int32 (B = padded batch bucket).
        k_pools/v_pools: length-L lists of pool arrays (donated by the
        caller; returned updated).  page_tables: [B, MP] int32 padded
        with page 0.  lens: [B] int32 — live token counts INCLUDING the
        token being decoded; 0 marks a DUMMY padding row, whose K/V
        write is routed to the out-of-range sentinel page `num_pages`
        (dropped by the scatter, mode="drop") and whose attention row is
        zero-length (masked to exact zeros).  `out` is logits [B, V], or
        argmax'd token ids [B] when greedy=True (the all-greedy batch
        fetches B ints instead of B x V floats).

        Per-position math is IDENTICAL to the eager decode()/attend()
        path — same helpers, same scatter semantics
        (kv_cache.scatter_pool_update), same attention reference — so
        fused-vs-eager differences are only whatever XLA whole-program
        fusion does to float association (why eager stays the CPU
        tier-1 default, docs/GENERATION.md).

        mesh / tp_axis: tensor-parallel sharding.  The body stays the
        same trace; sharding constraints pin the GSPMD solution the
        decode_param_specs layout implies — q/k/v (and the pool
        scatters) sharded over heads, pools pinned to the pool sharding
        so the donation chain round-trips, `out` pinned replicated so
        the engine's single host fetch is legal.  XLA inserts the two
        per-layer allreduces (after wo and w2) from the row-sharded
        contractions; nothing here issues a collective by hand — unless
        quant_collectives, which swaps those two matmuls for the
        explicit EQuARX-style quantized ring (_row_matmul).

        kv_quant: int8 pools — the per-layer [P, H] scale arrays join
        the donated state (``..., k_pools, v_pools, k_scales, v_scales,
        page_tables, lens``), writes quantize in-trace, attention
        dequantizes in-kernel."""
        from ..parallel.sharding_annotations import (constrain,
                                                     kv_pool_spec,
                                                     kv_scale_spec)
        from .kv_cache import scatter_pool_update
        from .quantized_kv import quantized_pool_write

        page_size = int(page_size)
        num_pages = int(num_pages)
        pool_spec = (kv_pool_spec(pool_layout, tp_axis)
                     if mesh is not None else None)
        scale_spec = (kv_scale_spec(tp_axis)
                      if mesh is not None else None)
        rowmm = self._row_matmul(mesh, tp_axis, quant_collectives)

        def step(params, tokens, positions, k_pools, v_pools, *rest):
            if kv_quant:
                k_scales, v_scales, page_tables, lens = rest
            else:
                page_tables, lens = rest
            tokens = jnp.asarray(tokens, jnp.int32)
            positions = jnp.asarray(positions, jnp.int32)
            pt = jnp.asarray(page_tables, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            b = tokens.shape[0]
            # no host-side bounds check in-trace: the engine guarantees
            # positions < max_positions (enforced typed at submit)
            x = params["tok_emb"][tokens] + params["pos_emb"][positions]
            # dummy rows (lens == 0) write to the sentinel page, which
            # the drop-mode scatter discards on device
            pages = jnp.where(
                lens > 0,
                pt[jnp.arange(b), positions // page_size], num_pages)
            rows = positions % page_size
            k_out, v_out, ks_out, vs_out = [], [], [], []
            for li, blk in enumerate(params["blocks"]):
                hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
                q, k, v = self._qkv(blk, hn)
                # head-sharded activations: each device projects and
                # attends over ITS heads only; the scatter below is then
                # fully local (sharded update into the sharded pool)
                q = constrain(q, mesh, None, tp_axis, None)
                k = constrain(k, mesh, None, tp_axis, None)
                v = constrain(v, mesh, None, tp_axis, None)
                ks = vs = None
                if kv_quant:
                    kp, ks = quantized_pool_write(
                        k_pools[li], k_scales[li], pages, rows, k,
                        pool_layout)
                    vp, vs = quantized_pool_write(
                        v_pools[li], v_scales[li], pages, rows, v,
                        pool_layout)
                    if scale_spec is not None:
                        ks = constrain(ks, mesh, *scale_spec)
                        vs = constrain(vs, mesh, *scale_spec)
                    ks_out.append(ks)
                    vs_out.append(vs)
                else:
                    kp = scatter_pool_update(
                        k_pools[li], pages, rows,
                        k.astype(k_pools[li].dtype), pool_layout,
                        mesh=mesh, tp_axis=tp_axis)
                    vp = scatter_pool_update(
                        v_pools[li], pages, rows,
                        v.astype(v_pools[li].dtype), pool_layout,
                        mesh=mesh, tp_axis=tp_axis)
                if pool_spec is not None:
                    kp = constrain(kp, mesh, *pool_spec)
                    vp = constrain(vp, mesh, *pool_spec)
                k_out.append(kp)
                v_out.append(vp)
                attn = decode_attention.paged_decode_attention(
                    q, kp, vp, pt, lens, use_kernel=use_kernel,
                    layout=pool_layout, mesh=mesh, tp_axis=tp_axis,
                    k_scale=ks, v_scale=vs)
                x = x + rowmm(attn.reshape(b, self.d_model), blk["wo"])
                x = x + self._mlp_rowmm(
                    blk, _layer_norm(x, blk["ln2_s"], blk["ln2_b"]),
                    rowmm)
            logits = _layer_norm(x, params["ln_f_s"],
                                 params["ln_f_b"]) @ params["head"]
            out = (jnp.argmax(logits, axis=-1).astype(jnp.int32)
                   if greedy else logits)
            # replicated output: the engine fetches it in ONE host sync,
            # which a sharded-out array would turn into a cross-device
            # gather on the host's side of the fence
            out = constrain(out, mesh)  # bare spec == fully replicated
            if kv_quant:
                return out, k_out, v_out, ks_out, vs_out
            return out, k_out, v_out

        return step

    # -------------------------- ragged step ---------------------------
    def _ragged_core_fn(self, use_kernel=False, pool_layout="token",
                        mesh=None, tp_axis=None, kv_quant=False,
                        quant_collectives=False):
        """Build the shared RAGGED LAYER STACK: embed -> L x (scatter
        K/V into the pools + ragged paged attention + MLP) -> hidden
        states, over one packed token axis.

        Both ragged entry points run exactly this body —
        `ragged_step_fn` (one engine step per dispatch) and
        `ragged_loop_fn` (N steps per dispatch, the host-free decode
        loop) — so the loop's per-iteration math IS the single-step
        math: same ops in the same order, the property the
        N-steps-vs-N-dispatches token-identity oracle rests on.

            core(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, k_pools, v_pools, k_scales,
                 v_scales) -> (x [T, d], k_pools', v_pools', ks', vs')

        k_scales/v_scales are None unless kv_quant (ks'/vs' are []
        then); every array contract matches ragged_step_fn's docstring.
        """
        from ..parallel.sharding_annotations import (constrain,
                                                     kv_pool_spec,
                                                     kv_scale_spec)
        from .kv_cache import scatter_pool_update
        from .quantized_kv import quantized_pool_write

        pool_spec = (kv_pool_spec(pool_layout, tp_axis)
                     if mesh is not None else None)
        scale_spec = (kv_scale_spec(tp_axis)
                      if mesh is not None else None)
        rowmm = self._row_matmul(mesh, tp_axis, quant_collectives)

        def core(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, k_pools, v_pools, k_scales,
                 v_scales):
            tokens = jnp.asarray(tokens, jnp.int32)
            positions = jnp.asarray(positions, jnp.int32)
            pages = jnp.asarray(pages, jnp.int32)
            rows = jnp.asarray(rows, jnp.int32)
            pt = jnp.asarray(page_tables, jnp.int32)
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
            t = tokens.shape[0]
            # inert slots embed token 0 at position 0 (in bounds by
            # construction); their K/V rides the sentinel page and their
            # attention rows belong to no descriptor (exact zeros)
            with step_scope("embed"):
                x = params["tok_emb"][tokens] + params["pos_emb"][positions]
            # the kernel's grid follows the descriptors, not the layer:
            # built once here (once an iteration inside the host-free
            # loop, whose descriptors change), shared by every layer
            with step_scope("attention"):
                work = decode_attention.ragged_work_list(
                    pt, starts, lens, kv_lens,
                    k_pools[0].shape[2 if pool_layout == "kernel" else 1],
                    t, use_kernel=use_kernel)
            k_out, v_out, ks_out, vs_out = [], [], [], []
            for li, blk in enumerate(params["blocks"]):
                with step_scope("attention"):
                    hn = _layer_norm(x, blk["ln1_s"], blk["ln1_b"])
                    q, k, v = self._qkv(blk, hn)
                    q = constrain(q, mesh, None, tp_axis, None)
                    k = constrain(k, mesh, None, tp_axis, None)
                    v = constrain(v, mesh, None, tp_axis, None)
                    ks = vs = None
                    if kv_quant:
                        kp, ks = quantized_pool_write(
                            k_pools[li], k_scales[li], pages, rows, k,
                            pool_layout)
                        vp, vs = quantized_pool_write(
                            v_pools[li], v_scales[li], pages, rows, v,
                            pool_layout)
                        if scale_spec is not None:
                            ks = constrain(ks, mesh, *scale_spec)
                            vs = constrain(vs, mesh, *scale_spec)
                        ks_out.append(ks)
                        vs_out.append(vs)
                    else:
                        kp = scatter_pool_update(
                            k_pools[li], pages, rows,
                            k.astype(k_pools[li].dtype), pool_layout,
                            mesh=mesh, tp_axis=tp_axis)
                        vp = scatter_pool_update(
                            v_pools[li], pages, rows,
                            v.astype(v_pools[li].dtype), pool_layout,
                            mesh=mesh, tp_axis=tp_axis)
                    if pool_spec is not None:
                        kp = constrain(kp, mesh, *pool_spec)
                        vp = constrain(vp, mesh, *pool_spec)
                    k_out.append(kp)
                    v_out.append(vp)
                    attn = decode_attention.ragged_paged_attention(
                        q, kp, vp, pt, starts, lens, kv_lens,
                        use_kernel=use_kernel, layout=pool_layout,
                        mesh=mesh, tp_axis=tp_axis, k_scale=ks, v_scale=vs,
                        work=work)
                    x = x + rowmm(attn.reshape(t, self.d_model),
                                  blk["wo"])
                with step_scope("mlp"):
                    x = x + self._mlp_rowmm(
                        blk, _layer_norm(x, blk["ln2_s"], blk["ln2_b"]),
                        rowmm)
            return x, k_out, v_out, ks_out, vs_out

        return core

    def ragged_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", mesh=None, tp_axis=None,
                       kv_quant=False, quant_collectives=False,
                       spec_tokens=0):
        """Build the PURE mixed-batch RAGGED step function the engine's
        one-dispatch-per-step path jits (fused.RaggedStep)::

            fn(params, tokens, positions, pages, rows, page_tables,
               starts, lens, kv_lens, k_pools, v_pools)
              -> ((token_ids [S], logits [S, V]), k_pools', v_pools')

        tokens/positions: [T] int32 — the step's PACKED token axis:
        every decode sequence's single new token followed by the
        prefill chunk's tokens, no dummy rows between them (slots past
        the packed count are inert padding of the fixed axis).  pages/
        rows: [T] int32 scatter targets, host-computed from the page
        tables; inert slots carry the OOB sentinel page `num_pages`
        (dropped in-trace, mode="drop" — exactly the fused-decode dummy
        -row contract).  page_tables: [S, MP] int32.  starts/lens/
        kv_lens: [S] int32 descriptors — descriptor s owns packed rows
        [starts[s], starts[s]+lens[s]) and has kv_lens[s] cache-
        resident tokens after this step's writes; lens == 0 marks an
        unused descriptor.

        One trace serves decode-only, chunk-only, and combined steps,
        greedy and stochastic alike: logits are taken at each
        descriptor's LAST packed row (a decode row's own position; a
        chunk's last position — the first-token logits when the chunk
        completes its prompt) and BOTH the [S] on-device argmax ids and
        the [S, V] logits come back unmaterialized; the engine fetches
        whichever its samplers need (ids for all-greedy, logits
        otherwise, nothing for a mid-prompt chunk-only step).

        mesh / tp_axis: the decode_step_fn sharding contract — q/k/v
        and the pool scatters sharded over heads, pools pinned through
        the donation chain, ids/logits pinned replicated for the single
        host fetch.

        kv_quant / quant_collectives: exactly the decode_step_fn
        contract — scale arrays after the pools
        (``..., k_pools, v_pools, k_scales, v_scales``), quantized
        in-trace writes, in-kernel dequant; and the two row-sharded
        matmuls through the quantized ring when asked.

        spec_tokens > 0 grows the SPECULATIVE accept/reject epilogue
        (generation/speculation.py): a speculating greedy row packs as
        an ordinary ``len = 1 + k`` descriptor (its committed token
        followed by k draft tokens — the attention math is untouched;
        a verify row IS a chunk-shaped row), and the epilogue gathers
        each descriptor's rows start..start+k plus the S sample rows
        BEFORE the head matmul (its head cost is O(S * k), never
        O(T)), takes their per-row argmax, counts each descriptor's
        accepted draft prefix (verify_accept: row start+j's argmax vs
        the shifted draft id at row start+j+1), and takes the bonus
        token at the first unaccepted row.  The
        two unmaterialized outputs become

            ints [S, 3] int32     — (last-row argmax id, accepted
                                     count, bonus id): the all-greedy
                                     single fetch
            logits_aug [S, V + 3] — the last-row logits with the same
                                     three columns appended as floats
                                     (ids are exact in f32 far past
                                     any practical vocab): the mixed-
                                     batch single fetch

        so the host still syncs at most ONE array per step whatever
        the sampling mix.  spec_tokens shapes a [S, k] intermediate
        only — the compile menu stays one executable per pages bucket,
        exactly as without speculation."""
        from ..parallel.sharding_annotations import constrain

        core = self._ragged_core_fn(
            use_kernel=use_kernel, pool_layout=pool_layout, mesh=mesh,
            tp_axis=tp_axis, kv_quant=kv_quant,
            quant_collectives=quant_collectives)

        def step(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, k_pools, v_pools, *rest):
            if kv_quant:
                k_scales, v_scales = rest
            else:
                k_scales = v_scales = None
            tokens = jnp.asarray(tokens, jnp.int32)
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            t = tokens.shape[0]
            x, k_out, v_out, ks_out, vs_out = core(
                params, tokens, positions, pages, rows, page_tables,
                starts, lens, kv_lens, k_pools, v_pools, k_scales,
                v_scales)
            with step_scope("head"):
                # per-descriptor sampling rows: the last packed row each
                # descriptor owns (padding descriptors read row 0 — garbage
                # the engine never fetches a token from)
                sample_rows = jnp.clip(starts + lens - 1, 0, t - 1)
                if spec_tokens:
                    from .speculation import verify_accept

                    # the verify epilogue needs argmax at each
                    # descriptor's rows start..start+k (row start+j
                    # predicts the token drafted at row start+j+1) plus
                    # the S sample-row logits — gather those S*(k+2) rows
                    # BEFORE the head matmul, so the epilogue's head cost
                    # is O(S * k), never O(T) (chunk rows past the window
                    # and inert padding can't be read by it anyway)
                    s_n = starts.shape[0]
                    kk = int(spec_tokens)
                    vrows = jnp.clip(
                        starts[:, None]
                        + jnp.arange(kk + 1, dtype=jnp.int32)[None, :],
                        0, t - 1)                            # [S, k + 1]
                    gathered = jnp.concatenate(
                        [x[vrows.reshape(-1)], x[sample_rows]], axis=0)
                    heads = (_layer_norm(gathered, params["ln_f_s"],
                                         params["ln_f_b"])
                             @ params["head"])
                    amax_rows = jnp.argmax(
                        heads[:s_n * (kk + 1)],
                        axis=-1).astype(jnp.int32).reshape(s_n, kk + 1)
                    logits = heads[s_n * (kk + 1):]          # [S, V]
                    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    accepted, bonus = verify_accept(
                        amax_rows, tokens, starts, lens, kk, np_mod=jnp)
                    ints = jnp.stack([ids, accepted, bonus],
                                     axis=1)                     # [S, 3]
                    # one fetchable array per sampling mix: ints for the
                    # all-greedy step, logits with the int columns appended
                    # for a mixed batch — either way ONE host sync
                    aug = jnp.concatenate(
                        [logits, ints.astype(logits.dtype)], axis=1)
                    ints = constrain(ints, mesh)
                    aug = constrain(aug, mesh)
                    if kv_quant:
                        return (ints, aug), k_out, v_out, ks_out, vs_out
                    return (ints, aug), k_out, v_out
                xs = x[sample_rows]                              # [S, d]
                logits = (_layer_norm(xs, params["ln_f_s"],
                                      params["ln_f_b"]) @ params["head"])
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # replicated outputs: the engine's single host fetch reads
                # ONE of them without a cross-device gather
                ids = constrain(ids, mesh)
                logits = constrain(logits, mesh)
                if kv_quant:
                    return (ids, logits), k_out, v_out, ks_out, vs_out
                return (ids, logits), k_out, v_out

        return step

    # ------------------------ host-free decode loop --------------------
    def ragged_loop_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", mesh=None, tp_axis=None,
                       kv_quant=False, quant_collectives=False,
                       spec_tokens=0, loop_steps=2, max_stop_ids=8,
                       max_stop_seqs=4, max_stop_len=8):
        """Build the HOST-FREE DECODE LOOP function: N ragged decode
        steps fused into one dispatch (fused.LoopedRaggedStep), with
        on-device sampling, on-device stop matching, per-row done masks
        with early exit, and ONE fetchable output for the whole loop
        (docs/GENERATION.md "Host-free decode loop")::

            fn(params, cur_tok, cur_pos, live, page_tables, temps,
               top_ks, top_ps, seeds, counters, remaining, stop_ids,
               stop_seqs, stop_seq_lens, tail, drafts, draft_lens,
               k_pools, v_pools[, k_scales, v_scales])
              -> (out [S, N + K + 6] int32, pools'...)

        Decode-only by construction: descriptor s statically owns
        packed rows ``[s*(1+K), s*(1+K) + len_s)`` (K = spec_tokens),
        so the packed axis is ``S * (1 + K)`` and `starts` never moves
        — prefill chunks and admissions happen at LOOP BOUNDARIES
        (engine._step_ragged), which is what makes N a
        latency-vs-admission knob rather than a correctness concern.

        Inputs, all length-S unless noted: cur_tok/cur_pos — the last
        committed token and its position (== resident KV length: its
        K/V is written by the FIRST iteration, exactly the single-step
        protocol); live — 1 for occupied slots; temps/top_ks/top_ps/
        seeds/counters — the per-row sampling menu and SampleStream
        state (temps == 0 marks a greedy row; stochastic rows consume
        exactly one hash-uniform draw per live iteration, the SAME key
        sequence the host sampler consumes); remaining — max_new_tokens
        minus tokens generated (>= 1 for live rows); stop_ids [S, MS]
        (pad -1), stop_seqs [S, NS, LS] right-aligned (pad -1) with
        stop_seq_lens [S, NS], tail [S, LS - 1] — the last generated
        tokens right-aligned (pad -1), the suffix-match window; drafts
        [S, max(K, 1)] / draft_lens — ngram drafts verified at
        ITERATION 0 ONLY (greedy token streams are draft-independent,
        so drafting only at the boundary is exact vs the
        draft-every-step N=1 oracle; later iterations overwrite any
        rejected-draft positions, and the host truncates to final_pos
        after the fetch).

        Per iteration, the body runs the SHARED ragged core
        (_ragged_core_fn — the same trace ragged_step_fn runs), then
        an epilogue that mirrors the engine's host gate order
        (_apply_token) token for token: verify drafts (verify_accept),
        sample stochastic rows on device
        (sampling.sample_tokens_device's math), then for each of the
        up-to-(K+1) candidate tokens — stop-token membership, stop-
        sequence suffix match (the completing token is withheld),
        append (stream + tail shift), length finish (that token IS
        streamed).  Rows finish with code 1 (stop) or 2 (length); the
        loop exits early when every live row has finished.

        The single output packs, per row: N + K emitted-token columns,
        then n_emit, finish code, finish_iter (-1 if unfinished),
        final_pos (position of the last committed token — the host's
        truncate target), counter_after, iters_run — token ids +
        done/stop metadata in ONE [S, N+K+6] host fetch per N steps.
        Pools (and int8 scales) ride the lax.while_loop carry on the
        existing donation chain.
        """
        import jax.lax as lax

        from ..parallel.sharding_annotations import constrain
        from . import sampling as _sampling
        from .speculation import verify_accept

        page_size = int(page_size)
        num_pages = int(num_pages)
        n_steps = int(loop_steps)
        kk = int(spec_tokens)
        kd = max(kk, 1)
        ms = int(max_stop_ids)
        ns = int(max_stop_seqs)
        ls = max(int(max_stop_len), 1)
        if n_steps < 1:
            raise ValueError(f"loop_steps must be >= 1, got {loop_steps}")
        max_emit = n_steps + kk
        core = self._ragged_core_fn(
            use_kernel=use_kernel, pool_layout=pool_layout, mesh=mesh,
            tp_axis=tp_axis, kv_quant=kv_quant,
            quant_collectives=quant_collectives)
        max_pos = self.max_positions

        def fn(params, cur_tok, cur_pos, live, page_tables, temps,
               top_ks, top_ps, seeds, counters, remaining, stop_ids,
               stop_seqs, stop_seq_lens, tail, drafts, draft_lens,
               k_pools, v_pools, *rest):
            if kv_quant:
                k_scales, v_scales = rest
            else:
                k_scales = v_scales = None
            cur_tok = jnp.asarray(cur_tok, jnp.int32)
            cur_pos = jnp.asarray(cur_pos, jnp.int32)
            live = jnp.asarray(live, jnp.int32)
            pt = jnp.asarray(page_tables, jnp.int32)
            temps = jnp.asarray(temps, jnp.float32)
            top_ks = jnp.asarray(top_ks, jnp.int32)
            top_ps = jnp.asarray(top_ps, jnp.float32)
            seeds = jnp.asarray(seeds, jnp.int32)
            counters = jnp.asarray(counters, jnp.int32)
            remaining = jnp.asarray(remaining, jnp.int32)
            stop_ids = jnp.asarray(stop_ids, jnp.int32)
            stop_seqs = jnp.asarray(stop_seqs, jnp.int32)
            stop_seq_lens = jnp.asarray(stop_seq_lens, jnp.int32)
            tail = jnp.asarray(tail, jnp.int32)
            drafts = jnp.asarray(drafts, jnp.int32)
            draft_lens = jnp.asarray(draft_lens, jnp.int32)
            s = cur_tok.shape[0]
            offs = jnp.arange(1 + kk, dtype=jnp.int32)          # [1+K]
            starts = jnp.arange(s, dtype=jnp.int32) * (1 + kk)
            greedy_row = temps <= 0.0
            row_ix = jnp.arange(s, dtype=jnp.int32)

            def body(carry):
                (it, cur_tok, cur_pos, finish, finish_iter, n_emit,
                 remaining, counters, tail, emitted, k_po, v_po, k_sc,
                 v_sc) = carry
                act0 = (live > 0) & (finish == 0)
                # iteration 0 verifies the host's ngram drafts; later
                # iterations are plain single-token rows (greedy
                # streams are draft-independent, so this is exact)
                dlen = jnp.where((it == 0) & act0, draft_lens, 0)
                len_s = jnp.where(act0, 1 + dlen, 0)
                valid = offs[None, :] < len_s[:, None]        # [S,1+K]
                tok_grid = (jnp.concatenate(
                    [cur_tok[:, None], drafts[:, :kk]], axis=1)
                    if kk else cur_tok[:, None])
                pos_grid = cur_pos[:, None] + offs[None, :]
                tokens_p = jnp.where(valid, tok_grid, 0).reshape(-1)
                positions_p = jnp.where(
                    valid, jnp.clip(pos_grid, 0, max_pos - 1),
                    0).reshape(-1)
                page_ix = jnp.clip(pos_grid // page_size, 0,
                                   pt.shape[1] - 1)
                pages_p = jnp.where(
                    valid, jnp.take_along_axis(pt, page_ix, axis=1),
                    num_pages).reshape(-1)
                rows_p = jnp.where(valid, pos_grid % page_size,
                                   0).reshape(-1)
                kv_lens = jnp.where(act0, cur_pos + 1 + dlen, 0)
                x, k_po, v_po, k_sc, v_sc = core(
                    params, tokens_p, positions_p, pages_p, rows_p,
                    pt, starts, len_s, kv_lens, list(k_po), list(v_po),
                    list(k_sc) if kv_quant else None,
                    list(v_sc) if kv_quant else None)
                t = tokens_p.shape[0]
                # verify window + sample rows through ONE head matmul
                # (the ragged_step_fn spec-epilogue shape: O(S*K) head
                # cost, never O(T))
                sample_rows = jnp.clip(starts + len_s - 1, 0, t - 1)
                vrows = jnp.clip(starts[:, None] + offs[None, :],
                                 0, t - 1)                    # [S,1+K]
                gathered = jnp.concatenate(
                    [x[vrows.reshape(-1)], x[sample_rows]], axis=0)
                heads = (_layer_norm(gathered, params["ln_f_s"],
                                     params["ln_f_b"])
                         @ params["head"])
                amax_rows = jnp.argmax(
                    heads[:s * (1 + kk)],
                    axis=-1).astype(jnp.int32).reshape(s, 1 + kk)
                logits = heads[s * (1 + kk):]                 # [S, V]
                accepted, bonus = verify_accept(
                    amax_rows, tokens_p, starts, len_s, kk, np_mod=jnp)
                # on-device sampling: the host sampler's exact f32
                # formula over the same hash-uniform key sequence;
                # greedy rows consume no draw
                sampled, ctr_next = _sampling.sample_tokens_device(
                    logits, temps, top_ks, top_ps, seeds, counters,
                    jnp_mod=jnp)
                counters = jnp.where(act0, ctr_next, counters)
                final_tok = jnp.where(greedy_row, bonus, sampled)
                # stream the accepted drafts then the final token
                # through the engine's exact _apply_token gate order:
                # stop-id -> stop-seq (token withheld) -> append ->
                # length (token streamed)
                for j in range(kk + 1):
                    tok = (jnp.where(j < accepted, drafts[:, min(j, kd - 1)],
                                     final_tok)
                           if kk else final_tok)
                    emit_ok = act0 & (finish == 0) & (j <= accepted)
                    hit_id = jnp.any(tok[:, None] == stop_ids, axis=1)
                    cand = jnp.concatenate([tail, tok[:, None]],
                                           axis=1)            # [S, LS]
                    seq_eq = ((stop_seqs == -1)
                              | (cand[:, None, :] == stop_seqs))
                    hit_seq = jnp.any(
                        jnp.all(seq_eq, axis=2) & (stop_seq_lens > 0),
                        axis=1)
                    stop_hit = emit_ok & (hit_id | hit_seq)
                    appended = emit_ok & ~stop_hit
                    col = jnp.clip(n_emit, 0, max_emit - 1)
                    old = emitted[row_ix, col]
                    emitted = emitted.at[row_ix, col].set(
                        jnp.where(appended, tok, old))
                    n_emit = n_emit + appended.astype(jnp.int32)
                    tail = jnp.where(
                        appended[:, None],
                        jnp.concatenate([tail[:, 1:], tok[:, None]],
                                        axis=1), tail)
                    cur_tok = jnp.where(appended, tok, cur_tok)
                    cur_pos = jnp.where(appended, cur_pos + 1, cur_pos)
                    remaining = remaining - appended.astype(jnp.int32)
                    len_hit = appended & (remaining <= 0)
                    finish = jnp.where(
                        stop_hit, 1, jnp.where(len_hit, 2, finish))
                    done_now = (stop_hit | len_hit) & (finish_iter < 0)
                    finish_iter = jnp.where(done_now, it, finish_iter)
                return (it + 1, cur_tok, cur_pos, finish, finish_iter,
                        n_emit, remaining, counters, tail, emitted,
                        tuple(k_po), tuple(v_po), tuple(k_sc),
                        tuple(v_sc))

            def cond(carry):
                it, finish = carry[0], carry[3]
                return (it < n_steps) & jnp.any((live > 0)
                                                & (finish == 0))

            init = (jnp.int32(0), cur_tok, cur_pos,
                    jnp.zeros((s,), jnp.int32),
                    jnp.full((s,), -1, jnp.int32),
                    jnp.zeros((s,), jnp.int32), remaining, counters,
                    tail, jnp.full((s, max_emit), -1, jnp.int32),
                    tuple(k_pools), tuple(v_pools),
                    tuple(k_scales) if kv_quant else (),
                    tuple(v_scales) if kv_quant else ())
            (it, cur_tok, cur_pos, finish, finish_iter, n_emit,
             remaining, counters, tail, emitted, k_po, v_po, k_sc,
             v_sc) = lax.while_loop(cond, body, init)
            out = jnp.concatenate(
                [emitted, n_emit[:, None], finish[:, None],
                 finish_iter[:, None], cur_pos[:, None],
                 counters[:, None],
                 jnp.full((s, 1), 1, jnp.int32) * it], axis=1)
            # replicated output: ONE host fetch for the whole loop
            out = constrain(out, mesh)
            if kv_quant:
                return out, list(k_po), list(v_po), list(k_sc), \
                    list(v_sc)
            return out, list(k_po), list(v_po)

        return fn

    # ------------------------ reference decode ------------------------
    def greedy_reference(self, prompt, max_new_tokens, stop_tokens=()):
        """Naive sequential generation, FULL recompute each step (the
        oracle the engine is measured against): re-runs prefill over the
        whole prefix for every token, no KV cache at all."""
        stop = frozenset(int(s) for s in stop_tokens)
        tokens = [int(t) for t in prompt]
        out = []
        for _ in range(max_new_tokens):
            logits, _, _ = self.prefill(np.asarray(tokens, np.int32))
            nxt = int(np.argmax(np.asarray(logits)))
            if nxt in stop:
                break
            tokens.append(nxt)
            out.append(nxt)
        return out
