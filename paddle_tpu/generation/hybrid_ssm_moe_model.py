"""HybridSSMMoELM: state-space (Mamba-2) layers beside a few attention
layers, every layer with softmax-routed experts of which this chip may
hold a share, behind the engine's ragged step — the block kind
`granitemoehybrid` (IBM's Granite 4.0-H family).

    h_0     = embedding_multiplier * E[token]          E tied to the head
    h'      = h  + residual_multiplier * Mixer_l(rms(h;  g1))
    h''     = h' + residual_multiplier * (Experts_l(x) + Shared_l(x)),
              x = rms(h'; g2)
    logits  = rms(h_L; g_f) E^T / logits_scaling

    Attention: q -> H heads of D; k, v -> n heads of D; NO rotation and
               no other position signal; head h reads KV head h // (H/n);
               o_h = softmax(q_h . k * attention_multiplier, causal) v
    State-space, one group:
               [z | xBC | dt] = x W_in;  xBC_t = silu(b_c + sum_j w_c[j]
               * xBC_{t-(K-1)+j});  [x | B | C] = xBC;  dt_t =
               softplus(dt_t + dt_bias);  a_t = exp(-dt_t exp(A_log))
               S_t = a_t S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t
               + D x_t;  out = rms(y * silu(z); g_n) W_out
    Experts:   `moe.route(..., "softmax_topk")` over the router's whole
               width, `moe.expert_ffn` over the experts held
               (`experts_held`), beside a shared gated MLP

What a layer keeps between steps is told to the engine's pool by kind
(`kv_layer_kinds`): an attention layer (`full`) ONE row a token,
``[k_0 .. k_{n-1} | v_0 .. v_{n-1}]`` in pages (`kv_rows`, a
`kv_cache.HeadRows`); a state-space layer (`state`) nothing a token and,
for each decode SLOT, the last K - 1 rows of xBC before the convolution
and the state S (`kv_slot_state`, a `kv_cache.SlotState`), whatever the
context's length.  The step is told each descriptor's slot.  Inside it a
state-space layer has two forms that agree (`tests/
test_hybrid_ssm_moe.py` holds both to the reference's recurrence):

  `state_space/update`  descriptors of ONE row (a decode row): the
        recurrence's one step for every slot at once, elementwise over
        the whole state array, written back where a slot had a row;
  `state_space/scan`    descriptors of several rows (a prefill chunk):
        the chunked (SSD) form over blocks of `mamba_chunk_size` rows
        counted from the chunk's own start, the state carried from
        block to block and, through the slot, from chunk to chunk.

A descriptor whose first row is position 0 starts from a zero state and
a zero tail INSIDE the step, whatever its slot held: a slot's reuse and
a preemption's recompute need nothing from the host.  Rows of the packed
axis that belong to no descriptor touch no state.

`ragged_step_fn` / `decode_params` are the whole of the engine protocol
this model implements: the ragged step is the one path that serves it,
and what cannot hold for a recurrence (the prefix cache, speculation,
`loop_steps`, page export/import) is refused when the engine is built
(`engine._refuse_paths_off_the_ragged_step`).

Weights are seeded and drawn on the device in `dtype` (`blocks.
DeviceDraw`); matrix products accumulate in float32 and round to
`dtype`; `dt`, `a_t`, the state and its update, the norms, the router
and the logits are float32; the tail is kept in `dtype`.
"""
import math

import jax
import jax.numpy as jnp

from . import decode_attention
from .blocks import (HELD_STEP_COUNTERS, DeviceDraw, feed_forward,
                     feed_forward_scope, rms_norm, valid_rows)
from .fused import step_scope
from .kv_cache import HeadRows, SlotState

STATE, FULL = "state", "full"
# the published names of the two kinds of layer
_KINDS = {"mamba": STATE, "attention": FULL, STATE: STATE, FULL: FULL}
# what a step counts behind the experts' four: decode rows x state
# layers, chunk tokens x state layers, sequences started from zero
SSM_COUNTERS = tuple(f"generation.ssm_{name}" for name in (
    "rows_updated", "tokens_scanned", "state_starts"))


class HybridSSMMoELM:
    """The model.  Argument names are `LatentMoELM`'s wherever they
    mean the same; the configuration file says which published key each
    one is.  `n_routed_experts` is the experts HELD (whose weights the
    model has), `router_width` the experts its routers know and
    `experts_held` = (first, count) which of them the held ones are."""

    def __init__(self, vocab_size=256, hidden_size=64, num_layers=4,
                 num_heads=4, num_kv_heads=2, head_dim=16,
                 moe_intermediate_size=32, shared_intermediate_size=48,
                 n_routed_experts=8, num_experts_per_tok=2,
                 router_width=None, experts_held=None, layer_types=None,
                 mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                 mamba_d_conv=4, mamba_chunk_size=256,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=None, logits_scaling=16.0,
                 rms_norm_eps=1e-5, sliding_window=None,
                 max_positions=131072, dtype="bfloat16",
                 state_dtype="float32", seed=0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads do not divide")
        self.expert_width = int(moe_intermediate_size)
        self.shared_width = int(shared_intermediate_size)
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
        if layer_types is None:   # one attention layer in each four
            layer_types = ["attention" if li % 4 == 1 else "mamba"
                           for li in range(self.num_layers)]
        self.layer_kinds = tuple(_KINDS[kind] for kind in layer_types)
        if (len(self.layer_kinds) != self.num_layers
                or STATE not in self.layer_kinds):
            raise ValueError(
                f"{len(self.layer_kinds)} layer types for "
                f"{self.num_layers} layers, or no state-space layer")
        self.ssm_heads = int(mamba_n_heads)
        self.ssm_head_dim = int(mamba_d_head)
        self.ssm_state = int(mamba_d_state)
        self.conv_taps = int(mamba_d_conv)
        self.scan_block = int(mamba_chunk_size)
        self.d_inner = self.ssm_heads * self.ssm_head_dim
        self.conv_width = self.d_inner + 2 * self.ssm_state
        self.embed_scale = float(embedding_multiplier)
        self.residual = float(residual_multiplier)
        self.scale = (float(attention_multiplier) if attention_multiplier
                      else self.head_dim ** -0.5)
        self.logits_scaling = float(logits_scaling)
        self.eps = float(rms_norm_eps)
        self.max_positions = int(max_positions)
        if sliding_window is not None and int(
                sliding_window) < self.max_positions:
            raise ValueError(
                f"sliding_window {sliding_window}: no layer of this model "
                f"keeps a window; leave it out or at max_positions")
        self.dtype = jnp.dtype(dtype)
        self.state_dtype = jnp.dtype(state_dtype)
        self.seed = seed
        self.step_counters = HELD_STEP_COUNTERS + SSM_COUNTERS
        self.params = self._draw(int(seed))

    # ----------------------------- weights ---------------------------
    def _draw(self, seed):
        """Normals of 1/sqrt(fan-in) and gains of 1 + 0.1 n as the other
        models draw them; the recurrence's own parameters in the ranges
        Mamba-2 models are initialised from: A uniform in [-16, -1],
        softplus(dt_bias) log-uniform in [0.001, 0.1], D = 1, so that a
        head's decay exp(dt * A) lies between 0.2 and 0.999 a token."""
        draw = DeviceDraw(seed, self.dtype)
        w, gain = draw.w, draw.gain
        d, hd = self.d_model, self.head_dim
        q_width, kv_width = self.num_heads * hd, self.num_kv_heads * hd
        heads, f32 = self.ssm_heads, jnp.float32
        layers = []
        for kind in self.layer_kinds:
            layer = {"norm1": gain(d)}
            if kind == STATE:
                dt = jnp.exp(draw.uniform(heads, math.log(1e-3),
                                          math.log(1e-1)))
                layer.update({
                    "w_in": w(d, 2 * self.d_inner + 2 * self.ssm_state
                              + heads),
                    "conv_w": w(self.conv_taps, self.conv_width,
                                scale=self.conv_taps ** -0.5, dtype=f32),
                    "conv_b": w(self.conv_width, scale=0.1, dtype=f32),
                    # softplus(dt_bias) == dt
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(draw.uniform(heads, 1.0, 16.0)),
                    "D": jnp.ones((heads,), f32),
                    "norm_ssm": gain(self.d_inner),
                    "w_out": w(self.d_inner, d)})
            else:
                layer.update({"w_q": w(d, q_width), "w_k": w(d, kv_width),
                              "w_v": w(d, kv_width), "w_o": w(q_width, d)})
            layer.update({
                "norm2": gain(d),
                "w_router": w(d, self.router_width, dtype=f32),
                "experts_gate_up": w(self.n_experts, d,
                                     2 * self.expert_width),
                "experts_down": w(self.n_experts, self.expert_width, d),
                "shared_gate_up": w(d, 2 * self.shared_width),
                "shared_down": w(self.shared_width, d)})
            layers.append(layer)
        # the tied matrix is drawn as a head (1/sqrt(d)) over the
        # embedding's multiplier: drawn any larger, a token's own logit
        # (multiplier * |E|^2) stands far above all others and a random
        # model only echoes its input, which tests nothing behind it
        return {"embed": w(self.vocab_size, d,
                           scale=1.0 / (self.embed_scale * math.sqrt(d))),
                "layers": layers, "norm_f": gain(d)}

    def decode_params(self):
        """The weights as a pytree: an argument of the step, never a
        constant of it."""
        return self.params

    def kv_rows(self):
        """A token's cache row in an attention layer, for
        `DeviceKVPool(rows=...)`."""
        return HeadRows(self.num_kv_heads, self.head_dim, self.dtype)

    def kv_layer_kinds(self):
        """``(kinds, window)``: 'state' for a layer that keeps a
        recurrent state a slot and no pages (`kv_slot_state`), 'full'
        for an attention layer, whose pages the pool allocates; no
        layer keeps a window (0)."""
        return self.layer_kinds, 0

    def kv_slot_state(self):
        """What a state layer keeps for a decode slot, for
        `DeviceKVPool(state=...)`."""
        return SlotState(
            (self.conv_taps - 1, self.conv_width), self.dtype,
            (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
            self.state_dtype)

    def build_gauges(self):
        """What the engine stamps of this model beside its own gauges."""
        return {"moe_experts_held": self.experts_held[1],
                "moe_router_width": self.router_width}

    # ------------------------------ layers ---------------------------
    def _mm(self, a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(
            self.dtype)

    def _ein(self, spec, a, b):
        """A product of the scan: operands in `dtype`, float32 out."""
        return jnp.einsum(spec, a.astype(self.dtype), b.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    def _conv(self, lp, ext):
        """The causal convolution over ext [..., n + K - 1, C]: row i of
        the result sees ext rows i .. i + K - 1, the last its own."""
        n = ext.shape[-2] - self.conv_taps + 1
        acc = lp["conv_b"] + sum(
            lp["conv_w"][j] * ext[..., j:j + n, :].astype(jnp.float32)
            for j in range(self.conv_taps))
        return jax.nn.silu(acc).astype(self.dtype)

    def _split(self, xbc):
        """[x [..., heads, P] | B [..., N] | C [..., N]] of xBC."""
        x = xbc[..., :self.d_inner].reshape(
            xbc.shape[:-1] + (self.ssm_heads, self.ssm_head_dim))
        return (x, xbc[..., self.d_inner:self.d_inner + self.ssm_state],
                xbc[..., self.d_inner + self.ssm_state:])

    def _schedule(self, starts, lens, kv_lens, state_slots, n_slots):
        """What every state-space layer of a step reads of its
        descriptors, made once: for each slot the one row it updates (or
        none), and the descriptors of several rows, first in `order`.
        `n_slots`: the state arrays' rows, the last belonging to no
        sequence."""
        s = starts.shape[0]
        fresh = (kv_lens - lens == 0) & (lens > 0)
        chunked = lens > 1
        desc = jnp.full((n_slots,), -1, jnp.int32).at[
            jnp.where(lens > 0, state_slots, n_slots)].set(
                jnp.arange(s, dtype=jnp.int32), mode="drop")
        d = jnp.maximum(desc, 0)
        update = (desc >= 0) & (lens[d] == 1)
        return {
            "starts": starts, "lens": lens, "slots": state_slots,
            "update": update, "row": jnp.where(update, starts[d], 0),
            "slot_fresh": update & fresh[d], "fresh": fresh,
            "order": jnp.argsort(~chunked, stable=True).astype(jnp.int32),
            "chunks": jnp.sum(chunked.astype(jnp.int32)),
            "counts": jnp.stack([
                jnp.sum(update.astype(jnp.int32)),
                jnp.sum(jnp.where(chunked, lens, 0)),
                jnp.sum(fresh.astype(jnp.int32))])}

    def _update(self, lp, xbc, dt_raw, state, tail, sched):
        """The recurrence's one step for every slot that has a one-row
        descriptor.  Returns (y [slots, d_inner] float32, state', tail');
        slots without such a row keep what they had."""
        f32 = jnp.float32
        upd, fresh = sched["update"], sched["slot_fresh"]
        row = sched["row"]
        old = jnp.where(fresh[:, None, None], 0, tail)
        ext = jnp.concatenate([old, xbc[row][:, None]], axis=1)
        x, b, c = self._split(self._conv(lp, ext)[:, 0])
        x, b, c = x.astype(f32), b.astype(f32), c.astype(f32)
        dt = jax.nn.softplus(dt_raw[row] + lp["dt_bias"])       # [K, heads]
        decay = jnp.exp(-dt * jnp.exp(lp["A_log"]))
        before = jnp.where(fresh[:, None, None, None], 0, state.astype(f32))
        after = (decay[:, :, None, None] * before
                 + (dt[:, :, None] * x)[..., None] * b[:, None, None, :])
        y = (jnp.sum(after * c[:, None, None, :], axis=-1)
             + lp["D"][None, :, None] * x)
        state = jnp.where(upd[:, None, None, None],
                          after.astype(state.dtype), state)
        tail = jnp.where(upd[:, None, None], ext[:, 1:], tail)
        return y.reshape(y.shape[0], -1), state, tail

    def _scan_block(self, lp, xbc, dt_raw, first, running, at, left):
        """One block of the chunked form: rows `at` .. `at + Q - 1` of
        the packed axis, of which the first `left` belong to the chunk
        (the others neither decay nor add).  `first`: the K - 1 rows of
        xBC before the block; `running`: the state before it, float32.
        Returns (y [Q, d_inner] float32, the state after)."""
        f32, q = jnp.float32, self.scan_block
        t = xbc.shape[0]
        idx = jnp.clip(at + jnp.arange(q, dtype=jnp.int32), 0, t - 1)
        live = jnp.arange(q) < left
        x, b, c = self._split(self._conv(
            lp, jnp.concatenate([first, xbc[idx]], axis=0)))
        dt = jnp.where(live[:, None],
                       jax.nn.softplus(dt_raw[idx] + lp["dt_bias"]), 0.0)
        cum = jnp.cumsum(-dt * jnp.exp(lp["A_log"]), axis=0)   # [Q, heads]
        xdt = dt[:, :, None] * x.astype(f32)                    # [Q, h, P]
        # inside the block: y_t += sum_{s <= t} C_t.B_s e^(cum_t - cum_s) xdt_s
        later = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        weigh = jnp.exp(jnp.where(
            later[None], cum.T[:, :, None] - cum.T[:, None, :], -jnp.inf))
        scores = self._ein("tn,sn->ts", c, b)[None] * weigh    # [h, Q, Q]
        y = self._ein("hts,shp->thp", scores, xdt).reshape(q, -1)
        # from the state before the block, decayed to each row.  The
        # state is one [d_inner, N] matrix in both products: a product
        # with two free axes on a side comes back in an order of XLA's
        # choosing, and the whole state array is then copied to match
        flat = running.reshape(self.d_inner, -1)
        y = y + (self._ein("tn,mn->tm", c, flat)
                 * jnp.repeat(jnp.exp(cum), self.ssm_head_dim, axis=1))
        y = y + (lp["D"][None, :, None] * x.astype(f32)).reshape(q, -1)
        to_end = jnp.exp(cum[-1][None] - cum)                   # [Q, heads]
        flat = (jnp.repeat(jnp.exp(cum[-1]), self.ssm_head_dim)[:, None]
                * flat + self._ein(
                    "sm,sn->mn", (xdt * to_end[:, :, None]).reshape(q, -1),
                    b))
        running = flat.reshape(running.shape)
        return y, running

    def _scan(self, lp, xbc, dt_raw, y, state, tail, sched):
        """The chunked form over every descriptor of several rows, one
        after another, each in blocks counted from its own start.  `y`
        [T, d_inner] gains the chunks' rows; `state` and `tail` the
        slots' new contents."""
        starts, lens, slots = sched["starts"], sched["lens"], sched["slots"]
        q, taps = self.scan_block, self.conv_taps
        t = xbc.shape[0]

        def chunk(carry):
            i, y, state, tail = carry
            d = sched["order"][i]
            start, n, slot = starts[d], lens[d], slots[d]
            fresh = sched["fresh"][d]
            before = jnp.where(fresh, 0, tail[slot])            # [K-1, C]

            def block(inner):
                j, y, running = inner
                at = start + j * q
                # the rows before the block: the slot's tail before the
                # chunk's first block, the packed axis's own after
                back = jnp.clip(at - (taps - 1) + jnp.arange(taps - 1),
                                0, t - 1)
                first = jnp.where(j == 0, before, xbc[back])
                rows, running = self._scan_block(
                    lp, xbc, dt_raw, first, running, at, n - j * q)
                mine = jnp.arange(q) < n - j * q
                y = y.at[jnp.where(mine, at + jnp.arange(q), t)].set(
                    rows, mode="drop")
                return j + 1, y, running

            _, y, running = jax.lax.while_loop(
                lambda inner: inner[0] * q < n, block,
                (jnp.int32(0), y,
                 jnp.where(fresh, 0, state[slot].astype(jnp.float32))))
            # the chunk's last K - 1 rows, the old tail's where it is
            # shorter than that
            off = n - (taps - 1) + jnp.arange(taps - 1)
            last = jnp.where(
                (off >= 0)[:, None], xbc[jnp.clip(start + off, 0, t - 1)],
                before[jnp.clip(off + taps - 1, 0, taps - 2)])
            return (i + 1, y, state.at[slot].set(running.astype(state.dtype)),
                    tail.at[slot].set(last))

        _, y, state, tail = jax.lax.while_loop(
            lambda carry: carry[0] < sched["chunks"], chunk,
            (jnp.int32(0), y, state, tail))
        return y, state, tail

    def _state_space(self, lp, h, state, tail, sched):
        """The state-space mixer over the packed, normed rows h [T, d].
        Returns (out [T, d], state', tail')."""
        f32 = jnp.float32
        proj = jnp.dot(h, lp["w_in"], preferred_element_type=f32)
        z = proj[:, :self.d_inner]
        xbc = proj[:, self.d_inner:self.d_inner + self.conv_width].astype(
            self.dtype)
        dt_raw = proj[:, self.d_inner + self.conv_width:]
        t = h.shape[0]
        with jax.named_scope("update"):
            rows, state, tail = self._update(lp, xbc, dt_raw, state, tail,
                                             sched)
            y = jnp.zeros((t, self.d_inner), f32).at[
                jnp.where(sched["update"], sched["row"], t)].set(
                    rows, mode="drop")
        with jax.named_scope("scan"):
            y, state, tail = self._scan(lp, xbc, dt_raw, y, state, tail,
                                        sched)
        # the gate before the norm, the norm over all lanes
        gated = rms_norm(y * jax.nn.silu(z), lp["norm_ssm"], self.eps)
        return self._mm(gated.astype(self.dtype), lp["w_out"]), state, tail

    def _attention(self, lp, h, pool, write, attend):
        """The attention mixer: the rows' keys and values into the pool,
        then every head over its sequence's pages."""
        t = h.shape[0]
        q = self._mm(h, lp["w_q"]).reshape(t, self.num_heads, self.head_dim)
        parts = [self._mm(h, lp["w_k"]), self._mm(h, lp["w_v"])]
        spare = pool.shape[-1] - 2 * parts[0].shape[-1]
        if spare:       # a row is whole 128-lane registers wide
            parts.append(jnp.zeros((t, spare), self.dtype))
        pool = pool.at[write].set(jnp.concatenate(parts, axis=-1),
                                  mode="drop")
        o = attend(q, pool)
        return self._mm(o.reshape(t, -1).astype(self.dtype), lp["w_o"]), pool

    # --------------------------- the ragged step ---------------------
    def ragged_step_fn(self, page_size, num_pages, use_kernel=False,
                       pool_layout="token", interpret=None):
        """The pure mixed-batch step `fused.RaggedStep` jits, over a row
        cache with state layers:

            fn(params, tokens, positions, pages, rows, page_tables,
               starts, lens, kv_lens, state_slots, pools, tails)
              -> ((token_ids [S], logits [S, V] f32, counters [7]),
                  pools', tails')

        The packed axis, the descriptors and the sampling rows are
        `TinyCausalLM.ragged_step_fn`'s; `state_slots` [S] is each
        descriptor's decode slot.  `pools` has an array a layer: an
        attention layer's row pool, a state layer's states
        ``[slots + 1, heads, P, N]``; `tails` one ``[slots + 1, K - 1,
        C]`` a state layer, in order.  `counters` is `step_counters`:
        the experts' four summed over the layers, then the three of
        `SSM_COUNTERS`."""
        del num_pages, pool_layout
        n_state = self.layer_kinds.count(STATE)

        def step(params, tokens, positions, pages, rows, page_tables,
                 starts, lens, kv_lens, state_slots, pools, tails):
            del positions         # no layer has a position signal
            tokens = jnp.asarray(tokens, jnp.int32)
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
            state_slots = jnp.asarray(state_slots, jnp.int32)
            table = jnp.asarray(page_tables, jnp.int32)
            write = (jnp.asarray(pages, jnp.int32),
                     jnp.asarray(rows, jnp.int32))
            t = tokens.shape[0]
            valid = valid_rows(starts, lens, t)
            with step_scope("embed"):
                x = (params["embed"][tokens].astype(jnp.float32)
                     * self.embed_scale).astype(self.dtype)
            with step_scope("attention"):
                work = decode_attention.gqa_work_lists(
                    starts, lens, kv_lens, page_size, table.shape[1], t,
                    None, use_kernel)[FULL]
            with step_scope("state_space"):
                sched = self._schedule(starts, lens, kv_lens, state_slots,
                                       tails[0].shape[0])

            def attend(q, pool):
                return decode_attention.gqa_ragged_attention(
                    q, pool, table, starts, lens, kv_lens, self.scale,
                    self.num_kv_heads, None, use_kernel,
                    interpret=interpret, work=work)

            pools_out, tails_out, tails = [], [], iter(tails)
            with step_scope("head"):
                moe_counts = jnp.zeros((len(HELD_STEP_COUNTERS),),
                                       jnp.int32)
            for lp, pool, kind in zip(params["layers"], pools,
                                      self.layer_kinds):
                if kind == STATE:
                    with step_scope("state_space"):
                        mixed, pool, tail = self._state_space(
                            lp, rms_norm(x, lp["norm1"], self.eps), pool,
                            next(tails), sched)
                        x = self._residual(x, mixed)
                    tails_out.append(tail)
                else:
                    with step_scope("attention"), jax.named_scope(FULL):
                        mixed, pool = self._attention(
                            lp, rms_norm(x, lp["norm1"], self.eps), pool,
                            write, attend)
                        x = self._residual(x, mixed)
                pools_out.append(pool)
                with feed_forward_scope(lp):
                    y, stats = feed_forward(
                        lp, rms_norm(x, lp["norm2"], self.eps), valid,
                        self.top_k, None, "softmax_topk", self.experts_held)
                with step_scope("head"):
                    moe_counts = moe_counts + stats
                with feed_forward_scope(lp):
                    x = self._residual(x, y)
            with step_scope("head"):
                sample_rows = jnp.clip(starts + lens - 1, 0, t - 1)
                logits = jax.lax.dot_general(
                    rms_norm(x[sample_rows], params["norm_f"], self.eps),
                    params["embed"], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) / self.logits_scaling
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                counters = jnp.concatenate([
                    moe_counts, sched["counts"] * jnp.asarray(
                        [n_state, n_state, 1], jnp.int32)])
            return (ids, logits, counters), pools_out, tails_out

        return step

    def _residual(self, x, y):
        return (x.astype(jnp.float32)
                + self.residual * y.astype(jnp.float32)).astype(self.dtype)
