"""The serving side's expert layer: top-k routing in one of two scoring
forms and a DROPLESS grouped product over the experts the layer holds.

    "sigmoid_bias"   s   = sigmoid(x W_r)       float32, a score an expert
                     pick the k experts with the largest s + b   (b: the
                          router's correction biases; they choose, they
                          do not weigh)
                     w_i = scale * s_i / sum_chosen s_j
    "softmax_topk"   l   = x W_r                float32 logits
                     pick the k experts with the largest l
                     w   = softmax over the chosen k logits (no sigmoid,
                          no bias, no scale)
    y = sum_i w_i Expert_i(x)    over the chosen experts THE LAYER HOLDS

A layer holds every expert its router knows unless it is told its share
(`experts_held` = (first, count): experts first .. first + count - 1 of
the router's width, the others on other chips).  A pick of an expert
held elsewhere adds nothing here and is counted: the chip that holds
that expert adds its part after the exchange, which this module does
not have.

No capacity and nothing dropped: the step's (token, expert) pairs are
sorted by expert and each expert multiplies exactly its own rows
(`jax.lax.ragged_dot`, which XLA:TPU lowers to a grouped-matmul kernel
that reads an expert's weights only if it has rows).  Shapes follow the
padded row count alone, so a step's batch, chunk and routing never
retrace.  Rows of the packed axis that belong to no sequence, and picks
of experts held elsewhere, are sorted past every group: they touch no
expert and come back 0.

The pairs lie choice-major (pair j * T + t is token t's j-th choice),
so the way home is a gather of the float32 products into [k * T, d],
which is [k, T, d] as it lies, weighed and summed over k.  Token-major,
[T, k, d] would put k on the second-minor axis, which the TPU tiles by
8: a k that is no multiple of 8 (Granite's 10, GLM's 4) would have XLA
relay the products out, padded, in every expert layer.

`distributed/fleet/meta_parallel/moe_layer.py` is the trainer's layer
(top-2, capacity dropping, one-hot dispatch): another thing.
"""
import jax
import jax.numpy as jnp

# what `expert_ffn` counts a layer, in this order: the (token, expert)
# pairs computed, the busiest expert's share of them, the experts that
# got any (all three of the experts HELD) and, by a layer told its
# share alone, the picks that went to experts held elsewhere
STATS = ("assignments", "max_expert", "experts_touched", "elsewhere")
SCORING = ("sigmoid_bias", "softmax_topk")


def route(x, w_router, bias, top_k, scaling, scoring="sigmoid_bias"):
    """x: [T, d] -> (experts [T, k] int32, weights [T, k] float32) by
    the scoring form `scoring` (the module's header; "softmax_topk"
    takes no `bias` and no `scaling`).  Scores in float32 at full
    precision whatever x's dtype: a router whose near-ties fall with the
    matmul's rounding picks other experts than the model it stands
    for."""
    if scoring == "softmax_topk":
        logits = jnp.dot(x.astype(jnp.float32), w_router,
                         precision="highest")
        chosen, experts = jax.lax.top_k(logits, top_k)
        return experts.astype(jnp.int32), jax.nn.softmax(chosen, axis=1)
    if scoring != "sigmoid_bias":
        raise ValueError(f"scoring {scoring!r}: one of {SCORING}")
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_router,
                               precision="highest"))
    _, experts = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    weights = scaling * chosen / jnp.sum(chosen, axis=1, keepdims=True)
    return experts.astype(jnp.int32), weights


def expert_ffn(x, experts, weights, valid, w_gate_up, w_down,
               experts_held=None):
    """The routed experts' part of the layer's output.

    x: [T, d]; experts/weights: [T, k] from `route`; valid: [T] bool,
    the rows that belong to a sequence.  w_gate_up: [E, d, 2f] (an
    expert's gate and up projections side by side), w_down: [E, f, d],
    E the experts held.  `experts_held`: (first, count) of the router's
    experts where the layer holds a share of them (count == E), None
    where it holds them all.
    The (choice, token) pairs are sorted by expert, multiplied, and
    gathered home into [k, T, d] (the module's header); the pairs not
    computed are selected away, the rest weighed and summed over k in
    float32.
    Returns (y [T, d] float32, stats int32 as `STATS` names them: three
    counts, four by a layer told its share).
    """
    t, k = experts.shape
    n_experts, _, f2 = w_gate_up.shape
    # choice-major: pair p = j * T + t is token t's j-th choice
    experts = experts.T                                    # [k, T]
    # the (choice, row) pairs computed here: a sequence's rows, and of
    # those, where the layer holds a share, the picks of held experts
    pair = valid[None, :]
    if experts_held is not None:
        first, count = experts_held
        if count != n_experts:
            raise ValueError(f"{count} experts held, weights of {n_experts}")
        experts = experts - first          # the held ones: 0 .. count - 1
        pair = pair & (experts >= 0) & (experts < count)
    # the other pairs sort past the last expert and into no group
    flat = jnp.where(pair, experts, n_experts).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)[
        :n_experts]
    xs = x[order % t]                                      # [k * T, d]
    gate_up = jax.lax.ragged_dot(xs, w_gate_up, sizes,
                                 preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate_up[:, :f2 // 2])
              * gate_up[:, f2 // 2:]).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, sizes,
                             preferred_element_type=jnp.float32)
    # home through the inverse permutation (a scatter, not a second
    # sort) as [k, T, d], a bitcast while T is a multiple of the 8-row
    # tile; weighed, and a row past the groups is whatever the product
    # left there, so it is selected away, not multiplied away
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(k * t, dtype=order.dtype), unique_indices=True)
    out = out[back].reshape(k, t, -1)
    out = jnp.where(pair[:, :, None], out * weights.T[:, :, None], 0.0)
    stats = [jnp.sum(sizes), jnp.max(sizes),
             jnp.sum((sizes > 0).astype(jnp.int32))]
    if experts_held is not None:
        stats.append(jnp.sum(valid.astype(jnp.int32)) * k - stats[0])
    stats = jnp.stack(stats)
    return jnp.sum(out, axis=0), stats
