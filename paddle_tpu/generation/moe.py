"""The serving side's expert layer: sigmoid-scored, bias-corrected top-k
routing and a DROPLESS grouped product over the experts.

    s   = sigmoid(x W_r)                    float32, one score an expert
    pick the k experts with the largest s + b     (b: the router's
          correction biases; they choose, they do not weigh)
    w_i = scale * s_i / sum_chosen s_j
    y   = sum_i w_i Expert_i(x)             every chosen expert computed

No capacity and nothing dropped: the step's (token, expert) pairs are
sorted by expert and each expert multiplies exactly its own rows
(`jax.lax.ragged_dot`, which XLA:TPU lowers to a grouped-matmul kernel
that reads an expert's weights only if it has rows).  Shapes follow the
padded row count alone, so a step's batch, chunk and routing never
retrace.  Rows of the packed axis that belong to no sequence are sorted
past every group: they touch no expert and come back 0.

`distributed/fleet/meta_parallel/moe_layer.py` is the trainer's layer
(top-2, capacity dropping, one-hot dispatch): another thing.
"""
import jax
import jax.numpy as jnp

# what `expert_ffn` counts a layer, in this order: the (token, expert)
# pairs computed, the busiest expert's share of them, the experts that
# got any
STATS = ("assignments", "max_expert", "experts_touched")


def route(x, w_router, bias, top_k, scaling):
    """x: [T, d] -> (experts [T, k] int32, weights [T, k] float32).
    Scores in float32 at full precision whatever x's dtype: a router
    whose near-ties fall with the matmul's rounding picks other
    experts than the model it stands for."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_router,
                               precision="highest"))
    _, experts = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    weights = scaling * chosen / jnp.sum(chosen, axis=1, keepdims=True)
    return experts.astype(jnp.int32), weights


def expert_ffn(x, experts, weights, valid, w_gate_up, w_down):
    """The routed experts' part of the layer's output.

    x: [T, d]; experts/weights: [T, k] from `route`; valid: [T] bool,
    the rows that belong to a sequence.  w_gate_up: [E, d, 2f] (an
    expert's gate and up projections side by side), w_down: [E, f, d].
    Returns (y [T, d] float32, stats [3] int32 as `STATS` names them).
    """
    t, k = experts.shape
    n_experts, _, f2 = w_gate_up.shape
    # padding rows sort past the last expert and into no group
    flat = jnp.where(valid[:, None], experts, n_experts).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)[
        :n_experts]
    xs = x[order // k]                                     # [T * k, d]
    gate_up = jax.lax.ragged_dot(xs, w_gate_up, sizes,
                                 preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate_up[:, :f2 // 2])
              * gate_up[:, f2 // 2:]).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, sizes,
                             preferred_element_type=jnp.float32)
    # back to (token, choice) order, weighed; a row past the groups is
    # whatever the product left there, so it is selected away, not
    # multiplied away
    out = out[jnp.argsort(order)].reshape(t, k, -1)
    out = jnp.where(valid[:, None, None], out * weights[:, :, None], 0.0)
    stats = jnp.stack([jnp.sum(sizes), jnp.max(sizes),
                       jnp.sum((sizes > 0).astype(jnp.int32))])
    return jnp.sum(out, axis=1), stats
