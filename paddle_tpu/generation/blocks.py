"""What the served models with experts share: the norm, the gated MLP,
the expert half of a layer and the on-device draw of seeded weights.

`latent_moe_model.py` (latent attention), `gqa_window_moe_model.py`
(grouped-query heads, window and full layers) and
`hybrid_ssm_moe_model.py` (state-space layers beside one attention
layer in ten) differ in their mixers and in what a layer caches; their
feed-forward halves, their norms and the way their weights come to be
are one thing, stated here once.
Matrix products accumulate in float32 and round to the model's dtype;
norms and the router are float32.
"""
import math

import jax
import jax.numpy as jnp

from . import moe
from .fused import step_scope

# what a step counts, in the order of its third output:
# generation.moe_* summed over the expert layers (`moe.STATS`: the
# fourth by a model whose layers hold a share of their experts)
HELD_STEP_COUNTERS = tuple(f"generation.moe_{name}" for name in (
    "assignments_total", "assignments_max_expert", "experts_touched",
    "assignments_elsewhere"))
STEP_COUNTERS = HELD_STEP_COUNTERS[:3]


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * gain).astype(x.dtype)


def gated_mlp(x, w_gate_up, w_down):
    """(silu(x W_g) * x W_u) W_d in float32 out; `w_gate_up` holds the
    gate and the up projection side by side."""
    gate_up = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    f = gate_up.shape[-1] // 2
    hidden = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]).astype(x.dtype)
    return jnp.dot(hidden, w_down, preferred_element_type=jnp.float32)


def feed_forward_scope(lp):
    """The part of the step a layer's feed-forward half is (its norm
    and residual with it): "experts" where the layer has a router, else
    "mlp"."""
    return step_scope("experts" if "w_router" in lp else "mlp")


def feed_forward(lp, x, valid, top_k, scaling, scoring="sigmoid_bias",
                 experts_held=None):
    """A layer's feed-forward half over normed rows x [T, d]: the dense
    gated MLP where the layer has no router, else the routed experts
    (`moe.route` in the scoring form `scoring`, `moe.expert_ffn` over
    the experts `experts_held` names, all of them for None) beside the
    shared one; the caller runs it under `feed_forward_scope(lp)`.
    Returns (y [T, d] in x's dtype, stats int32 as `moe.STATS` or
    None)."""
    if "w_router" not in lp:
        return gated_mlp(x, lp["w_gate_up"], lp["w_down"]).astype(
            x.dtype), None
    experts, weights = moe.route(
        x, lp["w_router"], lp.get("router_bias"), top_k, scaling, scoring)
    y, stats = moe.expert_ffn(
        x, experts, weights, valid, lp["experts_gate_up"],
        lp["experts_down"], experts_held)
    y = y + gated_mlp(x, lp["shared_gate_up"], lp["shared_down"])
    return y.astype(x.dtype), stats


def valid_rows(starts, lens, t):
    """[t] bool: the packed rows that belong to a descriptor (the rows
    the experts route), under the experts' scope."""
    with step_scope("experts"):
        row = jnp.arange(t, dtype=jnp.int32)[None, :]
        return jnp.any((row >= starts[:, None])
                       & (row < (starts + lens)[:, None]), axis=0)


class DeviceDraw:
    """Seeded weights drawn ON THE DEVICE in `dtype`: billions of
    normals through numpy on the host would be most of a run's set-up.
    Every draw folds the next number of one count into the seed's key,
    so a model's weights are a function of the seed and of the ORDER of
    its draws."""

    def __init__(self, seed, dtype):
        # any whole number up to a little over 2**31 is a seed
        self._root = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
        self._count = iter(range(1 << 20))
        self._dtype = dtype
        self._draws = {}  # one program a (shape, scale, dtype), not a tensor

    def _key(self):
        return jax.random.fold_in(self._root, next(self._count))

    def w(self, *shape, scale=None, dtype=None):
        """Normals of 1/sqrt(fan-in) unless `scale` says."""
        dtype = self._dtype if dtype is None else dtype
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        key = self._key()
        if (shape, scale, dtype) not in self._draws:
            self._draws[shape, scale, dtype] = jax.jit(
                lambda k: (jax.random.normal(k, shape, jnp.float32)
                           * scale).astype(dtype))
        return self._draws[shape, scale, dtype](key)

    def gain(self, n):
        """A norm's gain, not all ones: a norm whose gain is dropped
        has to show."""
        return 1.0 + 0.1 * jax.random.normal(self._key(), (n,), jnp.float32)

    def uniform(self, n, lo, hi):
        """[n] float32, uniform in [lo, hi)."""
        return jax.random.uniform(self._key(), (n,), jnp.float32, lo, hi)

    def expert_layer(self, d, width, n_experts, n_shared):
        """The router, its correction biases (about a tenth of the
        scores' spread, so that they decide some choices), the experts
        and the shared expert of one layer, in this order."""
        fs = n_shared * width
        return {
            "w_router": self.w(d, n_experts, dtype=jnp.float32),
            "router_bias": self.w(n_experts, scale=0.02, dtype=jnp.float32),
            "experts_gate_up": self.w(n_experts, d, 2 * width),
            "experts_down": self.w(n_experts, width, d),
            "shared_gate_up": self.w(d, 2 * fs),
            "shared_down": self.w(fs, d),
        }
