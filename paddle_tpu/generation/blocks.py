"""What the served models with experts share: the norm, the gated MLP,
the expert half of a layer, the hyper-connections of a residual several
streams wide and the on-device draw of seeded weights.

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


def gated_mlp(x, w_gate_up, w_down, swiglu_limit=None):
    """(silu(x W_g) * x W_u) W_d in float32 out; `w_gate_up` holds the
    gate and the up projection side by side (`moe.swiglu`, clamped by
    `swiglu_limit` where given)."""
    gate_up = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    hidden = moe.swiglu(gate_up, swiglu_limit).astype(x.dtype)
    return jnp.dot(hidden, w_down, preferred_element_type=jnp.float32)


def feed_forward_scope(lp):
    """The part of the step a layer's feed-forward half is (its norm
    and residual with it): "experts" where the layer has a router, else
    "mlp"."""
    return step_scope("experts" if "w_router" in lp else "mlp")


def feed_forward(lp, x, valid, top_k, scaling, scoring="sigmoid_bias",
                 experts_held=None, swiglu_limit=None):
    """A layer's feed-forward half over normed rows x [T, d]: the dense
    gated MLP where the layer has no router, else the routed experts
    (`moe.route` in the scoring form `scoring`, `moe.expert_ffn` over
    the experts `experts_held` names, all of them for None) beside the
    shared one; the caller runs it under `feed_forward_scope(lp)`.
    Returns (y [T, d] in x's dtype, stats int32 as `moe.STATS` or
    None)."""
    if "w_router" not in lp:
        return gated_mlp(x, lp["w_gate_up"], lp["w_down"],
                         swiglu_limit).astype(x.dtype), None
    experts, weights = moe.route(
        x, lp["w_router"], lp.get("router_bias"), top_k, scaling, scoring)
    y, stats = moe.expert_ffn(
        x, experts, weights, valid, lp["experts_gate_up"],
        lp["experts_down"], experts_held, swiglu_limit)
    y = y + gated_mlp(x, lp["shared_gate_up"], lp["shared_down"],
                      swiglu_limit)
    return y.astype(x.dtype), stats


def hyper_coefficients(streams, hc, eps, sinkhorn_eps, magnitude, rounds):
    """The mixing of one sublayer's hyper-connections (mHC's form), a
    token at a time, float32: streams [T, n, d] ->

        u = rms(vec X);  a = alpha * (u Phi) + b     (Phi: [n d, n (n + 2)])
        h_pre = sigmoid(a[:n]);  h_post = magnitude * sigmoid(a[n:2n])
        H_res = Sinkhorn(exp(a[2n:] as n x n))       doubly stochastic

    `hc`: {"phi", "alpha", "bias"}; the Sinkhorn alternates `rounds`
    normalisations of rows and of columns, `sinkhorn_eps` under each
    sum.  Returns (h_pre [T, n], h_post [T, n], h_res [T, n, n])."""
    t, n, d = streams.shape
    x = streams.astype(jnp.float32).reshape(t, n * d)
    u = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    a = jnp.dot(u, hc["phi"], precision="highest") * hc["alpha"] + hc["bias"]
    m = jnp.exp(a[:, 2 * n:].reshape(t, n, n))

    def normalise(_, m):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + sinkhorn_eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + sinkhorn_eps)

    m = jax.lax.fori_loop(0, rounds, normalise, m)
    return (jax.nn.sigmoid(a[:, :n]),
            magnitude * jax.nn.sigmoid(a[:, n:2 * n]), m)


def hyper_connection(streams, hc, sublayer, eps, sinkhorn_eps=1e-6,
                     magnitude=2.0, rounds=20):
    """One sublayer F behind hyper-connections: the residual is n
    streams X [T, n, d] in their own dtype, and

        X <- H_res X + h_post (x) F(sum_i h_pre_i X_i)

    with the coefficients of `hyper_coefficients` (float32, the sums
    over the streams elementwise in float32).  `sublayer(x)` returns
    (F(x) [T, d], aux); returns (X', aux).  The mixing is the step's
    part `hyper`, F's own work whatever part F names."""
    with step_scope("hyper"):
        h_pre, h_post, h_res = hyper_coefficients(
            streams, hc, eps, sinkhorn_eps, magnitude, rounds)
        x32 = streams.astype(jnp.float32)
        x = jnp.sum(h_pre[:, :, None] * x32, axis=1).astype(streams.dtype)
    y, aux = sublayer(x)
    with step_scope("hyper"):
        mixed = jnp.sum(h_res[:, :, :, None] * x32[:, None, :, :], axis=2)
        out = mixed + h_post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.astype(streams.dtype), aux


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

    def expert_layer(self, d, width, n_experts, n_shared, router_width=None):
        """The router, its correction biases (about a tenth of the
        scores' spread, so that they decide some choices), the experts
        and the shared expert of one layer, in this order.  The router
        is `router_width` wide where the layer holds `n_experts` of
        them, else `n_experts`."""
        fs = n_shared * width
        router_width = router_width or n_experts
        return {
            "w_router": self.w(d, router_width, dtype=jnp.float32),
            "router_bias": self.w(router_width, scale=0.02,
                                  dtype=jnp.float32),
            "experts_gate_up": self.w(n_experts, d, 2 * width),
            "experts_down": self.w(n_experts, width, d),
            "shared_gate_up": self.w(d, 2 * fs),
            "shared_down": self.w(fs, d),
        }
