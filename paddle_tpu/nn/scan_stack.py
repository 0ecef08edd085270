"""Scan-over-identical-layers: one traced layer body instead of N.

TPU-first rationale: a 12-48 layer transformer traced layer-by-layer
produces an HLO module whose size (and XLA compile time) grows linearly
with depth, and the first compile dominates time-to-first-step.
Stacking the per-layer parameters on a leading axis and running
`jax.lax.scan` over them keeps the program size constant in depth —
the standard JAX "scan over layers" idiom (cf. flax
`nn.remat_scan`).  The reference has no analogue (per-op CUDA kernels
have no compile step); this is a deliberate architecture divergence.

The whole stack is ONE tape op (`apply_op` over x [, mask] and every
layer parameter), so eager `loss.backward()` differentiates through the
scan and per-parameter grads land on the individual layer Tensors.
"""
import jax
import jax.numpy as jnp

from ..core.registry import apply_op
from ..core.tensor import _wrap_data
from ..core import random as _random
from ..core import autograd


def scan_layer_stack(layers, x, mask=None, remat=False, op_type=None):
    """Apply `layers` (identical-structure Layer instances) sequentially to
    x via one lax.scan.  mask, when given, is passed as each layer's second
    argument (broadcast to all layers).  Each layer's dropout draws from
    its own folded rng key, mirroring the sequential path's decorrelated
    masks (keys differ from the sequential path's draw order, so with
    dropout enabled the two paths are statistically, not bitwise, equal).
    """
    layers = list(layers)
    if len(layers) == 1:
        return layers[0](x) if mask is None else layers[0](x, mask)
    template = layers[0]
    rel_names = [n for n, _ in template.named_parameters()]
    per = len(rel_names)
    flat = []
    for lyr in layers:
        d = dict(lyr.named_parameters())
        if sorted(d) != sorted(rel_names):
            raise ValueError(
                "scan_layer_stack requires identically-structured layers; "
                f"got param sets {sorted(rel_names)} vs {sorted(d)}")
        flat.extend(d[n] for n in rel_names)
    n_layers = len(layers)
    base_key = _random.next_key()

    def fn(xv, *rest):
        if mask is not None:
            mv, pvals = rest[0], rest[1:]
        else:
            mv, pvals = None, rest
        stacked = {
            rel_names[j]: jnp.stack(
                [pvals[i * per + j] for i in range(n_layers)])
            for j in range(per)
        }

        def one(h, xs):
            rel, li = xs
            k = jax.random.fold_in(base_key, li)
            with _random.rng_guard(k), autograd.no_grad():
                t_args = (_wrap_data(h),)
                if mv is not None:
                    t_args += (_wrap_data(mv),)
                out = template.functional_call(
                    {n: _wrap_data(v) for n, v in rel.items()}, *t_args)
            return out._data.astype(h.dtype), None

        if remat:
            one = jax.checkpoint(one)
        out, _ = jax.lax.scan(
            one, xv, (stacked, jnp.arange(n_layers)))
        return out

    args = (x,) + ((mask,) if mask is not None else ()) + tuple(flat)
    return apply_op(op_type or "scan_layer_stack", fn, args, {})
